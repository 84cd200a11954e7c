#!/usr/bin/env bash
# What the tier-1 pytest line does not do by itself: the native library
# rebuilt from clean, the pure-Python twin of the wire codec, the metrics
# registry lint — and, between them, the seed's pytest line as ROADMAP.md
# has it. Every invariant gate is a test (tests/test_soaks.py holds the
# seeded soaks); speed is measured by benchmarks/run.py on the chip.
set -u
cd "$(dirname "$0")/.."

# A broker refuses to boot on a CPU that JAX_PLATFORMS did not ask for
# (chanamq_tpu/device.py). The chip's own check is `python chip_smoke.py`.
export JAX_PLATFORMS=cpu

fail() { echo "tier1: $1 FAILED (rc=$2)" >&2; exit "$2"; }

# A stale .so that quietly fell back to Python would pass every parity test
# while the suite below ran the wrong code: rebuild, then require the load.
if command -v g++ >/dev/null 2>&1 || command -v c++ >/dev/null 2>&1; then
    echo "tier1: native rebuild from clean"
    { make -C native clean && make -C native; } || fail "native build" $?
    python - <<'PY' || fail "native pipeline after a clean build" 1
from chanamq_tpu import native_ext
assert native_ext.available(), "native library failed to load"
assert native_ext.pipeline_available(), "pipeline entry points missing"
PY
    echo "tier1: pure-Python twin of the codec (CHANAMQ_NATIVE=0)"
    timeout -k 10 300 env CHANAMQ_NATIVE=0 python -m pytest \
        tests/test_frame.py tests/test_golden_wire.py -q \
        -p no:cacheprovider -p no:randomly || fail "pure-Python twin" $?
else
    echo "tier1: no C++ compiler, native rebuild skipped"
fi

set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
[ "$rc" -eq 0 ] || fail "pytest" "$rc"

echo "tier1: metrics registry lint (every exported chanamq_* series is in README.md)"
python scripts/metrics_lint.py || fail "metrics lint" $?
echo "tier1: OK"
