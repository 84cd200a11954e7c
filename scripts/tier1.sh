#!/usr/bin/env bash
# Tier-1 gate: the exact ROADMAP.md verify line, then short bench smokes —
# a 2-node cluster run so the binary interconnect (push_many / settle_many
# / deliver_many over the data plane) gets exercised end to end, and a
# stream run for the segmented-log dispatch path (bench.py --stream:
# 1 producer, 3 cursors at first/next/timestamp).
set -u
cd "$(dirname "$0")/.."

# This gate runs on the CPU, and says so once for every smoke below: a
# broker refuses to boot on a CPU that JAX_PLATFORMS did not ask for
# (chanamq_tpu/device.py), and every `python bench.py` line starts brokers.
# The chip's own check is `python chip_smoke.py`, not this script.
export JAX_PLATFORMS=cpu

# Native pipeline gate: rebuild the library from a clean tree so the suite
# below exercises the freshly-built scanner/encoder (a stale .so silently
# falling back to Python would pass every parity test while benching the
# wrong thing). Parity fuzz runs under BOTH backends: native on, and
# CHANAMQ_NATIVE=0 for the pure-Python twin the fallback path depends on.
if command -v g++ >/dev/null 2>&1 || command -v c++ >/dev/null 2>&1; then
    echo "tier1: native rebuild from clean"
    make -C native clean && make -C native || {
        rc=$?
        echo "tier1: native build FAILED (rc=$rc)" >&2
        exit "$rc"
    }
    python - <<'EOF' || { echo "tier1: native pipeline unavailable after clean build" >&2; exit 1; }
from chanamq_tpu import native_ext
assert native_ext.available(), "native library failed to load"
assert native_ext.pipeline_available(), "pipeline entry points missing"
EOF
    echo "tier1: native parity fuzz (both backends)"
    timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
            tests/test_native_pipeline.py tests/test_native.py -q \
            -p no:cacheprovider -p no:randomly || {
        rc=$?
        echo "tier1: native parity fuzz FAILED (rc=$rc)" >&2
        exit "$rc"
    }
    timeout -k 10 300 env JAX_PLATFORMS=cpu CHANAMQ_NATIVE=0 python -m pytest \
            tests/test_frame.py tests/test_golden_wire.py -q \
            -p no:cacheprovider -p no:randomly || {
        rc=$?
        echo "tier1: pure-Python twin (CHANAMQ_NATIVE=0) FAILED (rc=$rc)" >&2
        exit "$rc"
    }
else
    echo "tier1: no C++ compiler — skipping native rebuild gate"
fi

set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
if [ "$rc" -ne 0 ]; then
    echo "tier1: pytest FAILED (rc=$rc)" >&2
    exit "$rc"
fi

echo "tier1: metrics-registry lint (every exported chanamq_* series documented)"
python scripts/metrics_lint.py || {
    rc=$?
    echo "tier1: metrics lint FAILED (rc=$rc) — undocumented Prometheus series" >&2
    exit "$rc"
}

echo "tier1: 2-node cluster bench smoke (5 s)"
BENCH_SECONDS=5 timeout -k 10 120 python bench.py --cluster || {
    rc=$?
    echo "tier1: cluster bench smoke FAILED (rc=$rc)" >&2
    exit "$rc"
}

echo "tier1: traced 2-node cluster smoke (sample-rate 1.0, stitched-trace gate)"
BENCH_TRACE=1 BENCH_SECONDS=5 timeout -k 10 120 python bench.py --cluster || {
    rc=$?
    echo "tier1: traced cluster smoke FAILED (rc=$rc) — no stitched cross-node trace?" >&2
    exit "$rc"
}

echo "tier1: seeded chaos soak smoke (~5 s: partition + owner crash + slow store)"
# health-gated: the soak itself fails (violation -> exit 1) unless both
# nodes report ready before load AND the scripted alert phase fires
# exactly backlog-growth + consumer-stall; the grep double-checks the
# firing set landed in the report rather than the phase being skipped
CHAOS_MESSAGES=80 timeout -k 10 180 python bench.py --chaos --seed 42 \
        | tee /tmp/_t1_chaos.json || {
    rc=$?
    echo "tier1: chaos soak smoke FAILED (rc=$rc) — invariant violation or harness error" >&2
    exit "$rc"
}
grep -q '"fired_rules": \["backlog-growth", "consumer-stall"\]' /tmp/_t1_chaos.json || {
    echo "tier1: chaos soak report missing the exact alert firings" >&2
    exit 1
}
grep -q '"bus_stream_exact": true' /tmp/_t1_chaos.json || {
    echo "tier1: chaos soak event-bus stream did not match the engine history" >&2
    exit 1
}

echo "tier1: overload soak smoke (~7 s: memory-pressure chaos, refuse + recover)"
# the soak itself fails (violation -> exit 1) on confirmed loss, missing
# refusals/paging, or a broken channel.flow resume; the grep double-checks
# the broker stayed under the accounted-byte ceiling in the report
timeout -k 10 180 python bench.py --overload --seed 7 \
        | tee /tmp/_t1_overload.json || {
    rc=$?
    echo "tier1: overload soak smoke FAILED (rc=$rc) — flow-ladder invariant violation" >&2
    exit "$rc"
}
grep -q '"under_hard_limit": true' /tmp/_t1_overload.json || {
    echo "tier1: overload soak exceeded the accounted-byte hard limit" >&2
    exit 1
}
# the ISSUE-15 live-demo path: a consumer on amq.chanamq.event must see
# the stage escalation, the memory-pressure alert and an slo.burn-rate
# event, and the SLO budget must actually draw down
grep -q '"event_stream_ok": true' /tmp/_t1_overload.json || {
    echo "tier1: overload soak event-bus consumer missed a required event" >&2
    exit 1
}
grep -q '"slo_burned": true' /tmp/_t1_overload.json || {
    echo "tier1: overload soak SLO budget never drew down" >&2
    exit 1
}

echo "tier1: elasticity soak smoke (~30 s: join, drain, kill -9, fenced stale owner, x2 runs)"
# the soak itself fails (violation -> exit 1) on confirmed loss, dual
# holders at quiesce, an unfenced stale-epoch ship, a non-contiguous
# stream resume, or same-seed runs whose normalized decision/evacuation
# logs differ; the grep double-checks at least one stale ship was refused
timeout -k 10 300 python bench.py --elastic --seed 11 \
        | tee /tmp/_t1_elastic.json || {
    rc=$?
    echo "tier1: elasticity soak smoke FAILED (rc=$rc) — lifecycle invariant violation" >&2
    exit "$rc"
}
grep -q '"stale_epoch_refused": [1-9]' /tmp/_t1_elastic.json || {
    echo "tier1: elasticity soak never refused a stale-epoch ship" >&2
    exit 1
}

echo "tier1: control soak smoke (~10 s: pre-armed vs reactive spike, x4 runs)"
# the soak itself fails (violation -> exit 1) unless the pre-armed run
# beats the reactive ladder (strictly lower max stage, strictly fewer
# refusals), the same-seed decision logs compare byte-identical, the
# dry run provably mutates nothing and no run loses a confirmed
# message; the grep double-checks the stage delta landed in the report
timeout -k 10 240 python bench.py --control --seed 7 \
        | tee /tmp/_t1_control.json || {
    rc=$?
    echo "tier1: control soak smoke FAILED (rc=$rc) — predictive-control invariant violation" >&2
    exit "$rc"
}
grep -q '"violations": \[\]' /tmp/_t1_control.json || {
    echo "tier1: control soak report carries violations" >&2
    exit 1
}

echo "tier1: control overhead smoke (5 s x2: control plane <= 2%)"
# same retry rationale as the telemetry overhead gate below: the off/on
# delta from two independent runs is noise-prone on shared boxes
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 120 python bench.py --control-overhead; then
        ok=1
        break
    fi
    echo "tier1: control overhead attempt $attempt over budget, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: control overhead smoke FAILED (3 attempts) — control plane cost over budget" >&2
    exit 1
}

echo "tier1: connection-churn smoke (500 cycles: no accounted-bytes leak)"
timeout -k 10 180 python bench.py --churn || {
    rc=$?
    echo "tier1: connection-churn smoke FAILED (rc=$rc) — accounted-bytes leak" >&2
    exit "$rc"
}

echo "tier1: telemetry overhead smoke (5 s x2: per-entity sampling <= 2%)"
# the off/on delta is measured from two independent 5 s runs, so on a
# shared/virtualized box a CPU-steal burst in either run can swamp the
# 2% budget with pure noise (observed swings of +/-10% run to run while
# the sampled tick cost itself is ~50us, 0.05% of a core). Retry up to
# 3 attempts: a real systematic overhead fails every attempt
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 120 python bench.py --telemetry-overhead; then
        ok=1
        break
    fi
    echo "tier1: telemetry overhead attempt $attempt over budget, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: telemetry overhead smoke FAILED (3 attempts) — sampling cost over budget" >&2
    exit 1
}

echo "tier1: profile attribution smoke (5 s: >=5 stages, >=90% CPU attributed, stacks)"
# ledger + stack sampler on, /admin/profile scraped around the load
# window. Retried: the 90% attribution floor is tight when a CPU-steal
# burst lands inside the measurement window on a shared box
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 120 python bench.py --profile; then
        ok=1
        break
    fi
    echo "tier1: profile smoke attempt $attempt failed, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: profile smoke FAILED (3 attempts) — attribution or stacks gate" >&2
    exit 1
}

echo "tier1: profile overhead smoke (5 s x2: cost ledger <= 2%)"
# same retry rationale as the other overhead gates: two independent 5 s
# runs carry +/-10% noise; the ledger's true cost is batch-granular
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 120 python bench.py --profile-overhead; then
        ok=1
        break
    fi
    echo "tier1: profile overhead attempt $attempt over budget, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: profile overhead smoke FAILED (3 attempts) — ledger cost over budget" >&2
    exit 1
}

echo "tier1: event-bus overhead smoke (5 s x2: bus + firehose, nothing bound, <= 2%)"
# same retry rationale as the other overhead gates
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 120 python bench.py --event-overhead; then
        ok=1
        break
    fi
    echo "tier1: event overhead attempt $attempt over budget, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: event overhead smoke FAILED (3 attempts) — bus/firehose cost over budget" >&2
    exit 1
}

echo "tier1: otel overhead smoke (5 s x2: OTLP export vs tracing alone <= 2%)"
# both variants run tracing at the default 1% sample rate; the delta
# isolates the otel layer (header probe + finish-hook enqueue + flusher
# against a dead collector). Same retry rationale as the other gates
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 120 python bench.py --otel-overhead; then
        ok=1
        break
    fi
    echo "tier1: otel overhead attempt $attempt over budget, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: otel overhead smoke FAILED (3 attempts) — OTLP export cost over budget" >&2
    exit 1
}

echo "tier1: SLO overhead smoke (5 s x2: SLI sampler + burn-rate eval <= 2%)"
# same retry rationale as the other overhead gates
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 120 python bench.py --slo-overhead; then
        ok=1
        break
    fi
    echo "tier1: SLO overhead attempt $attempt over budget, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: SLO overhead smoke FAILED (3 attempts) — SLO engine cost over budget" >&2
    exit 1
}

echo "tier1: bench-trajectory regression gate (5 s x2, record + gate)"
# first leg seeds/extends BENCH_trajectory.jsonl (and judges against the
# previous recorded baseline when one exists); second leg re-judges
# against the freshly recorded line — two consecutive --regress runs
# against the same baseline must agree. Both retried for box noise; a
# real regression moves wall AND CPU together and fails every attempt
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 240 python bench.py --regress --record; then
        ok=1
        break
    fi
    echo "tier1: regress record attempt $attempt failed, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: bench regression gate FAILED (3 attempts) — wall+CPU cost regressed" >&2
    exit 1
}
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 240 python bench.py --regress; then
        ok=1
        break
    fi
    echo "tier1: regress gate attempt $attempt failed, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: bench regression re-gate FAILED (3 attempts)" >&2
    exit 1
}

echo "tier1: 2-shard node smoke (5 s x2: multi-process + UDS interconnect)"
# a real multi-process node: supervisor + 2 SO_REUSEPORT workers, queue
# ownership split by the hash ring, cross-shard messages over the Unix
# data plane. Gates on harness health (all shards converge, per-shard
# admin scrape works, no child errors); throughput/speedup are reported,
# not asserted — this box may be single-core
BENCH_SECONDS=5 timeout -k 10 240 python bench.py --shard 2 || {
    rc=$?
    echo "tier1: 2-shard smoke FAILED (rc=$rc)" >&2
    exit "$rc"
}

echo "tier1: WAL kill-9 recovery smoke (confirmed set must survive SIGKILL)"
# pumps publisher confirms against a WAL-backed broker, SIGKILLs it
# mid-stream, restarts on the same data dir and asserts every confirmed
# message is redelivered — a confirm means the group commit fsynced it
timeout -k 10 120 python bench.py --wal-recovery || {
    rc=$?
    echo "tier1: WAL recovery smoke FAILED (rc=$rc) — confirmed messages lost after kill -9" >&2
    exit "$rc"
}

echo "tier1: stream bench smoke (5 s)"
BENCH_SECONDS=5 timeout -k 10 120 python bench.py --stream || {
    rc=$?
    echo "tier1: stream bench smoke FAILED (rc=$rc)" >&2
    exit "$rc"
}

echo "tier1: rpc bench smoke (request-reply, exclusive reply queues)"
BENCH_SECONDS=5 timeout -k 10 120 python bench.py --rpc || {
    rc=$?
    echo "tier1: rpc bench smoke FAILED (rc=$rc)" >&2
    exit "$rc"
}

echo "tier1: dlx/priority scenario smoke (burst drain order + exactly-once DLX)"
# the bench itself fails (exit 1) on any priority inversion, lost or
# duplicated dead-letter, or malformed x-death header
BENCH_SECONDS=5 timeout -k 10 240 python bench.py --dlx || {
    rc=$?
    echo "tier1: dlx/priority smoke FAILED (rc=$rc) — ordering or dead-letter violation" >&2
    exit "$rc"
}

echo "tier1: semantics soak smoke (~8 s: Tx kill at the WAL boundary + TTL DLX under faults)"
# the soak itself fails (violation -> exit 1) on confirmed loss, a
# partially recovered transaction, post-rollback ghosts, or non-exactly-
# once dead-lettering; the grep double-checks both same-seed repeats
# serialized byte-identically
timeout -k 10 300 python bench.py --semantics-soak --seed 42 \
        | tee /tmp/_t1_semantics.json || {
    rc=$?
    echo "tier1: semantics soak smoke FAILED (rc=$rc) — delivery-semantics invariant violation" >&2
    exit "$rc"
}
grep -q '"deterministic": true' /tmp/_t1_semantics.json || {
    echo "tier1: semantics soak repeats were not byte-identical" >&2
    exit 1
}

echo "tier1: semantics overhead smoke (5 s x2: disabled-path cost <= 2%)"
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 120 python bench.py --semantics-overhead; then
        ok=1
        break
    fi
    echo "tier1: semantics overhead attempt $attempt over budget, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: semantics overhead smoke FAILED (3 attempts) — semantics disabled-path cost over budget" >&2
    exit 1
}

echo "tier1: federation soak smoke (~15 s x2: sever mid-stream, failover, heal)"
# two independent clusters joined by one link; the soak itself fails
# (violation -> exit 1) on confirmed loss, a non-contiguous cursor
# resume on the mirror, duplicate post-settle deliveries or a mirror
# audit read that differs from the published set; the greps double-check
# both same-seed repeats serialized byte-identically and violation-free
# retried like the overhead gates: the soak's quiesce/failover waits are
# deadline-based, so a CPU-steal burst on a shared box can time one out;
# a real invariant violation fails every attempt
ok=""
for attempt in 1 2 3; do
    if timeout -k 10 300 python bench.py --federation --seed 42 \
            | tee /tmp/_t1_federation.json \
            && grep -q '"deterministic": true' /tmp/_t1_federation.json \
            && grep -q '"violations": \[\]' /tmp/_t1_federation.json; then
        ok=1
        break
    fi
    echo "tier1: federation soak attempt $attempt failed, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: federation soak smoke FAILED (3 attempts) — cross-cluster invariant violation" >&2
    exit 1
}

echo "tier1: federation overhead smoke (5 s x2: idle-link cost <= 2%)"
# same retry rationale as the other overhead gates: federation is enabled
# with zero links configured, so the per-publish cost is one attribute
# test, but the off/on delta between independent runs is noise-prone
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 120 python bench.py --federation-overhead; then
        ok=1
        break
    fi
    echo "tier1: federation overhead attempt $attempt over budget, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: federation overhead smoke FAILED (3 attempts) — idle-link cost over budget" >&2
    exit 1
}

echo "tier1: route microbench smoke (tensor router vs trie, parity gate)"
# the bench itself fails (exit 1) on any kernel/oracle parity mismatch or
# a broken key-shared fan-out; the grep double-checks both batched paths
# really routed with zero mismatches at every table size
timeout -k 10 240 python bench.py --route --quick \
        | tee /tmp/_t1_route.json || {
    rc=$?
    echo "tier1: route smoke FAILED (rc=$rc) — parity mismatch or fan-out error" >&2
    exit "$rc"
}
grep -q '"parity_mismatches": 0' /tmp/_t1_route.json || {
    echo "tier1: route smoke report missing the zero-mismatch parity gate" >&2
    exit 1
}

echo "tier1: tenant soak smoke (~10 s x2 seeds: noisy neighbor, victim SLO intact)"
# the soak itself fails (violation -> exit 1) unless the aggressor is
# rate-gated at the exact token boundary, its held publishes drain in
# FIFO order across every resume, the memory tenant gates and recovers,
# the victim's p99 and both tenant-scoped SLO budgets stay untouched,
# and the tenant-labelled event/firehose streams match exactly; each
# seed runs twice and the decision logs must be byte-identical. Seeds 5
# and 7 sit in different mod-3 classes so the drain-episode counts differ
for seed in 5 7; do
    timeout -k 10 300 python bench.py --tenant --seed "$seed" \
            | tee /tmp/_t1_tenant.json || {
        rc=$?
        echo "tier1: tenant soak smoke FAILED (rc=$rc, seed=$seed) — isolation invariant violation" >&2
        exit "$rc"
    }
    grep -q '"violations": \[\]' /tmp/_t1_tenant.json || {
        echo "tier1: tenant soak report carries violations (seed=$seed)" >&2
        exit 1
    }
    grep -q '"log_sha256": "[0-9a-f]' /tmp/_t1_tenant.json || {
        echo "tier1: tenant soak report missing the decision-log digest (seed=$seed)" >&2
        exit 1
    }
done

echo "tier1: tenant churn smoke (10k define/remove cycles: no registry or byte leak)"
timeout -k 10 300 python bench.py --tenant-churn \
        | tee /tmp/_t1_tenant_churn.json || {
    rc=$?
    echo "tier1: tenant churn smoke FAILED (rc=$rc) — registry/accounting leak" >&2
    exit "$rc"
}
grep -q '"leaked_bytes": 0' /tmp/_t1_tenant_churn.json || {
    echo "tier1: tenant churn leaked accounted bytes" >&2
    exit 1
}

echo "tier1: tenant overhead smoke (5 s x2: quota-less tenant attach <= 2%)"
# same retry rationale as the other overhead gates: the per-publish cost
# of an unrated tenant is one attribute load + None test, but the off/on
# delta from two independent 5 s runs swings +/-10% on a shared box
ok=""
for attempt in 1 2 3; do
    if BENCH_SECONDS=5 timeout -k 10 120 python bench.py --tenant-overhead; then
        ok=1
        break
    fi
    echo "tier1: tenant overhead attempt $attempt over budget, retrying" >&2
done
[ -n "$ok" ] || {
    echo "tier1: tenant overhead smoke FAILED (3 attempts) — tenancy cost over budget" >&2
    exit 1
}
echo "tier1: OK"
