"""chip_smoke.py's contract, rehearsed on the CPU: the shape of its last
stdout line, every phase line before it, a parent that stays off jax — and
the rule that decides which platforms a broker may come up on.

Nothing here starts JAX without JAX_PLATFORMS=cpu: with the TPU library
installed and no chip, that start can wait a long time on probes.
"""

import json
import os
import re
import subprocess
import sys

import pytest

from chanamq_tpu.config import ConfigError
from chanamq_tpu.device import check_platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASE_LINES = (
    "chip_smoke: scale=small",
    "native: ",
    "boot1: ready in ",
    "compile cache: dir=",
    "topic: bindings=",
    "headers: bindings=",
    "boot1 forecast: rounds=",
    "boot1: SIGTERM -> exit code 0",
    "boot2: ready in ",
    "replay-topic: bindings=",
    "replay-headers: bindings=",
    "boot2 forecast: rounds=",
    "boot2: SIGTERM -> exit code 0",
    "every phase passed, but on 'cpu'",
)


def test_cpu_rehearsal_ends_with_the_contract_line_and_not_ok(tmp_path):
    cache_dir = tmp_path / "jax-cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    proc = subprocess.run(
        # --no-build: the suite's other workers have native/'s library open
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--scale", "small", "--no-build", "--out", str(tmp_path / "out")],
        env=env, cwd=str(tmp_path), stdin=subprocess.DEVNULL,
        capture_output=True, text=True, timeout=240)
    lines = proc.stdout.splitlines()
    assert lines, proc.stderr[-2000:]
    # the last line: one JSON object, exactly the contract's keys
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"}, lines[-1]
    assert set(last["device"]) == {"platform", "kind", "count"}, lines[-1]
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= 1 and last["device"]["kind"]
    # nothing but a TPU may yield ok: a CPU run is a rehearsal and fails
    assert last["ok"] is False
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    # every phase ran, in order, each on a line of its own before the last
    cursor = 0
    for prefix in PHASE_LINES:
        found = next((i for i in range(cursor, len(lines) - 1)
                      if lines[i].startswith(prefix)), None)
        assert found is not None, (prefix, proc.stdout, proc.stderr[-2000:])
        cursor = found + 1
    # the parent says so itself if it ever imported jax; no phase failed
    assert "FAILED" not in proc.stdout, proc.stdout
    # the deliveries agreed with the Python matchers and the kernels ran
    for label in ("topic", "headers", "replay-topic", "replay-headers"):
        line = next(ln for ln in lines if ln.startswith(label + ": "))
        assert "wrong_queues=0 duplicates=0" in line, line
        assert int(re.search(r"router_kernel_launches=\+(\d+)", line)[1]) > 0
    # JAX_COMPILATION_CACHE_DIR is the only cache either boot used, and the
    # second boot read entries back from it
    assert f"compile_cache={cache_dir}" in "\n".join(lines)
    assert any(name.endswith("-cache") for name in os.listdir(cache_dir))
    boot2 = next(ln for ln in lines if ln.startswith("boot2: SIGTERM"))
    assert int(re.search(r"hits=(\d+)", boot2)[1]) > 0, boot2


def test_smoke_fails_without_the_repo_beside_it(tmp_path):
    """Alone in a directory, the script exits non-zero and names no device."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes(open(os.path.join(REPO, "chip_smoke.py"), "rb").read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(alone)], env=env, cwd=str(tmp_path),
        stdin=subprocess.DEVNULL, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert json.loads(proc.stdout.splitlines()[-1]) == {
        "ok": False, "device": None}


def test_supervisor_and_python_backend_never_import_jax():
    """The shard supervisor and a worker that does not hold the chip must
    stay off JAX: importing the entry point and building a broker on the
    numpy backend leaves jax unimported."""
    code = (
        "import sys\n"
        "import chanamq_tpu.broker.server, chanamq_tpu.shard.supervisor\n"
        "from chanamq_tpu.broker.broker import Broker\n"
        "from chanamq_tpu import device\n"
        "broker = Broker(router_backend='python')\n"
        "assert broker.router.device is None and device.claimed() is None\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_PLATFORMS", None)  # nothing here may need it
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.parametrize("found,asked", [
    ("tpu", None),          # the chip, by JAX's own choice
    ("tpu", "tpu"),
    ("tpu", "tpu,cpu"),
    ("cpu", "cpu"),         # the CPU, because it was asked for
    ("cpu", "CPU"),
    ("cpu", " cpu ,tpu"),   # ... first
])
def test_platform_rule_accepts(found, asked):
    check_platform(found, asked)


@pytest.mark.parametrize("found,asked", [
    ("cpu", None),          # what JAX does by itself when the chip is held
    ("cpu", ""),
    ("cpu", "tpu"),
    ("cpu", "tpu,cpu"),     # a TPU host's own setting: a fallback, not a wish
    ("gpu", None),
    ("cpu", "gpu"),
])
def test_platform_rule_refuses_a_backend_nobody_asked_for(found, asked):
    with pytest.raises(ConfigError, match="JAX_PLATFORMS"):
        check_platform(found, asked)
