"""The durable cell end to end on the CPU (PR 35): `benchmarks/run.py
--workload topic_durable_acked --scale small` exits 0, `correct`, nothing
left unsettled; the untraced line carries the cell's end-to-end metrics and
the traced line all six per-layer metrics of the log and the settle path
(per-layer metrics are on a `--trace 1` line only), read from the counters
this PR added to `Metrics`. In a file of its own, so that another worker
than the benchmark's own tests' takes it."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "topic_durable_acked"
NEW_METRICS = {
    "wal_records_per_delivery": "records",
    "wal_queue_msgs_per_delivery": "rows",
    "wal_msgs_per_commit": "rows",
    "wal_commit_share": "%",
    "settle_us_per_ack": "us",
    "settle_rows_per_ack": "rows",
}
LIMIT_S = 300  # the test's own: a run takes ~15 s, ~25 with the priming run
# a traced run reads its counters over the window's untraced part, here 3 s:
# what is in flight at its two edges (a read chunk of acks whose settle
# callbacks have not run yet, the deliveries a commit has not covered yet)
# then stays inside the tenth the ratios below allow
SECONDS = {0: "3", 1: "6"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_durable_cell_runs_correct_on_the_cpu(trace, tmp_path):
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL,
         "--seed", str(2**31 + 35 + trace), "--seconds", SECONDS[trace],
         "--scale", "small", "--trace", str(trace),
         "--out", str(tmp_path / "out")],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=LIMIT_S)
    output = proc.stdout + proc.stderr[-3000:]
    assert proc.returncode == 0, output
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0, output
    assert last["attempted"] > 1000
    assert list(last["compared"]) == [
        "unconfirmed", "missing", "unexpected", "duplicates", "unsettled"]
    assert all(pair == {"value": 0, "limit": 0}
               for pair in last["compared"].values()), output
    assert "wal_commit_errors=0" in output
    metrics = last["metrics"]
    if not trace:
        assert set(metrics) == {"delivered_msgs_per_s", "setup_s"}
        assert metrics["delivered_msgs_per_s"]["value"] > 0
        return
    assert {name: metrics[name]["unit"] for name in NEW_METRICS
            if name in metrics} == NEW_METRICS, output
    value = {name: metrics[name]["value"] for name in NEW_METRICS}
    # a row is committed for every delivery, but for what is in flight at
    # the window's two edges
    assert 0.9 <= value["wal_queue_msgs_per_delivery"] <= 1.1, value
    # and a settle row for every ack, but for the acks of a read chunk whose
    # queues' settle callbacks run after the snapshot
    assert 0.9 <= value["settle_rows_per_ack"] <= 1.1, value
    assert value["wal_records_per_delivery"] >= \
        value["wal_queue_msgs_per_delivery"]
    assert value["wal_msgs_per_commit"] > 1  # a group commit is a group
    assert 0 < value["wal_commit_share"] < 100
    assert value["settle_us_per_ack"] > 0
    assert metrics["dispatch_run_share"]["value"] == 0  # acked: no head run
    # persistent: the enqueue run takes none, not even what routes nowhere
    assert metrics["enqueue_run_share"]["value"] == 0
    assert metrics["router_fallback_share"]["value"] == 0
