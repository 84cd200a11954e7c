"""The per-layer metrics over the loop's own books (PR 39): the timed
selector's waits and slow turns, the collector's pauses, the writers' socket
writes, the render and the checkpoint's waits, each a counter of nanoseconds
read as a share of the window by a reader that was here. Every new file
names that reader and agrees with its entry of BENCHMARK.json, a `.paced`
twin takes only the paced cell and a saturated metric none, and over a
program without the counters (the parent commit) every one reads nothing.

Nothing here pins BENCHMARK.json to this PR's day: a later PR appends
entries to `per_layer[]` and cells to a metric's `workloads`, and the last
case runs this module over such a copy (added_cell_cases.py's) to hold that.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import reference  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)

# metric -> (layer, the counter of /admin/overview it reads, better)
TWINNED = {
    "loop_idle_share": ("event loop", "loop_idle_ns", "higher"),
    # the turns over 500 ms, not the operator's 100 ms count: a sound
    # headers_every_flush turn and a full collection pass 100 ms
    "loop_slow_turn_share": ("event loop", "loop_stall_ns", "lower"),
    "gc_pause_share": ("event loop", "gc_pause_ns", "lower"),
    "egress_write_share": ("connection engine", "egress_write_ns", "lower"),
    "egress_render_share": ("connection engine", "egress_render_ns", "lower"),
}
DURABLE_ONLY = {"wal_checkpoint_share": ("log", "wal_checkpoint_ns", "lower")}
SPECS = {**TWINNED, **DURABLE_ONLY,
         **{name + ".paced": spec for name, spec in TWINNED.items()}}
PACED = "topic_paced"
# the cells a saturated metric was listed for when it was added (a later
# cell that reports the rate is appended to these lists): the durable cell
# alone for the checkpoint, and for the idle share, which a saturated
# transient loop reads 0 by construction (it never enters `select` with
# nothing ready)
DURABLE = {"topic_durable_acked"}
SATURATED = DURABLE | {"topic_fresh_keys", "headers_every_flush",
                       "topic_fleet_keys", "topic_graph_fresh_keys"}
LISTED_FOR = {"wal_checkpoint_share": DURABLE, "loop_idle_share": DURABLE}


def cells_reporting(metric: str) -> list:
    (entry,) = [m for m in BENCHMARK["end_to_end"] if m["name"] == metric]
    return entry["workloads"]


@pytest.mark.parametrize("name", sorted(SPECS))
def test_a_loop_metric_names_a_reader_and_matches_its_entry(name):
    layer, counter, better = SPECS[name]
    (entry,) = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    spec = reference.load_json("layer_metrics", f"{name}.json")
    assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) == \
        (entry["name"], entry["layer"], entry["unit"], entry["moves"])
    assert (entry["layer"], entry["unit"], entry["better"]) == \
        (layer, "%", better)
    assert entry["source"] == "program_counter"
    # a reader that was here: this PR adds none
    assert spec["reader"] == "admin_delta_per_second"
    assert callable(importlib.import_module("readers." + spec["reader"]).read)
    assert spec["params"] == {"counter": "metrics." + counter, "scale": 1e-7}
    paced = set(cells_reporting("deliver_latency_p50_ms"))
    if name.endswith(".paced"):
        # a twin lists the paced cell and no saturated one
        assert entry["moves"] == "deliver_latency_p50_ms"
        assert PACED in entry["workloads"]
        assert set(entry["workloads"]) <= paced
    else:
        # a saturated metric no paced cell
        assert entry["moves"] == "delivered_msgs_per_s"
        assert not set(entry["workloads"]) & paced
        assert LISTED_FOR.get(name, SATURATED) <= set(entry["workloads"])
    assert all(cell in cells_reporting(entry["moves"])
               for cell in entry["workloads"])
    # the counter is one the program serves
    from chanamq_tpu.utils.metrics import Metrics

    assert counter in Metrics().snapshot()


def test_every_addition_has_its_file_and_its_entry_once():
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    assert len(SPECS) == 11
    assert all(names.count(name) == 1 for name in SPECS)
    files = set(os.listdir(os.path.join(BENCH, "layer_metrics")))
    assert {name + ".json" for name in SPECS} <= files


def ctx_of(before: dict, after: dict, seconds: float = 1.0) -> dict:
    """A recorded pair of /admin/overview around a window of `seconds`."""
    def snap(ns, metrics):
        return {"ns": ns, "admin": {"metrics": metrics}}

    return {"snaps": {"window0": snap(0, before),
                      "window1": snap(int(seconds * 1e9), after)}}


def read(name: str, ctx: dict):
    spec = reference.load_json("layer_metrics", f"{name}.json")
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(spec["params"], ctx)


@pytest.mark.parametrize("name", sorted(SPECS))
def test_over_a_program_without_the_counters_it_reads_nothing(name):
    """The parent's /admin/overview: the counters it had, none of this
    PR's. The metric is left out of the line and nothing raises."""
    parent = ({"published_msgs": 10, "wal_commit_ns": 5, "wal_checkpoints": 1},
              {"published_msgs": 90, "wal_commit_ns": 9, "wal_checkpoints": 2})
    assert read(name, ctx_of(*parent)) is None


@pytest.mark.parametrize("name", sorted(SPECS))
def test_half_a_second_of_a_one_second_window_reads_fifty(name):
    counter = SPECS[name][1]
    ctx = ctx_of({counter: 250_000_000}, {counter: 750_000_000})
    assert read(name, ctx) == pytest.approx(50.0)
    # a counter that stood still is 0, not nothing: the program has it
    assert read(name, ctx_of({counter: 7}, {counter: 7})) == 0.0


def test_the_cases_above_hold_in_a_copy_that_a_later_pr_appended_to():
    """added_cell_cases.py's scratch checkout: a cell appended to every
    list of the metrics that move the rate (these among them), and a
    per-layer entry after these eleven. Its own rehearsal runs
    test_benchmark.py and test_launch_metrics.py there; this runs the
    cases above, as they stand."""
    import added_cell_cases

    copy = added_cell_cases.added_copy()
    with open(os.path.join(copy, "BENCHMARK.json"), encoding="utf-8") as f:
        theirs = json.load(f)
    assert theirs["per_layer"][-1]["name"] == added_cell_cases.METRIC
    (idle,) = [m for m in theirs["per_layer"]
               if m["name"] == "loop_idle_share"]
    assert idle["workloads"][-1] == added_cell_cases.CELL
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "no:xdist",
         "-k", "not a_later_pr_appended_to",
         "benchmarks/tests/test_loop_metrics.py"],
        cwd=copy, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    output = proc.stdout[-6000:] + proc.stderr[-2000:]
    assert proc.returncode == 0, output
    assert f"{3 * len(SPECS) + 1} passed" in output, output
