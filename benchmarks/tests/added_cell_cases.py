"""A deployment added as files, the way a `model_config` PR adds one: in a
temporary copy of benchmarks/ and BENCHMARK.json, with nothing but additions
— a configuration with an `applied` section, a traffic mix, a per-layer
metric over a counter no program serves, their entries, and the cell's name
appended to every list of the metrics it reports. The copy's own structural
tests (one pytest child over the copy's benchmarks/tests) must then pass as
they stand, the copy must differ from the repository by the added files and
BENCHMARK.json alone, and run.py must run the added cell correct, settled,
with the unserved metric left out of its line.

This is the guard for every PR that adds a cell: a test beside the harness
that pins the benchmark to the cells of its day (a set equality over
BENCHMARK.json, a literal list of cells) fails here first.

test_benchmark.py imports these tests (see graph_cases.py for why); nothing
here is a fixture, what several tests share is made once by a cached
function.
"""

from __future__ import annotations

import atexit
import copy
import functools
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import xml.etree.ElementTree as ElementTree

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, HERE)

import rehearsal  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)
with open(os.path.join(HERE, "data", "parent_client_calls.json"),
          encoding="utf-8") as _f:
    RECORDED = list(json.load(_f))

# names no file of the benchmark and no rehearsal has
CONFIG, TRAFFIC, CELL = "added-cell-durable", "added_cell_keys", "added_cell"
METRIC = "added_cell_records_per_msg"
MOVED = "delivered_msgs_per_s"
ADDED_FILES = {f"configs/{CONFIG}.json", f"traffic/{TRAFFIC}.json",
               f"layer_metrics/{METRIC}.json"}

# the copy's structural tests, and how many cases of each the copy holds
# (counted from what the repository holds today, so that a cell added to the
# repository moves them and fails nothing): the repaired three with what
# they gained, the names test, and the added cell's own cases of the two
# tests parametrised over the cells
_CONFIG_OF = {c["name"]: c["config"] for c in BENCHMARK["workloads"]}
STRUCTURAL = {
    "test_an_accepted_cell_makes_the_parents_client_calls": 3 * len(RECORDED),
    "test_an_unrecorded_cell_makes_the_calls_its_file_states":
        len(set(_CONFIG_OF) - set(RECORDED)) + 1,
    "test_an_accepted_configuration_resolves_to_the_defaults":
        len({_CONFIG_OF[cell] for cell in RECORDED}),
    "test_a_configuration_resolves_and_builds_its_table":
        len(BENCHMARK["configs"]) + 1,
    "test_a_launch_metric_names_a_reader_and_matches_its_entry": 6,
    "test_every_name_in_benchmark_json_is_there_and_well_formed": 1,
    "test_reference_agrees_with_the_programs_matchers": 1,
    "test_generators_are_pure_functions_of_the_seed": 1,
}
PER_CELL = ("test_reference_agrees_with_the_programs_matchers",
            "test_generators_are_pure_functions_of_the_seed")


def rehearsal_json(kind: str, name: str) -> dict:
    with open(os.path.join(rehearsal.DATA, kind, f"{name}.json"),
              encoding="utf-8") as f:
        return json.load(f)


def added_benchmark() -> dict:
    """BENCHMARK.json as the adding PR leaves it: entries appended, no
    entry that is there changed but by its `workloads` growing."""
    bench = copy.deepcopy(BENCHMARK)
    bench["configs"].append({
        "name": CONFIG, "source": "benchmarks/tests/added_cell_cases.py",
        "file": f"benchmarks/configs/{CONFIG}.json", "reduced": [],
        "why": "a durable, acknowledged deployment added as files"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "3 producers closed loop, 1 consumer acking each delivery"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric.get("moves", metric["name"]) == MOVED:
            metric["workloads"].append(CELL)
    bench["per_layer"].append({
        "name": METRIC, "unit": "records/msg", "better": "lower",
        "source": "program_counter", "layer": "store and log",
        "moves": MOVED, "workloads": [CELL]})
    return bench


@functools.lru_cache(maxsize=None)
def added_copy() -> str:
    """The copy, made once for the session's tests: benchmarks/ whole (its
    tests too), then the three added files."""
    parent = tempfile.mkdtemp(prefix="added-cell-")
    atexit.register(shutil.rmtree, parent, ignore_errors=True)
    dest = rehearsal.checkout(os.path.join(parent, "checkout"),
                              added_benchmark(), tests=True)
    files = {
        f"configs/{CONFIG}.json": dict(
            rehearsal_json("configs", "rehearsal-durable"), name=CONFIG),
        f"traffic/{TRAFFIC}.json": dict(
            rehearsal_json("traffic", "rehearsal_durable_keys"),
            name=TRAFFIC),
        f"layer_metrics/{METRIC}.json": {
            "name": METRIC, "layer": "store and log", "unit": "records/msg",
            "moves": MOVED, "reader": "admin_delta_ratio_optional",
            "params": {"num": "metrics.added_cell_no_such_counter",
                       "den": "metrics.published_msgs"},
            "reads": "a counter no program serves: left out of every line"}}
    assert set(files) == ADDED_FILES
    for path, doc in files.items():
        with open(os.path.join(dest, "benchmarks", path), "x",
                  encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
    return dest


def hashes(bench_dir: str) -> dict:
    """{path under bench_dir: SHA-256} of every file but Python's caches."""
    out = {}
    for folder, folders, names in os.walk(bench_dir):
        folders[:] = [d for d in folders if d != "__pycache__"]
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, bench_dir)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def assert_only_appended(old, new, where: str = "BENCHMARK.json") -> None:
    """`new` is `old` with entries appended to its lists and nothing else."""
    if isinstance(old, dict):
        assert isinstance(new, dict) and list(new) == list(old), where
        for key in old:
            assert_only_appended(old[key], new[key], f"{where}.{key}")
    elif isinstance(old, list):
        assert isinstance(new, list) and len(new) >= len(old), where
        for i, entry in enumerate(old):
            assert_only_appended(entry, new[i], f"{where}[{i}]")
    else:
        assert new == old, where


def test_the_copy_differs_by_added_files_and_benchmark_json_alone():
    ours, theirs = hashes(BENCH), hashes(os.path.join(added_copy(),
                                                      "benchmarks"))
    assert {path for path in ours if theirs.get(path) != ours[path]} == set()
    assert set(theirs) - set(ours) == ADDED_FILES
    with open(os.path.join(added_copy(), "BENCHMARK.json"),
              encoding="utf-8") as f:
        theirs_json = json.load(f)
    assert theirs_json != BENCHMARK
    assert_only_appended(BENCHMARK, theirs_json)
    grown = [m["name"] for m in theirs_json["end_to_end"]
             + theirs_json["per_layer"] if CELL in m.get("workloads", [])]
    # the launch metrics among them, and no `.paced` twin
    assert {"router_launch_dispatch_us", "router_launch_wait_us",
            "router_route_share", MOVED, METRIC} <= set(grown)
    assert not [name for name in grown if name.endswith(".paced")]
    assert "setup_s" not in grown  # no list: it applies to every cell


@functools.lru_cache(maxsize=None)
def structural_outcomes() -> "tuple[dict, str]":
    """({test function: [outcome of each of its cases]}, pytest's output)
    of the copy's own structural tests, run once in a child from the copy's
    root: the copy's tests read the copy's files, not the repository's."""
    names = " or ".join(f"({name} and {CELL})" if name in PER_CELL else name
                        for name in STRUCTURAL)
    # the cases below carry those names as their ids: not them, in the copy
    names = f"({names}) and not test_the_copys_structural_tests"
    report = os.path.join(os.path.dirname(added_copy()), "structural.xml")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "no:xdist", f"--junitxml={report}",
         "-k", names, "benchmarks/tests/test_benchmark.py",
         "benchmarks/tests/test_launch_metrics.py"],
        cwd=added_copy(), env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    output = proc.stdout[-6000:] + proc.stderr[-2000:]
    outcomes: dict = {name: [] for name in STRUCTURAL}
    for case in ElementTree.parse(report).iter("testcase"):
        bad = [child.tag for child in case
               if child.tag in ("failure", "error", "skipped")]
        outcomes.setdefault(case.get("name").split("[")[0], []).append(
            bad[0] if bad else "passed")
    return outcomes, output


@pytest.mark.parametrize("name", list(STRUCTURAL))
def test_the_copys_structural_tests_pass_as_they_stand(name):
    outcomes, output = structural_outcomes()
    assert set(outcomes) == set(STRUCTURAL), output
    assert outcomes[name] == ["passed"] * STRUCTURAL[name], output


def test_the_added_cell_runs_correct_and_leaves_the_unserved_metric_out():
    """One traced run of the added cell on the CPU, from the copy."""
    out_dir = tempfile.mkdtemp(dir=os.path.dirname(added_copy()))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL,
         "--seed", str(2**31 + 31), "--seconds", "2", "--scale", "small",
         "--trace", "1", "--out", out_dir],
        cwd=added_copy(), env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    output = proc.stdout + proc.stderr[-3000:]
    assert proc.returncode == 0, output
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0, output
    assert last["compared"]["unsettled"] == {"value": 0, "limit": 0}
    assert last["attempted"] > 1000
    carried = [m["name"] for m in added_benchmark()["per_layer"]
               if CELL in m["workloads"]]
    readers = {}
    for name in carried:
        with open(os.path.join(added_copy(), "benchmarks", "layer_metrics",
                               f"{name}.json"), encoding="utf-8") as f:
            readers[name] = json.load(f)["reader"]
    # off the TPU no trace-read metric is reported; the unserved counter's
    # is left out; every other one of the cell is on the line
    assert METRIC in carried and METRIC not in last["metrics"]
    assert set(last["metrics"]) == {
        name for name in carried
        if readers[name] != "trace" and name != METRIC}
    assert len(last["metrics"]) > 8


# test_benchmark.py star-imports this module: the tests alone ride along
__all__ = [name for name in dir() if name.startswith("test_")]
