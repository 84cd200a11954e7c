"""The benchmark's own tests (`python -m pytest benchmarks/tests -q`, on the
CPU): the yardstick agrees with the program's matchers today, is a pure
function of --seed, does its arithmetic right, reduces a recorded chip trace
to known numbers, names only files that exist, prints the contract's last
line — and calls a run not correct when the control stands in the program's
place or a fault sits under the timed path.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import reference  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)
CELLS = [(c["name"], c["config"], c["traffic"]) for c in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def small(config: str, traffic: str, seed: int):
    cfg = reference.load_config(config, "small")
    mix = reference.load_traffic(traffic, "small")
    table = reference.build_table(cfg)
    mix["pool_size"], mix["pool_seed"] = 600, seed
    pool = reference.build_pool(cfg, table, mix)
    return cfg, mix, table, pool


@pytest.mark.parametrize("cell,config,traffic", CELLS)
def test_reference_agrees_with_the_programs_matchers(cell, config, traffic):
    from chanamq_tpu.broker.matchers import matcher_for

    _, _, table, pool = small(config, traffic, seed=5)
    matcher = matcher_for(table["type"])
    for key, queue, args in table["bindings"]:
        matcher.bind(key, queue, args)
    queue_id = {q: i for i, q in enumerate(table["queues"])}
    fast = reference.expected_sets(table, pool)
    plain = reference.expected_sets_plain(table, pool)
    reached = 0
    for i, (key, headers) in enumerate(pool):
        want = frozenset(queue_id[q] for q in matcher.route(key, headers))
        assert fast.of(i) == want, (key, headers)
        assert frozenset(queue_id[q] for q in plain[i]) == want
        reached += len(want)
    assert reached > len(pool) // 4  # the pool does aim at the table


def test_topic_definition_on_the_grammar():
    cases = [("a.*.c", "a.b.c", True), ("a.*.c", "a.c", False),
             ("a.#", "a", True), ("a.#", "a.b.c.d", True), ("#.z", "z", True),
             ("#.z", "y.z.z", True), ("*.b.#", "b", False),
             ("a.#.c.#", "a.x.c", True), ("a.b", "a.b.c", False)]
    for pattern, key, want in cases:
        assert reference.topic_matches(pattern, key) is want, (pattern, key)
    table = {"exchange": "x", "type": "topic", "queues": ["q"],
             "bindings": [(p, "q", None) for p, _, _ in cases]}
    pool = [(k, None) for _, k, _ in cases]
    fast = reference.expected_sets(table, pool)
    plain = reference.expected_sets_plain(table, pool)
    assert [bool(fast.of(i)) for i in range(len(pool))] == \
        [bool(s) for s in plain]


@pytest.mark.parametrize("cell,config,traffic", CELLS)
def test_generators_are_pure_functions_of_the_seed(cell, config, traffic):
    big = 2**31 + 12345  # more than 32 signed bits hold
    a = small(config, traffic, big)
    b = small(config, traffic, big)
    c = small(config, traffic, big + 1)
    assert a[2] == b[2] == c[2]          # the table is the configuration's
    assert a[3] == b[3] and a[3] != c[3]  # the pool follows the mix's seed
    assert len({repr(e) for e in a[3]}) == len(a[3])
    mix = a[1]  # and --seed orders the stream
    one, two = reference.stream_draws(mix, big), reference.stream_draws(mix, big)
    assert np.array_equal(one, two)
    assert not np.array_equal(one, reference.stream_draws(mix, big + 1))
    assert 0 <= one.min() and one.max() < mix["pool_size"]
    # the first producer's warm-up bursts: distinct keys outside the fleet
    bursts = reference.warmup_bursts(mix)
    assert bursts[-1] == mix["confirm_window"] and bursts[0] == 16
    hot = mix.get("hot_size", 0)
    opening = one[:sum(bursts) * mix["producers"]:mix["producers"]]
    assert opening.min() >= hot
    assert len(set(opening[:mix["pool_size"] - hot].tolist())) == \
        min(len(opening), mix["pool_size"] - hot)
    fleet = reference.stream_draws(
        dict(mix, pool_size=65536, hot_size=2048, hot_share=0.98), big)
    assert (fleet < 2048).mean() == pytest.approx(0.98, abs=0.002)
    assert np.bincount(fleet[fleet < 2048], minlength=2048).min() > 0


def test_percentile_rate_and_compare_arithmetic():
    ns = np.arange(1, 101, dtype=np.int64) * 1_000_000  # 1..100 ms
    assert reference.percentile_ms(ns, 50) == pytest.approx(50.5)
    assert reference.percentile_ms(ns, 95) == pytest.approx(95.05)
    assert reference.rate_per_s(123_456, 10.0) == pytest.approx(12_345.6)

    def pair(queue, seq):
        return (queue << 32) | seq

    expected = np.array(sorted([pair(0, 1), pair(0, 2), pair(1, 2),
                                pair(2, 3)]), dtype=np.uint64)
    numbers, bad = reference.compare(expected, expected[::-1].copy(), 3, 3)
    assert reference.is_correct(numbers) and bad.size == 0
    delivered = np.array([pair(0, 1), pair(0, 1), pair(1, 2), pair(2, 3),
                          pair(3, 3)], dtype=np.uint64)
    numbers, bad = reference.compare(expected, delivered, 4, 3)
    assert numbers == {"unconfirmed": 1, "missing": 1, "unexpected": 1,
                       "duplicates": 1}
    assert not reference.is_correct(numbers)
    assert sorted(bad.tolist()) == [1, 2, 3]
    report = reference.compared_report(numbers)
    # the four every cell compares; `unsettled` only where consumers ack
    assert list(report) == list(reference.LIMITS)[:4] == list(numbers)
    assert reference.is_correct(dict(numbers, missing=0, unexpected=0,
                                     duplicates=0, unconfirmed=0))
    acked = reference.compared_report(dict(numbers, unsettled=3))
    assert list(acked) == list(reference.LIMITS)
    assert acked["unsettled"] == {"value": 3, "limit": 0}
    assert not reference.is_correct({"unsettled": 1})
    assert all(v["limit"] == 0 for v in report.values())


def test_trace_reduction_on_a_recorded_chip_trace():
    """benchmarks/tests/data/topic_trace.json: the device plane's ops and
    the host's events of a few launches of topic_fresh_keys on a TPU v5
    lite, cut from this PR's first traced run."""
    with open(os.path.join(HERE, "data", "topic_trace.json"),
              encoding="utf-8") as f:
        recorded = json.load(f)
    planes = {name: {"ops": [tuple(e) for e in p["ops"]],
                     "launches": p["launches"]}
              for name, p in recorded["planes"].items()}
    host = {thread: [tuple(e) for e in events]
            for thread, events in recorded["host"].items()}
    out = trace_reduce.reduce_events(planes, host)
    for key, want in recorded["expect"].items():
        assert out[key] == pytest.approx(want), key
    assert 0 < out["busy_s"] <= out["device_op_s"] + 1e-12
    assert out["busy_s"] < out["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    # every idle instant goes to one name: together they are the idle time
    idle = sum(s for _, s in out["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"])
    named = dict(out["breakdown"]["idle_gaps"])
    assert named["host: PjitFunction(<lambda>)"] > 0.005  # the dispatch
    assert out["breakdown"]["device_ops"][0][0] == "multiply_reduce_fusion"
    # and the pieces: overlapping ops count once, gaps are what is left
    assert trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace_reduce.gaps([(5, 20), (30, 40)], 0, 50) == \
        [(0, 5), (20, 30), (40, 50)]
    assert trace_reduce.top_level([("a", 0, 10), ("b", 2, 3), ("c", 10, 1)]) \
        == [("a", 0, 10), ("c", 10, 1)]
    assert trace_reduce.attribute([(0, 10)], [("x", 2, 3), ("y", 4, 4)]) == {
        trace_reduce.NOTHING: 4, "host: x": 3, "host: y": 3}
    assert trace_reduce.reduce_events({}, {})["busy_s"] == 0.0


def test_roofline_counts_the_real_table():
    table = {"type": "topic", "bindings": [
        ("a.b.c", "q0", None), ("a.*.c", "q1", None), ("x.#", "q2", None)]}
    assert roofline.kernel_rows(table) == [(3, "q1"), (1, "q2")]
    # 2 rows: (3 + 1 words) * 4 B + 2 masks of 4 B; 10 msgs * (3*4 + 4) B
    assert roofline.launch_bytes(table, 10, 3) == 16 + 8 + 160
    peaks = reference.load_json("peaks.json")
    assert roofline.least_seconds(819e9, peaks["TPU v5 lite"]) == \
        pytest.approx(1.0)
    assert "cpu" not in peaks  # an unknown kind is an error, not a default


def test_every_name_in_benchmark_json_is_there_and_well_formed():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    for path in BENCHMARK["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert os.path.isfile(os.path.join(ROOT, BENCHMARK["command"][1]))
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    for config in configs.values():
        assert NAME.match(config["name"])
        with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as f:
            stated = json.load(f)
        assert stated["name"] == config["name"]
        assert stated["reduced"] == config["reduced"]
        assert all(NAME.match(k) for k in config["reduced"])
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for cell in BENCHMARK["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["config"] in configs and cell["chips"] in (1, 4)
        assert len(cell["why"]) <= 200
        assert reference.load_traffic(cell["traffic"])["name"] == \
            cell["traffic"]
    for metric in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
    for metric in BENCHMARK["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.25
    for metric in BENCHMARK["per_layer"]:
        spec = reference.load_json("layer_metrics", f"{metric['name']}.json")
        assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) == \
            (metric["name"], metric["layer"], metric["unit"], metric["moves"])
        assert metric["moves"] in e2e
        assert os.path.isfile(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))


def run_cell(workload: str, *extra: str) -> "tuple[int, dict | None, str]":
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", workload,
         "--seed", str(2**31 + 7), "--seconds", "2", "--scale", "small",
         *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    lines = proc.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1])
    except (ValueError, IndexError):
        last = None
    # the whole of standard output: a traced result line grows with every
    # per-layer metric a cell carries, and the lines before it are looked for
    return proc.returncode, last, proc.stdout + proc.stderr[-3000:]


@pytest.mark.parametrize("cell,trace", [
    ("topic_fresh_keys", 0), ("topic_fresh_keys", 1),
    ("topic_paced", 0), ("topic_paced", 1)])  # a closed and an open loop
def test_a_run_prints_the_contracts_last_line(cell, trace):
    traffic = next(c[2] for c in CELLS if c[0] == cell)
    rate = reference.load_traffic(traffic, "small")["rate"]
    rc, last, output = run_cell(cell, "--trace", str(trace))
    assert rc == 0 and isinstance(last, dict), output
    want = ["correct", "attempted", "failed", "metrics", "device"]
    if trace:
        want.append("breakdown")
    assert list(last) == want + ["compared"], output
    assert last["correct"] is True and last["failed"] == 0, output
    assert last["attempted"] > 1000
    assert last["device"]["platform"] == "cpu"  # named, never passed off
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(last["device"])
    named = BENCHMARK["per_layer"] if trace else BENCHMARK["end_to_end"]
    units = {m["name"]: m["unit"] for m in named
             if cell in m.get("workloads", [cell])}
    assert last["metrics"] and set(last["metrics"]) <= set(units)
    for name, metric in last["metrics"].items():
        assert set(metric) == {"value", "unit"}
        assert metric["unit"] == units[name]
    if trace:
        assert last["device"]["busy_s"] > 0
        assert last["device"]["window_s"] > last["device"]["busy_s"]
        # off the TPU no device metric is reported at all
        assert set(units) - set(last["metrics"]) == {
            name for name in units if reference.load_json(
                "layer_metrics", f"{name}.json")["reader"] == "trace"}
    else:
        assert set(last["metrics"]) == set(units)
        assert all(m["value"] > 0 for m in last["metrics"].values())
        late = float(re.search(r"generator_late_max_ms=([0-9.]+)", output)[1])
        assert (late > 0) == bool(rate)  # only a paced generator can be late
    assert "fan_out=" in output and "published=" in output
    assert "p95_ms=" in output
    assert "ready in" in output and "native (C++ scan/encode)" in output


def test_a_run_without_the_repo_or_the_chip_prints_no_result(tmp_path):
    """Only BENCHMARK.json and `paths`: exit code not 0, no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "topic_fresh_keys", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")


@pytest.mark.parametrize("cell,config,traffic", CELLS)
@pytest.mark.parametrize("control", reference.CONTROLS)
def test_the_control_comes_out_not_correct(control, cell, config, traffic):
    """The reference in the program's place, one guarantee broken, at a
    size a test can hold; on the chip it ran at the cell's own size."""
    for seed in (3, 2**31 + 5, 77):
        _, mix, table, pool = small(config, traffic, seed)
        draws = reference.stream_draws(mix, seed)
        seqs = np.arange(20_000, dtype=np.int64)
        entries = draws[seqs % len(draws)].astype(np.int64)
        exact = reference.expected_sets(table, pool)
        sound, _ = reference.compare(
            exact.pairs(seqs, entries), exact.pairs(seqs, entries),
            len(seqs), len(seqs))
        assert reference.is_correct(sound)
        numbers, _ = reference.compare(
            exact.pairs(seqs, entries),
            reference.control_pairs(control, table, pool, seqs, entries,
                                    exact, mix["confirm_window"]),
            len(seqs), len(seqs))
        assert not reference.is_correct(numbers), (control, seed, numbers)


@pytest.mark.parametrize("cell", [c[0] for c in CELLS])
@pytest.mark.parametrize("fault,shows_in", [
    ("alter_answer", ("missing", "unexpected")),  # an answer altered where
    ("half_batch", ("missing",)),                 # it is produced; half of
])                                                # each batch left out
def test_a_fault_under_the_timed_path_is_not_correct(fault, shows_in, cell):
    """The whole run but the look for a chip, with the router's answers
    broken underneath the timed path (broker_launch.plant)."""
    rc, last, output = run_cell(cell, "--fault", fault)
    assert rc == 0 and isinstance(last, dict), output
    assert last["correct"] is False, output
    assert 0 < last["failed"] <= last["attempted"]
    assert sum(last["compared"][name]["value"] for name in shows_in) > 0


# tier-1 collects this module by name (tests/test_benchmarks_suite.py loads
# test_benchmark and test_launch_metrics and copies their test functions), so
# the tests of the files beside it ride along here
from added_cell_cases import *  # noqa: E402,F401,F403
from deployment_cases import *  # noqa: E402,F401,F403
from graph_cases import *  # noqa: E402,F401,F403
