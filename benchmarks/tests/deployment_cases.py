"""What a configuration file states of its deployment, applied: the
`applied` section and its defaults, the client calls of the recorded cells
held to the parent harness's (data/parent_client_calls.json) and those of
every other cell to what its configuration's file states, and the two
rehearsal deployments of data/rehearsal (a durable, acknowledged one and a
graph of exchanges) run end to end through run.py in a scratch checkout that
rehearsal.py assembles — correct, on the device path, settled; and not
correct under every control.

What is held of a cell depends on the recording, not on BENCHMARK.json's
length: a PR that adds a cell as files edits nothing here, and its cell gets
the cases of an unrecorded one (added_cell_cases.py adds one in a copy).

test_benchmark.py imports these tests (see graph_cases.py for why). tier-1
collects them through tests/test_benchmarks_suite.py, which copies the test
functions alone, so nothing here is a fixture: what several tests share is
made once by a cached function.
"""

from __future__ import annotations

import atexit
import functools
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import reference  # noqa: E402
import rehearsal  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)
with open(os.path.join(HERE, "data", "parent_client_calls.json"),
          encoding="utf-8") as _f:
    PARENT_CALLS = json.load(_f)
CELLS = {c["name"]: c for c in BENCHMARK["workloads"]}
# the cells the parent harness (4dac9c0) was recorded on, and their
# configurations; a cell added since is held to its file instead
RECORDED = list(PARENT_CALLS)
RECORDED_CONFIGS = list(dict.fromkeys(
    CELLS[cell]["config"] for cell in RECORDED if cell in CELLS))
UNRECORDED = [cell for cell in CELLS if cell not in PARENT_CALLS]
REHEARSALS = ["rehearsal_durable", "rehearsal_graph"]
ROLES = ["declare", "producer", "consumer"]
DECLARE_CONNS = 8  # run.py's: each connection of declare() closes once


# -- the section and its defaults ----------------------------------------------


@pytest.mark.parametrize("config", RECORDED_CONFIGS)
def test_an_accepted_configuration_resolves_to_the_defaults(config):
    """The configurations of the recorded cells: no section, no graph."""
    cfg = reference.load_config(config)
    assert "applied" not in cfg
    assert reference.applied(cfg) == {
        "durable": False, "delivery_mode": None, "consumer_ack": None,
        "broker_options": {}}
    assert "exchanges" not in reference.build_table(
        reference.load_config(config, "small"))


@pytest.mark.parametrize("config", [c["name"] for c in BENCHMARK["configs"]])
def test_a_configuration_resolves_and_builds_its_table(config):
    """What holds of any configuration, whatever it states: the section
    resolves at both scales and the generator makes a table at the small
    one. Where it states `durable` and no `broker_options`, the broker's
    shipped default decides the log: nothing to assert."""
    for scale in ("full", "small"):
        cfg = reference.load_config(config, scale)
        assert set(reference.applied(cfg)) == set(reference.APPLIED_DEFAULTS)
    table = reference.build_table(cfg)
    assert table["queues"] and table["bindings"]
    assert table["type"] in ("direct", "fanout", "topic", "headers")
    assert (table["exchange"], table["type"]) in [
        tuple(e) for e in reference.table_exchanges(table)]


def test_the_section_takes_what_it_knows_and_nothing_else():
    stated = {"durable": True, "delivery_mode": 2,
              "consumer_ack": {"prefetch": 5000, "multiple_every": 1},
              "broker_options": {"chana.mq.wal.enabled": False}}
    assert reference.applied({"applied": stated}) == stated
    assert reference.applied({"applied": {"durable": True}}) == dict(
        reference.APPLIED_DEFAULTS, durable=True)
    for wrong in ({"persistent": True}, {"delivery_mode": 3},
                  {"consumer_ack": {"prefetch": 10}},
                  {"consumer_ack": {"prefetch": 10, "multiple_every": 0}},
                  {"broker_options": {"wal.enabled": True}}):
        with pytest.raises(ValueError):
            reference.applied({"applied": wrong})


# -- the client calls ----------------------------------------------------------


@functools.lru_cache(maxsize=None)
def client_calls(bench_dir: str) -> dict:
    """client_calls.py in a child: it puts a fake in the client's place."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "client_calls.py"), bench_dir],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout)


@pytest.mark.parametrize("role", ROLES)
@pytest.mark.parametrize("cell", RECORDED)
def test_an_accepted_cell_makes_the_parents_client_calls(cell, role):
    """A recorded cell is still a cell (a `benchmark` PR that retires one
    says so here) and makes, name for name and argument for argument, the
    calls the harness made before it could apply a section or a graph."""
    assert cell in CELLS
    now = client_calls(BENCH)
    assert now[cell][role] == PARENT_CALLS[cell][role]
    assert now[cell][role]["calls"] > 8


def stated(bench_dir: str, config: str) -> "tuple[dict, dict]":
    """(resolved `applied` section, table) of a configuration of the
    benchmarks/ directory `bench_dir` at the small scale, the generator
    loaded from that directory by its path."""
    with open(os.path.join(bench_dir, "configs", f"{config}.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    cfg["table"].update(cfg["scales"]["small"])
    generator = cfg["table"]["generator"]
    spec = importlib.util.spec_from_file_location(
        f"stated_tables_{generator}",
        os.path.join(bench_dir, "tables", f"{generator}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return reference.applied(cfg), module.table(cfg["table"])


def assert_calls_follow_the_file(calls: dict, applied: dict,
                                 table: dict) -> None:
    """The calls client_calls.py recorded of one cell are what the
    configuration's `applied` section and its table state: durable declares
    if and only if `durable`; `delivery_mode` on every publish or no such
    property; `basic_qos` and `no_ack=False` if and only if `consumer_ack`;
    every exchange of the table declared first and in its order, and one
    call for each queue, binding and connection."""
    flags = {"durable": True} if applied["durable"] else {}
    exchanges = [list(e) for e in reference.table_exchanges(table)]
    declares = calls["declare"]["first"]
    shown = len(declares)
    assert declares == ([["exchange_declare", e, flags] for e in exchanges] + [
        ["queue_declare", [q], flags] for i in range(DECLARE_CONNS)
        for q in table["queues"][i::DECLARE_CONNS]])[:shown]
    assert calls["declare"]["calls"] == (
        len(exchanges) + len(table["queues"]) + len(table["bindings"])
        + len(table.get("queue_bindings", []))
        + len(table.get("exchange_bindings", [])) + DECLARE_CONNS)
    mode = applied["delivery_mode"]
    publishes = [c for c in calls["producer"]["first"]
                 + calls["producer"]["last"] if c[0] == "basic_publish"]
    assert publishes
    for _, _, kwargs in publishes:
        assert kwargs["exchange"] == table["exchange"]
        properties = dict(kwargs["properties"] or {})
        if table["type"] != "headers":
            assert "headers" not in properties
        properties.pop("headers", None)
        assert properties == ({} if mode is None else {"delivery_mode": mode})
    consumes = calls["consumer"]["first"] + calls["consumer"]["last"]
    ack = applied["consumer_ack"]
    assert [c for c in consumes if c[0] == "basic_qos"] == (
        [["basic_qos", [], {"prefetch_count": ack["prefetch"]}]] if ack
        else [])
    if ack:
        assert consumes[0][0] == "basic_qos"
    subscribed = [c for c in consumes if c[0] == "basic_consume"]
    assert subscribed
    assert all(c[2]["no_ack"] is (ack is None) for c in subscribed)


@pytest.mark.parametrize("cell", UNRECORDED)
def test_an_unrecorded_cell_makes_the_calls_its_file_states(cell):
    """A cell added since the recording (none today: pytest then reports
    one skipped case for the empty list)."""
    calls = client_calls(BENCH)[cell]
    assert_calls_follow_the_file(calls, *stated(BENCH, CELLS[cell]["config"]))
    assert all(calls[role]["calls"] > 8 for role in ROLES)


@pytest.mark.parametrize("cell", REHEARSALS)
def test_a_rehearsal_makes_the_calls_its_file_states(cell):
    bench_dir = os.path.join(tree(), "benchmarks")
    calls = client_calls(bench_dir)[cell]
    (config,) = [c["config"] for c in rehearsal.benchmark_json()["workloads"]
                 if c["name"] == cell]
    assert_calls_follow_the_file(calls, *stated(bench_dir, config))
    # and, written out, what the two files state
    declare = [tuple(c[:2]) + (json.dumps(c[2], sort_keys=True),)
               for c in calls["declare"]["first"]]
    publish = calls["producer"]["last"][-1]
    consume = calls["consumer"]["first"]
    if cell == "rehearsal_durable":
        assert declare[0] == ("exchange_declare", ["bench.topic", "topic"],
                              '{"durable": true}')
        assert declare[1] == ("queue_declare", ["tq0"], '{"durable": true}')
        assert publish[2]["properties"] == {"delivery_mode": 2}
        assert consume[0] == ["basic_qos", [], {"prefetch_count": 5000}]
        assert consume[1][2] == {"consumer_tag": "tq0", "no_ack": False}
    else:
        assert declare[0] == ("exchange_declare", ["bench.graph", "topic"],
                              "{}")
        assert declare[1] == ("exchange_declare",
                              ["bench.graph.commands", "direct"], "{}")
        assert publish[2]["properties"] is None
        assert consume[0][2]["no_ack"] is True
        # 10 exchanges, 88 queues, 160 queue and 104 exchange bindings,
        # 8 closes
        assert calls["declare"]["calls"] == 10 + 88 + 160 + 104 + 8


# -- the rehearsal runs --------------------------------------------------------


@functools.lru_cache(maxsize=None)
def tree() -> str:
    """The scratch checkout, made once for the session's tests."""
    parent = tempfile.mkdtemp(prefix="rehearsal-")
    atexit.register(shutil.rmtree, parent, ignore_errors=True)
    return rehearsal.assemble(os.path.join(parent, "checkout"))


def test_the_rehearsals_data_keeps_to_its_prefix():
    """No file the rehearsal lays over benchmarks/ and no name it adds to
    BENCHMARK.json can be a real one's: each begins `rehearsal`."""
    with open(os.path.join(rehearsal.DATA, "cells.json"),
              encoding="utf-8") as f:
        added = json.load(f)
    laid = [name for kind in rehearsal.KINDS
            for name in os.listdir(os.path.join(rehearsal.DATA, kind))
            if not name.startswith("__")]
    assert len(laid) == 5
    names = [e["name"] for e in added["configs"] + added["workloads"]]
    names += [cell[key] for cell in added["workloads"]
              for key in ("config", "traffic")]
    assert [c["name"] for c in added["workloads"]] == REHEARSALS
    assert all(name.startswith("rehearsal") for name in laid + names)
    # and no file of the benchmark takes the prefix
    assert not [name for kind in rehearsal.KINDS + ("layer_metrics",)
                for name in os.listdir(os.path.join(BENCH, kind))
                if name.startswith("rehearsal")]
    taken = [e["name"] for kind in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in BENCHMARK[kind]]
    assert not [name for name in taken if name.startswith("rehearsal")]


@pytest.mark.parametrize("planted,said", [
    ("configs/rehearsal-durable.json", "is already there"),
    ("tables/rehearsal_graph_mix.py", "is already there"),
    ("a configuration's name", "already has configs"),
    ("a cell's name", "already has workloads")])
def test_the_rehearsal_refuses_what_is_already_there(planted, said, tmp_path):
    """A repository that already has one of the rehearsal's files or names:
    assemble() raises, and the file that was there is not overwritten."""
    root = tmp_path / "repository"
    for kind in rehearsal.KINDS:
        (root / "benchmarks" / kind).mkdir(parents=True)
    bench = json.loads(json.dumps(BENCHMARK))
    if planted == "a configuration's name":
        bench["configs"].append(dict(bench["configs"][0],
                                     name="rehearsal-graph"))
    elif planted == "a cell's name":
        bench["workloads"].append(dict(bench["workloads"][0],
                                       name="rehearsal_durable"))
    else:
        (root / "benchmarks" / planted).write_text("theirs")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(rehearsal.Collision, match=said):
        rehearsal.assemble(str(tmp_path / "checkout"), str(root))
    if "/" in planted:
        laid = tmp_path / "checkout" / "benchmarks" / planted
        assert laid.read_text() == "theirs"
    else:
        assert not (tmp_path / "checkout").exists()


def run_once(cell: str, *extra: str) -> "tuple[int, dict | None, str, str]":
    """(exit code, result line, run.py's output, the broker's log)."""
    out_dir = tempfile.mkdtemp(dir=os.path.dirname(tree()))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--seed", str(2**31 + 11), "--seconds", "2", "--scale", "small",
         "--out", out_dir, *extra],
        cwd=tree(), env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    try:
        last = json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        last = None
    try:
        with open(os.path.join(out_dir, "broker.log"), encoding="utf-8",
                  errors="replace") as f:
            log = f.read()
    except OSError:
        log = ""
    return proc.returncode, last, proc.stdout + proc.stderr[-3000:], log


run_rehearsal = functools.lru_cache(maxsize=None)(run_once)  # shared by tests


def counter(output: str, name: str) -> int:
    """The last `name=+n` or `name=n` that run.py printed."""
    return int(re.findall(rf"\b{name}=\+?(\d+)", output)[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", REHEARSALS)
def test_a_rehearsal_runs_correct_on_the_device_path(cell, trace):
    rc, last, output, log = run_rehearsal(cell, "--trace", str(trace))
    assert rc == 0 and isinstance(last, dict), output
    assert last["correct"] is True and last["failed"] == 0, output
    assert last["attempted"] > 1000 and last["metrics"]
    assert counter(output, "router_fallback_msgs") == 0
    assert counter(output, "router_kernel_launches") > 0
    if trace:
        assert last["device"]["busy_s"] > 0 and "breakdown" in last
        assert last["metrics"]["router_fallback_share"]["value"] == 0
    else:
        assert last["metrics"]["delivered_msgs_per_s"]["value"] > 0
    four = ["unconfirmed", "missing", "unexpected", "duplicates"]
    arguments = re.search(r"broker_launch: server arguments (.*)", log)[1]
    if cell == "rehearsal_durable":
        assert list(last["compared"]) == four + ["unsettled"]
        assert last["compared"]["unsettled"] == {"value": 0, "limit": 0}
        assert '"delivery_mode": 2' in output and '"durable": true' in output
        assert counter(output, "acks") == counter(output, "deliveries") > 0
        # the broker was given a store and the options, inside the run's
        # directory, and wrote its log there
        assert "'--store'" in arguments and "'--config'" in arguments
        assert "--config holds {'chana.mq.wal.enabled': True}" in log
        assert counter(output, "wal_appends") >= counter(output, "deliveries")
        assert counter(output, "wal_commit_errors") == 0
    else:
        assert list(last["compared"]) == four
        assert "--store" not in arguments and "--config" not in arguments
        assert "exchanges=10" in output and "exchange_bindings=104" in output
        assert counter(output, "fan_out") >= 1  # the regions' fanouts


def test_a_broker_option_reaches_the_broker():
    """The same durable deployment with its log stated off: the store is
    written directly, `wal_appends` stands still, the run is correct."""
    path = os.path.join(tree(), "benchmarks", "configs",
                        "rehearsal-durable.json")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    cfg = json.loads(text)
    assert cfg["applied"]["broker_options"] == {"chana.mq.wal.enabled": True}
    cfg["applied"]["broker_options"] = {"chana.mq.wal.enabled": False}
    try:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(cfg, f)
        rc, last, output, log = run_once("rehearsal_durable")
    finally:
        with open(path, "w", encoding="utf-8") as f:
            f.write(text)
    assert rc == 0 and last["correct"] is True, output
    assert "--config holds {'chana.mq.wal.enabled': False}" in log
    assert counter(output, "wal_appends") == 0
    assert last["compared"]["unsettled"] == {"value": 0, "limit": 0}
    on = run_rehearsal("rehearsal_durable", "--trace", "0")[2]
    assert counter(on, "wal_appends") > 0


@pytest.mark.parametrize("control", reference.CONTROLS)
@pytest.mark.parametrize("cell", REHEARSALS)
def test_a_control_in_a_rehearsal_is_not_correct(cell, control):
    """One run a rehearsal with `--control at_most_once`: its result line is
    that control's, and it prints every control on the pairs it recorded."""
    rc, last, output, _ = run_rehearsal(cell, "--control", "at_most_once")
    assert rc == 0 and isinstance(last, dict), output
    assert re.search(r"control program: correct=True ", output), output
    assert re.search(rf"control {control}: correct=False ", output), output
    if control == "at_most_once":
        assert last["correct"] is False and last["control"] == control
        assert last["compared"]["missing"]["value"] > 0
        if cell == "rehearsal_durable":  # the broker's own part still holds
            assert last["compared"]["unsettled"]["value"] == 0


# test_benchmark.py star-imports this module: the tests alone ride along
__all__ = [name for name in dir() if name.startswith("test_")]
