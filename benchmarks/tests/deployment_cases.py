"""What a configuration file states of its deployment, applied: the
`applied` section and its defaults, the client calls of the accepted cells
held to the parent harness's (data/parent_client_calls.json), and the two
rehearsal deployments of data/rehearsal (a durable, acknowledged one and a
graph of exchanges) run end to end through run.py in a scratch checkout that
rehearsal.py assembles — correct, on the device path, settled; and not
correct under every control.

test_benchmark.py imports these tests (see graph_cases.py for why). tier-1
collects them through tests/test_benchmarks_suite.py, which copies the test
functions alone, so nothing here is a fixture: what several tests share is
made once by a cached function.
"""

from __future__ import annotations

import atexit
import functools
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
ACCEPTED = [c["name"] for c in BENCHMARK["workloads"]]
REHEARSALS = ["rehearsal_durable", "rehearsal_graph"]
ROLES = ["declare", "producer", "consumer"]


# -- the section and its defaults ----------------------------------------------


@pytest.mark.parametrize("config", [c["name"] for c in BENCHMARK["configs"]])
def test_an_accepted_configuration_resolves_to_the_defaults(config):
    cfg = reference.load_config(config)
    assert "applied" not in cfg
    assert reference.applied(cfg) == {
        "durable": False, "delivery_mode": None, "consumer_ack": None,
        "broker_options": {}}
    assert "exchanges" not in reference.build_table(
        reference.load_config(config, "small"))


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
@pytest.mark.parametrize("cell", ACCEPTED)
def test_an_accepted_cell_makes_the_parents_client_calls(cell, role):
    """No `applied` section, no graph: name for name and argument for
    argument what the harness did before it could apply either."""
    with open(os.path.join(HERE, "data", "parent_client_calls.json"),
              encoding="utf-8") as f:
        parent = json.load(f)
    now = client_calls(BENCH)
    assert set(now) == set(parent) == set(ACCEPTED)
    assert now[cell][role] == parent[cell][role]
    assert now[cell][role]["calls"] > 8


@pytest.mark.parametrize("cell", REHEARSALS)
def test_a_rehearsal_makes_the_calls_its_file_states(cell):
    calls = client_calls(os.path.join(tree(), "benchmarks"))[cell]
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
                        "topic-telemetry-durable.json")
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
