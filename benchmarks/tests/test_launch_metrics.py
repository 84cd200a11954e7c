"""The per-layer metrics that read the router's launch counters (PR 28):
every new metric file names a reader that exists and agrees with its entry
of BENCHMARK.json, the two new readers do their arithmetic and read nothing
from a program without the counters, and the trace reduction credits the
chip's idle gaps to the program's own spans where a trace has them.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import reference  # noqa: E402
import trace_reduce  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)
SATURATED = ["topic_fresh_keys", "headers_every_flush", "topic_fleet_keys"]
TWINNED = ["router_launch_dispatch_us", "router_launch_wait_us",
           "router_route_share"]
NEW = TWINNED + [name + ".paced" for name in TWINNED]
SPANS = ("conn.ingress", "router.lookup", "router.tokenize", "router.decode",
         "broker.enqueue", "conn.confirms")


@pytest.mark.parametrize("name", NEW)
def test_a_launch_metric_names_a_reader_and_matches_its_entry(name):
    (entry,) = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    spec = reference.load_json("layer_metrics", f"{name}.json")
    assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) == \
        (entry["name"], entry["layer"], entry["unit"], entry["moves"])
    assert entry["layer"] == "router host part"
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower"
    paced = name.endswith(".paced")
    # the saturated cells of PR 28 are a floor: a cell added since joins the
    # list; a paced cell joins a `.paced` twin only by a `benchmark` PR
    if paced:
        assert entry["workloads"] == ["topic_paced"]
    else:
        assert set(SATURATED) <= set(entry["workloads"])
        assert "topic_paced" not in entry["workloads"]
    assert entry["moves"] == ("deliver_latency_p50_ms" if paced
                              else "delivered_msgs_per_s")
    assert spec["params"].get("over", "window") == "window"
    reader = importlib.import_module(f"readers.{spec['reader']}")
    assert callable(reader.read)
    # every counter the metric reads is one the program serves
    from chanamq_tpu.utils.metrics import Metrics

    served = set(Metrics().snapshot())
    for key in ("num", "den", "counter"):
        if key in spec["params"]:
            assert spec["params"][key].removeprefix("metrics.") in served


def ctx_of(before: dict, after: dict, seconds: float = 7.0) -> dict:
    def snap(ns, metrics):
        return {"ns": ns, "admin": {"metrics": metrics}}

    return {"snaps": {"window0": snap(0, before),
                      "window1": snap(int(seconds * 1e9), after),
                      "span0": snap(int(seconds * 1e9), after),
                      "span1": snap(int((seconds + 3) * 1e9), after)}}


def test_admin_delta_per_second_on_a_synthetic_run():
    from readers import admin_delta_per_second as reader

    ctx = ctx_of({"router_route_ns": 1_000_000_000},
                 {"router_route_ns": 4_500_000_000})
    share = {"counter": "metrics.router_route_ns", "scale": 1e-7}
    # 3.5 s of routing in a window of 7 s: half the loop
    assert reader.read(share, ctx) == pytest.approx(50.0)
    assert reader.read({"counter": "metrics.router_route_ns"}, ctx) == \
        pytest.approx(0.5e9)
    # over the span the counter stood still
    assert reader.read(dict(share, over="span"), ctx) == 0.0
    # a program from before the counter: nothing to read, and no error
    assert reader.read({"counter": "metrics.router_no_such_ns"}, ctx) is None
    assert reader.read(share, ctx_of({}, {})) is None
    assert reader.read(share, ctx_of({"router_route_ns": 0},
                                     {"router_route_ns": 5}, 0.0)) is None


def test_admin_delta_ratio_optional_on_a_synthetic_run():
    from readers import admin_delta_ratio_optional as reader

    before = {"router_dispatch_ns": 10_000, "router_kernel_launches": 4}
    after = {"router_dispatch_ns": 2_610_000, "router_kernel_launches": 6}
    params = {"num": "metrics.router_dispatch_ns",
              "den": "metrics.router_kernel_launches", "scale": 0.001}
    assert reader.read(params, ctx_of(before, after)) == pytest.approx(1300.0)
    # no launch in the window: no ratio
    assert reader.read(params, ctx_of(after, after)) is None
    # the parent commit serves launches but not their stages
    old = {"router_kernel_launches": 4}, {"router_kernel_launches": 6}
    assert reader.read(params, ctx_of(*old)) is None


def test_trace_reduction_credits_idle_gaps_to_the_programs_spans():
    """benchmarks/tests/data/topic_trace_spans.json: the device plane's ops
    and the event loop's line of the first launches of a traced run on a TPU
    v5 lite, cut from PR 28's first chip call: the loop's thread now writes
    the program's spans beside JAX's own two events of a launch."""
    with open(os.path.join(HERE, "data", "topic_trace_spans.json"),
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
    named = dict(out["breakdown"]["idle_gaps"])
    for name in SPANS:
        assert named["host: " + name] > 0, name
    launch = [k for k in named if k.startswith("host: PjitFunction(")]
    assert launch == ["host: PjitFunction(topic_match)"]
    assert named["host: np.asarray(jax.Array)"] > 0
    # every idle instant has one name, and NOTHING is what no span covers
    idle = out["window_s"] - out["busy_s"]
    assert sum(named.values()) == pytest.approx(idle)
    (loop,) = host.values()
    covered = trace_reduce.union_ns(
        [(s, s + d) for _, s, d in trace_reduce.top_level(loop)])
    assert named[trace_reduce.NOTHING] == pytest.approx(
        out["window_s"] - covered / 1e9, abs=out["busy_s"])
    # the spans lie flat: none of the program's inside another event
    tops = {(n, s) for n, s, _ in trace_reduce.top_level(loop)}
    assert all((n, s) in tops for n, s, _ in loop if n in SPANS)
    assert named[trace_reduce.NOTHING] < 0.5 * idle
