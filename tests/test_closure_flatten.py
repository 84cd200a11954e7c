"""The router's flattened exchange-to-exchange closure (router/engine.py
`_compile_closure`): the one compiled table routes every key as the
breadth-first walk (`VHost.route`) and as the benchmark's plain reference do,
every verdict that keeps a graph on the walk, each member flattened once a
compile however many hops lead to it, and the dependent invalidation.
"""

import importlib.util
import os
import random
import sys

import pytest

from chanamq_tpu.amqp.properties import BasicProperties
from chanamq_tpu.broker.broker import Broker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import reference  # noqa: E402 — benchmarks/reference.py: imports no program
from tables import topic_graph_mix  # noqa: E402

WORDS = ["a", "b", "c"]
PROPS = BasicProperties()


def _keys() -> list:
    """Every key of one to three of WORDS, and the empty key."""
    keys = [""]
    for n in (1, 2, 3):
        keys += [".".join(WORDS[(i // 3 ** p) % 3] for p in range(n))
                 for i in range(3 ** n)]
    return keys


def _table(exchanges, queue_bindings, exchange_bindings) -> dict:
    """A graph in the benchmark's table form; the first exchange is the one
    published to."""
    root = exchanges[0][0]
    queues = sorted({q for _, q, _, _ in queue_bindings})
    return {"exchange": root, "type": exchanges[0][1], "queues": queues,
            "bindings": [(key, queue, args) for ex, queue, key, args
                         in queue_bindings if ex == root],
            "exchanges": exchanges,
            "queue_bindings": [b for b in queue_bindings if b[0] != root],
            "exchange_bindings": exchange_bindings}


def several_hops_one_destination() -> dict:
    """Five exact-key hops and one wildcard hop onto ONE direct exchange:
    the shape whose flatten was quadratic."""
    return _table(
        [("root", "topic"), ("cmds", "direct")],
        [("root", "q0", "a.#", None)]
        + [("cmds", f"q{i % 2 + 1}", key, None)
           for i, key in enumerate(["a", "b", "a.b", "b.c", "c.c.c"])]
        + [("cmds", "q3", "a.b", None)],
        [("root", "cmds", key, None)
         for key in ["a", "b", "a.b", "b.c", "c.c.c"]]
        + [("root", "cmds", "*.c", None)])


def diamond() -> dict:
    """Two fanout exchanges onto one shared exchange, which hops on."""
    return _table(
        [("root", "topic"), ("left", "fanout"), ("right", "fanout"),
         ("shared", "direct"), ("leaf", "fanout")],
        [("left", "ql", "", None), ("right", "qr", "", None),
         ("shared", "qs", "a.b", None), ("shared", "qs2", "b", None),
         ("leaf", "qleaf", "", None)],
        [("root", "left", "a.#", None), ("root", "right", "#.b", None),
         ("left", "shared", "", None), ("right", "shared", "", None),
         ("shared", "leaf", "a.b", None)])


def dangling_destination() -> dict:
    """A hop to an exchange nobody declared routes nowhere."""
    return _table(
        [("root", "fanout"), ("mid", "topic")],
        [("root", "q0", "", None), ("mid", "q1", "a.*", None),
         ("mid", "q2", "#.c", None)],
        [("root", "gone", "", None), ("root", "mid", "", None),
         ("mid", "gone", "a.#", None), ("mid", "gone", "a.b", None)])


def random_flat_graph(seed: int) -> dict:
    """A seeded random graph the flattener accepts: a topic root over layers
    of direct, fanout and wildcard-free topic exchanges, edges forward only,
    several hops to one destination and hops to an undeclared exchange among
    them; wildcard hops only from the root."""
    rng = random.Random(seed)

    def key() -> str:
        return ".".join(rng.choice(WORDS) for _ in range(rng.randrange(1, 4)))

    def pattern() -> str:
        words = key().split(".")
        words[rng.randrange(len(words))] = rng.choice(["*", "#"])
        return ".".join(words)

    n = rng.randrange(4, 9)
    exchanges = [("x0", "topic")] + [
        (f"x{i}", rng.choice(["direct", "fanout", "topic"]))
        for i in range(1, n)]
    queue_bindings, exchange_bindings = [], []
    for i, (name, kind) in enumerate(exchanges):
        for _ in range(rng.randrange(1, 4)):
            bind_key = pattern() if i == 0 and rng.random() < 0.5 else key()
            queue_bindings.append(
                (name, f"q{rng.randrange(12)}", bind_key, None))
        if i + 1 == n:
            continue
        for _ in range(rng.randrange(3, 7) if i == 0 else rng.randrange(3)):
            target = (f"x{rng.randrange(i + 1, n)}"
                      if rng.random() < 0.9 else "gone")
            hop = (rng.choice([pattern(), "#"])
                   if i == 0 and rng.random() < 0.4 else key())
            exchange_bindings.append((name, target, hop, None))
    return _table(exchanges, queue_bindings, exchange_bindings)


async def _program(table: dict, direct: bool = False) -> Broker:
    """The table declared into a broker. `direct` writes the exchange
    bindings straight into the entities, as a recovered broker can hold a
    cycle that `bind_exchange`'s guard refuses; a hop to an exchange nobody
    declared goes in that way too."""
    broker = Broker()
    await broker.create_vhost("/")
    for name, kind in table["exchanges"]:
        await broker.declare_exchange("/", name, kind)
    for queue in table["queues"]:
        await broker.declare_queue("/", queue)
    for key, queue, args in table["bindings"]:
        await broker.bind_queue("/", queue, table["exchange"], key, args)
    for exchange, queue, key, args in table["queue_bindings"]:
        await broker.bind_queue("/", queue, exchange, key, args)
    vhost = broker.vhost("/")
    for source, destination, key, args in table["exchange_bindings"]:
        if direct or destination not in vhost.exchanges:
            vhost.exchanges[source].ensure_ex_matcher().bind(
                key, destination, args)
        else:
            await broker.bind_exchange("/", destination, source, key, args)
    broker.router.invalidate()
    broker.router.min_batch = 1
    return broker


def _routed(broker: Broker, root: str, keys: list) -> list:
    routes, _, _ = broker.router.route_pending(
        "/", [(root, key, PROPS, b"x", None, None, False) for key in keys])
    return [{queue.name for queue in queues} for queues in routes]


def _members(table: dict) -> set:
    """Every exchange name the walk can reach from the published one."""
    seen, todo = {table["exchange"]}, [table["exchange"]]
    while todo:
        name = todo.pop()
        for source, destination, _, _ in table["exchange_bindings"]:
            if source == name and destination not in seen:
                seen.add(destination)
                todo.append(destination)
    return seen


GRAPHS = {"several_hops_one_destination": several_hops_one_destination,
          "diamond": diamond, "dangling_destination": dangling_destination}
GRAPHS.update({f"random-{seed}": (lambda seed=seed: random_flat_graph(seed))
               for seed in (1, 3, 8, 11, 16, 18, 27, 2**31 + 35)})


@pytest.mark.parametrize("name", list(GRAPHS))
async def test_the_flattened_table_routes_as_the_walk_and_the_reference(name):
    table = GRAPHS[name]()
    root, keys = table["exchange"], _keys()
    broker = await _program(table)
    router, metrics, vhost = broker.router, broker.metrics, broker.vhost("/")
    assert router.defer_ok("/", root)  # the closure compiled
    members = _members(table)
    assert metrics.router_closure_compiles == 1
    # each member once, however many hops lead to it
    assert metrics.router_closure_flattens == len(members)
    assert metrics.router_closure_flatten_ns > 0
    # an edge from every member to the root, the undeclared among them
    assert {member for (_, member), roots in router._closure_deps.items()
            if ("/", root) in roots} == members
    plain = reference.expected_sets_plain(
        table, [(key, None) for key in keys])
    routed = _routed(broker, root, keys)
    hopped = 0
    for key, got, want in zip(keys, routed, plain):
        assert got == want == vhost.route(root, key, None), (name, key)
        hopped += want != vhost.exchanges[root].route(key, None)
    assert hopped >= 4  # the graph does carry messages past its root
    # through the snapshot, not the walk: every message counted, none fell back
    assert metrics.router_closure_msgs == len(keys)
    assert metrics.router_fallback_msgs == 0
    assert metrics.router_closure_compiles == 1


def _wildcard_over_wildcard():
    return _table([("root", "topic"), ("dst", "topic")],
                  [("dst", "q", "a.*", None)], [("root", "dst", "a.#", None)])


def _headers_member():
    return _table([("root", "fanout"), ("mid", "direct"), ("h", "headers")],
                  [("mid", "q", "a", None),
                   ("h", "qh", "", {"x-match": "all", "k": 1})],
                  [("root", "mid", "", None), ("mid", "h", "a", None)])


def _multi_hash_binding():
    return _table([("root", "fanout"), ("dst", "topic")],
                  [("dst", "q", "#.a.#", None)], [("root", "dst", "", None)])


def _multi_hash_hop():
    return _table([("root", "topic"), ("dst", "fanout")],
                  [("dst", "q", "", None)], [("root", "dst", "#.a.#", None)])


def _cycle():
    # reached twice before it closes: the second visit reuses nothing open
    return _table([("root", "fanout"), ("a", "fanout"), ("b", "fanout")],
                  [("a", "qa", "", None), ("b", "qb", "", None)],
                  [("root", "a", "", None), ("a", "b", "", None),
                   ("b", "a", "", None)])


def _self_loop():
    return _table([("root", "direct"), ("a", "direct")],
                  [("a", "qa", "a", None)],
                  [("root", "a", "a", None), ("a", "a", "a", None)])


VERDICTS = {
    "wildcard_over_wildcard": (_wildcard_over_wildcard,
                               "wildcard-over-wildcard e2e chain"),
    "headers_member": (_headers_member, "headers exchange in e2e closure"),
    "multi_hash_binding": (_multi_hash_binding, "multi-# pattern"),
    "multi_hash_hop": (_multi_hash_hop, "multi-# e2e pattern"),
    "cycle": (_cycle, "cycle in e2e closure"),
    "self_loop": (_self_loop, "cycle in e2e closure"),
}


@pytest.mark.parametrize("name", list(VERDICTS))
async def test_a_graph_the_flattener_refuses_stays_on_the_walk(name):
    make, reason = VERDICTS[name]
    table = make()
    root, keys = table["exchange"], _keys()
    broker = await _program(table, direct=True)
    router, metrics, vhost = broker.router, broker.metrics, broker.vhost("/")
    assert not router.defer_ok("/", root)
    assert router._compiled[("/", root)] == reason
    assert metrics.router_closure_compiles == 0
    # the attempt is counted as work done, not as a compile
    assert 1 <= metrics.router_closure_flattens <= len(_members(table))
    assert metrics.router_closure_flatten_ns > 0
    # the verdict hangs on every member it reached: a bind there drops it
    assert ("/", root) in router._closure_deps[("/", root)]
    plain = reference.expected_sets_plain(
        table, [(key, None) for key in keys])
    routed = _routed(broker, root, keys)
    for key, got, want in zip(keys, routed, plain):
        assert got == want == vhost.route(root, key, None), (name, key)
    assert metrics.router_closure_msgs == 0
    assert metrics.router_fallback_msgs == len(keys)


async def test_an_alternate_exchange_in_the_closure_stays_on_the_walk():
    broker = Broker()
    await broker.create_vhost("/")
    await broker.declare_exchange("/", "root", "fanout")
    await broker.declare_exchange("/", "spare", "fanout")
    await broker.declare_exchange(
        "/", "mid", "direct", arguments={"alternate-exchange": "spare"})
    await broker.declare_queue("/", "q")
    await broker.bind_queue("/", "q", "mid", "a")
    await broker.bind_exchange("/", "mid", "root", "")
    assert not broker.router.defer_ok("/", "root")
    assert (broker.router._compiled[("/", "root")]
            == "alternate exchange in e2e closure")
    assert broker.metrics.router_closure_compiles == 0


GRAPH_MIX = {"wildcards": 256, "regions": 64, "region_queues": 8,
             "commands": 232, "command_keys": 4000}


def _exact_hops():
    """The shape that made the old flatten quadratic: the rehearsal's
    generator (test data of the benchmark), which reaches the direct
    exchange by one exact-key hop a command."""
    spec = importlib.util.spec_from_file_location(
        "rehearsal_graph_mix", os.path.join(
            ROOT, "benchmarks", "tests", "data", "rehearsal", "tables",
            "rehearsal_graph_mix.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("hops", [4064, 65])
async def test_a_graph_flattens_66_members_and_a_bind_below_recompiles(hops):
    """Linearity without a clock: at 4,000 exact-key hops onto one direct
    exchange (the rehearsal's shape at full size) the compile flattens 66
    exchanges, not 4,065; the benchmark's graph reaches the same exchange
    by one `cmd.#` hop and compiles to the same table. A bind into a
    region's fanout, which only the root's hop reaches, drops the root's
    snapshot, and the next flush recompiles and routes to the new queue."""
    generator = _exact_hops() if hops == 4064 else topic_graph_mix
    table = generator.table(GRAPH_MIX)
    assert len(table["exchanges"]) == 66
    assert len(table["exchange_bindings"]) == hops
    assert len(table["queues"]) == 1000
    broker = await _program(table)
    router, metrics, vhost = broker.router, broker.metrics, broker.vhost("/")
    root = table["exchange"]
    assert router.defer_ok("/", root)
    assert metrics.router_closure_compiles == 1
    assert metrics.router_closure_flattens == 66
    compiled = router._compiled[("/", root)]
    assert compiled.kernel_rows == 320 and len(compiled.exact) == 4000
    # the snapshot routes a sample of the benchmark's pool as the walk does
    pool = generator.pool(GRAPH_MIX, table, 20000, random.Random(27))
    keys = [key for key, _ in pool[:1500]]
    assert sum(key.startswith("cmd.") for key in keys) > 100
    for key, got in zip(keys, _routed(broker, root, keys)):
        assert got == vhost.route(root, key, None), key
    assert metrics.router_closure_msgs == len(keys)
    assert metrics.router_closure_compiles == 1  # steady: nothing rebuilt

    region = "bench.graph.region7"
    assert router._closure_deps[("/", region)] == {("/", root)}
    await broker.declare_queue("/", "late")
    await broker.bind_queue("/", "late", region, "")
    assert ("/", root) not in router._compiled  # dropped through the edge
    (got,) = _routed(broker, root, ["r7.x"])
    assert "late" in got and got == vhost.route(root, "r7.x", None)
    assert len(got) == 9
    assert metrics.router_closure_compiles == 2
    assert metrics.router_closure_flattens == 132


async def test_closure_msgs_count_a_flush_and_nothing_of_a_plain_exchange():
    broker = await _program(diamond())
    await broker.declare_exchange("/", "plain", "topic")
    await broker.bind_queue("/", "ql", "plain", "a.*")
    metrics = broker.metrics
    _routed(broker, "plain", ["a.b"] * 7)
    assert metrics.router_batch_msgs == 7 and metrics.router_closure_msgs == 0
    assert metrics.router_closure_compiles == 0
    assert metrics.router_compiles == 1
    _routed(broker, "root", ["a.b"] * 5)
    assert metrics.router_batch_msgs == 12 and metrics.router_closure_msgs == 5
    # a closure counts as a compile of both kinds, a plain table of one
    assert metrics.router_closure_compiles == 1
    assert metrics.router_compiles == 2
    # under min-batch the flush walks the graph: not through the snapshot
    broker.router.min_batch = 16
    _routed(broker, "root", ["a.b"] * 3)
    assert metrics.router_closure_msgs == 5
    assert metrics.router_fallback_msgs == 3
