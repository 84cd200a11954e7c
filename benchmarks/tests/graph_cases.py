"""The reference over every exchange type and over graphs of exchanges:
`direct` and `fanout` written out, and the breadth-first walk (plain, and a
whole pool at once) against the program's own `VHost.route` on seeded random
graphs with cycles and bindings to exchanges nobody declared.

test_benchmark.py imports these tests: tier-1 collects that module by name
(tests/test_benchmarks_suite.py), and a file named test_*.py beside it would
run twice under `pytest benchmarks/tests`.
"""

from __future__ import annotations

import os
import random
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import reference  # noqa: E402

WORDS = ["a", "b", "c", "d"]
HEADER_NAMES, HEADER_VALUES = ["h0", "h1", "h2"], ["x", "y", 1]


def test_direct_and_fanout_definitions():
    assert reference.direct_matches("a.b", "a.b")
    assert not reference.direct_matches("a.b", "a.b.c")
    assert not reference.direct_matches("a.*", "a.b")  # no wildcard in direct
    assert not reference.direct_matches("", "a")
    assert reference.direct_matches("", "")
    assert reference.fanout_matches()
    for kind, binding_key, key, want in [
            ("direct", "k", "k", True), ("direct", "k", "j", False),
            ("fanout", "ignored", "anything", True),
            ("topic", "a.#", "a.b.c", True), ("topic", "a.*", "a", False)]:
        assert reference.binding_matches(
            kind, binding_key, None, key, None) is want
    assert reference.binding_matches(
        "headers", "", {"x-match": "any", "h": 1}, "", {"h": 1})
    with pytest.raises(ValueError):
        reference.binding_matches("x-custom", "k", None, "k", None)
    # one exchange of each type, a whole pool at once and pair by pair
    pool = [("k", None), ("j", None), ("", None)]
    for kind, reached in (("direct", [{"q"}, set(), set()]),
                          ("fanout", [{"q"}, {"q"}, {"q"}])):
        table = {"exchange": "x", "type": kind, "queues": ["q"],
                 "bindings": [("k", "q", None)]}
        assert reference.expected_sets_plain(table, pool) == reached
        fast = reference.expected_sets(table, pool)
        assert [bool(fast.of(i)) for i in range(3)] == \
            [bool(r) for r in reached]


def random_key(rng) -> str:
    return ".".join(rng.choice(WORDS) for _ in range(rng.randrange(1, 4)))


def random_pattern(rng) -> str:
    words = [rng.choice(WORDS + ["*", "*", "#"])
             for _ in range(rng.randrange(1, 4))]
    if words.count("#") > 1:  # one `#` at most, as the tables have them
        words = [w for w in words if w != "#"] + ["#"]
    return ".".join(words)


def random_binding(rng, kind: str) -> tuple:
    """(key, arguments) of one binding of an exchange of type `kind`."""
    if kind == "headers":
        args: dict = {"x-match": rng.choice(["all", "any"])}
        for name in rng.sample(HEADER_NAMES, rng.randrange(1, 3)):
            args[name] = rng.choice(HEADER_VALUES)
        return "", args
    if kind == "topic":
        return random_pattern(rng), None
    return random_key(rng), None


def random_graph(seed: int) -> "tuple[dict, list]":
    """A table of 4-7 exchanges of every type with queue and exchange
    bindings drawn at random — so cycles, self loops, several paths to one
    queue and hops to an exchange that does not exist all occur — and a pool
    of keys and header sets over the same small vocabulary."""
    rng = random.Random(seed)
    kinds = ["topic", "direct", "fanout", "topic",
             rng.choice(["headers", "topic", "direct"]),
             rng.choice(["fanout", "direct"]), "topic"][:rng.randrange(4, 8)]
    exchanges = [(f"x{i}", kind) for i, kind in enumerate(kinds)]
    kind_of = dict(exchanges)
    queues = [f"q{i}" for i in range(10)]
    bindings, queue_bindings, exchange_bindings = [], [], []
    for name, kind in exchanges:
        for _ in range(rng.randrange(1, 5)):
            key, args = random_binding(rng, kind)
            if name == "x0":
                bindings.append((key, rng.choice(queues), args))
            else:
                queue_bindings.append((name, rng.choice(queues), key, args))
        for _ in range(rng.randrange(3 if name == "x0" else 0, 5)):
            key, args = random_binding(rng, kind)
            target = rng.choice([n for n, _ in exchanges] + ["gone"])
            exchange_bindings.append((name, target, key, args))
    table = {"exchange": "x0", "type": kind_of["x0"], "queues": queues,
             "bindings": bindings, "exchanges": exchanges,
             "queue_bindings": queue_bindings,
             "exchange_bindings": exchange_bindings}
    pool = []
    for _ in range(300):
        headers = {name: rng.choice(HEADER_VALUES) for name in rng.sample(
            HEADER_NAMES, rng.randrange(0, 3))} or None
        pool.append((random_key(rng), headers))
    return table, pool


def program_vhost(table: dict):
    """The table declared straight into the program's entities (the wire
    refuses a bind that closes a cycle; a recovered broker can hold one)."""
    from chanamq_tpu.broker.entities import Exchange, VHost

    vhost = VHost("/")
    for name, kind in table["exchanges"]:
        vhost.exchanges[name] = Exchange("/", name, kind)
    for key, queue, args in table["bindings"]:
        vhost.exchanges[table["exchange"]].matcher.bind(key, queue, args)
    for exchange, queue, key, args in table["queue_bindings"]:
        vhost.exchanges[exchange].matcher.bind(key, queue, args)
    for source, destination, key, args in table["exchange_bindings"]:
        vhost.exchanges[source].ensure_ex_matcher().bind(
            key, destination, args)
    return vhost


@pytest.mark.parametrize("seed", [1, 3, 5, 6, 7, 10, 13, 21, 34, 2**31 + 34])
def test_graph_walk_agrees_with_the_programs_route(seed):
    table, pool = random_graph(seed)
    vhost = program_vhost(table)
    queue_id = {q: i for i, q in enumerate(table["queues"])}
    fast = reference.expected_sets(table, pool)
    plain = reference.expected_sets_plain(table, pool)
    hopped = 0
    for i, (key, headers) in enumerate(pool):
        want = vhost.route(table["exchange"], key, headers)
        assert plain[i] == want, (seed, key, headers)
        assert fast.of(i) == frozenset(queue_id[q] for q in want), (seed, key)
        alone = vhost.exchanges[table["exchange"]].route(key, headers)
        hopped += want != alone
    assert hopped > 10  # the graphs do carry messages past the first exchange
    # the walk, not the first exchange alone: without the hops it differs
    first_only = dict(table, exchange_bindings=[])
    assert reference.expected_sets_plain(first_only, pool) != plain


def test_a_cycle_and_a_dangling_hop_end_the_walk():
    table = {"exchange": "a", "type": "fanout", "queues": ["qa", "qb", "qc"],
             "bindings": [("", "qa", None)],
             "exchanges": [("a", "fanout"), ("b", "topic"), ("c", "direct")],
             "queue_bindings": [("b", "qb", "k.#", None),
                                ("c", "qc", "k.1", None),
                                ("c", "qa", "k.1", None)],
             "exchange_bindings": [("a", "b", "", None), ("b", "a", "#", None),
                                   ("b", "c", "k.*", None),
                                   ("b", "gone", "#", None),
                                   ("c", "c", "k.1", None)]}
    pool = [("k.1", None), ("k.2", None), ("j", None)]
    want = [{"qa", "qb", "qc"}, {"qa", "qb"}, {"qa"}]
    assert reference.expected_sets_plain(table, pool) == want
    fast = reference.expected_sets(table, pool)
    queue_id = {q: i for i, q in enumerate(table["queues"])}
    assert [fast.of(i) for i in range(3)] == [
        frozenset(queue_id[q] for q in w) for w in want]
    vhost = program_vhost(table)
    assert [vhost.route("a", key, None) for key, _ in pool] == want
    # a queue reached by two paths is due one delivery
    import numpy as np

    pairs = fast.pairs(np.array([7]), np.array([0]))
    assert len(pairs) == len(set(pairs.tolist())) == 3
