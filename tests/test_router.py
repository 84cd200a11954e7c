"""Tensorized router tests: randomized parity fuzzing of the compiled
kernels against the Python matcher oracles (and the native C++ trie when
built), engine/invalidation behavior, and the end-to-end deferred publish
path through a real connection.

The parity gate of ISSUE 13: TopicMatcher, NativeTopicMatcher, and the
tensor router must return identical destination sets over thousands of
generated bind/unbind/route sequences, including ``#`` edge cases."""

import asyncio
import random

import numpy as np
import pytest

from chanamq_tpu import native_ext
from chanamq_tpu.amqp.properties import BasicProperties
from chanamq_tpu.broker.broker import Broker
from chanamq_tpu.broker.matchers import (
    DirectMatcher, FanoutMatcher, HeadersMatcher, TopicMatcher,
)
from chanamq_tpu.broker.server import BrokerServer
from chanamq_tpu.client import AMQPClient
from chanamq_tpu.router import compile as rcompile
from chanamq_tpu.router.compile import Uncompilable, compile_exchange, route_batch

WORDS = ["a", "b", "c", "dd", "e1", "", "orders", "x"]


def _rand_pattern(rng):
    return ".".join(
        rng.choice(WORDS + ["*", "#"]) for _ in range(rng.randint(1, 6)))


def _rand_key(rng):
    return ".".join(rng.choice(WORDS) for _ in range(rng.randint(0, 6)))


def _route_all_backends(compiled, items):
    """Route via numpy and jit; assert the two kernels agree, return one.

    The result memo is cleared between backends — topic results are
    memoized by bare routing key, so without the clear the second
    backend would serve every answer from the first backend's kernel."""
    py = route_batch(compiled, items, "python")
    compiled._route_memo.clear()
    jx = route_batch(compiled, items, "jax")
    assert [set(a) for a in py] == [set(b) for b in jx]
    return py


# ---------------------------------------------------------------------------
# parity fuzz: compiled kernels vs Python trie vs native trie
# ---------------------------------------------------------------------------


def test_topic_parity_fuzz():
    """Thousands of randomized bind/unbind/route sequences: the Python
    trie, the native trie (when built), and both tensor backends must be
    destination-set identical."""
    rng = random.Random(0xC0FFEE)
    native = native_ext.available()
    for trial in range(150):
        py = TopicMatcher()
        nat = native_ext.NativeTopicMatcher() if native else None
        bound = []
        for _ in range(rng.randint(1, 30)):
            pattern, queue = _rand_pattern(rng), f"q{rng.randint(0, 9)}"
            py.bind(pattern, queue)
            if nat is not None:
                nat.bind(pattern, queue)
            bound.append((pattern, queue))
        # interleave some unbinds so pruning paths run too
        for _ in range(rng.randint(0, len(bound) // 2)):
            pattern, queue = rng.choice(bound)
            py.unbind(pattern, queue)
            if nat is not None:
                nat.unbind(pattern, queue)
        try:
            compiled = compile_exchange("topic", py.bindings())
        except Uncompilable:
            # multi-# pattern: the tensor router would fall back to the
            # matcher; nothing to diff, but native must still agree
            if nat is not None:
                for _ in range(10):
                    key = _rand_key(rng)
                    assert nat.route(key) == py.route(key), key
            continue
        keys = [_rand_key(rng) for _ in range(rng.randint(1, 40))]
        got = _route_all_backends(compiled, [(k, None) for k in keys])
        for key, names in zip(keys, got):
            oracle = py.route(key)
            assert set(names) == oracle, (key, sorted(py._patterns))
            if nat is not None:
                assert nat.route(key) == oracle, key


def test_topic_hash_edge_cases():
    """The '#' grammar corners: zero-word match, leading/trailing/middle
    '#', '#' vs empty words, and the lone-'#' always-match fold."""
    cases = [
        (["#"], ["", "a", "a.b.c"]),
        (["a.#"], ["a", "a.b", "a.b.c", "b.a", ""]),
        (["#.a"], ["a", "b.a", "a.a.a", "a.b"]),
        (["a.#.b"], ["a.b", "a.x.b", "a.x.y.b", "a", "b"]),
        (["*.#"], ["", "a", "a.b", "a.b.c"]),
        (["#.*"], ["", "a", "a.b"]),
        (["..#"], ["", ".", "..", "..a", ".a."]),
        (["#.b.*"], ["b.a", "x.b.a", "b.b.b", "b"]),
        (["a.*.c", "a.#"], ["a.b.c", "a.c", "a.b.c.d"]),
    ]
    for patterns, keys in cases:
        py = TopicMatcher()
        for i, pattern in enumerate(patterns):
            py.bind(pattern, f"q{i}")
        compiled = compile_exchange("topic", py.bindings())
        got = _route_all_backends(compiled, [(k, None) for k in keys])
        for key, names in zip(keys, got):
            assert set(names) == py.route(key), (patterns, key)


def test_headers_parity_fuzz():
    rng = random.Random(0xBEEF)
    values = [1, "s", True, 2.5, "t", 0, False]
    for trial in range(150):
        m = HeadersMatcher()
        for _ in range(rng.randint(1, 15)):
            args = {f"h{rng.randint(0, 4)}": rng.choice(values)
                    for _ in range(rng.randint(0, 3))}
            if rng.random() < 0.8:
                args["x-match"] = rng.choice(["all", "any"])
            m.bind("", f"q{rng.randint(0, 6)}", args)
        compiled = compile_exchange("headers", m.bindings())
        msgs = []
        for _ in range(25):
            msgs.append({f"h{rng.randint(0, 5)}": rng.choice(values)
                         for _ in range(rng.randint(0, 4))})
        got = _route_all_backends(compiled, [("", h) for h in msgs])
        for headers, names in zip(msgs, got):
            assert set(names) == m.route("", headers), headers


def test_headers_unhashable_binding_uncompilable():
    m = HeadersMatcher()
    m.bind("", "q0", {"x-match": "all", "h": [1, 2]})
    with pytest.raises(Uncompilable):
        compile_exchange("headers", m.bindings())


def test_headers_unhashable_message_value_skipped():
    m = HeadersMatcher()
    m.bind("", "q0", {"x-match": "any", "h": 1, "g": 2})
    compiled = compile_exchange("headers", m.bindings())
    headers = {"h": [1, 2], "g": 2}
    got = _route_all_backends(compiled, [("", headers)])
    assert set(got[0]) == m.route("", headers) == {"q0"}


def test_direct_fanout_compile():
    d = DirectMatcher()
    d.bind("k1", "a")
    d.bind("k1", "b")
    d.bind("k2", "c")
    cd = compile_exchange("direct", d.bindings())
    got = route_batch(cd, [("k1", None), ("k2", None), ("zzz", None)])
    assert [set(g) for g in got] == [{"a", "b"}, {"c"}, set()]
    f = FanoutMatcher()
    f.bind("ignored", "a")
    f.bind("", "b")
    cf = compile_exchange("fanout", f.bindings())
    got = route_batch(cf, [("anything", None), ("", None)])
    assert [set(g) for g in got] == [{"a", "b"}, {"a", "b"}]


def test_multi_hash_uncompilable_and_caps():
    m = TopicMatcher()
    m.bind("a.#.b.#", "q0")
    with pytest.raises(Uncompilable):
        compile_exchange("topic", m.bindings())
    m2 = TopicMatcher()
    for i in range(5):
        m2.bind(f"w{i}.*", f"q{i}")
    with pytest.raises(Uncompilable):
        compile_exchange("topic", m2.bindings(), max_wildcards=3)
    with pytest.raises(Uncompilable):
        compile_exchange("topic", m2.bindings(), max_queues=2)
    # exact patterns never count against the wildcard cap
    m3 = TopicMatcher()
    for i in range(50):
        m3.bind(f"exact.{i}", f"q{i}")
    m3.bind("wild.*", "qw")
    compiled = compile_exchange("topic", m3.bindings(), max_wildcards=1)
    got = _route_all_backends(
        compiled, [("exact.7", None), ("wild.x", None), ("nope", None)])
    assert [set(g) for g in got] == [{"q7"}, {"qw"}, set()]


# ---------------------------------------------------------------------------
# the batch mask decode: once per distinct mask, not once per key
# ---------------------------------------------------------------------------


def _wide_table(kind: str, words: int, rng):
    """A table whose destination mask is `words` uint32 wide, its last word
    part filled, a non-empty `always`, and a batch for it with duplicates,
    rows that match nothing and (topic) keys that hit an exact pattern and
    a wildcard row at once. Returns (oracle matcher, compiled, items)."""
    queues = [f"q{i:04d}" for i in range(32 * words - 3)]
    if kind == "topic":
        m = TopicMatcher()
        patterns = [(f"w{j}.#", f"t{j % 7}.*.s{j}", f"#.z{j}",
                     f"*.k{j}.#")[j % 4] for j in range(64)]
        for i, queue in enumerate(queues):
            m.bind(patterns[i % 64], queue)
        m.bind("#", "qall")
        for j in range(8):      # exact patterns a wildcard row matches too
            m.bind(f"w{j}.k{j}", f"qx{j}")
            m.bind(f"alone.{j}", f"qx{j}")
        keys = [rng.choice((f"w{j}.k{j}", f"w{j}.u.v", f"t{j % 7}.x.s{j}",
                            f"m.n.z{j}", f"x.k{j}", f"alone.{j % 8}",
                            f"miss.{j}", ""))
                for j in (rng.randrange(64) for _ in range(48))]
        keys += [f"w{j}.k{j}" for j in range(8)]
        items = [(key, None) for key in keys]
    else:
        m = HeadersMatcher()
        for i, queue in enumerate(queues):
            m.bind("", queue, {"x-match": ("all", "any")[i % 2],
                               f"h{i % 5}": i % 11, "g": i % 3})
        m.bind("", "qall", {"x-match": "all"})
        items = [("", {f"h{rng.randrange(6)}": rng.randrange(12),
                       "g": rng.randrange(4)}) for _ in range(12)]
        items += items[:4] + [("", None), ("", {"nope": 1})]
    compiled = compile_exchange(kind, m.bindings(), max_wildcards=4096)
    return m, compiled, items


def _kernel_rows(compiled, items):
    """The numpy twin's mask rows for `items`, padded to the bucket."""
    b = rcompile._bucket(len(items), 16)
    if compiled.kind == "topic":
        t = compiled.wild
        return rcompile._topic_kernel(
            np, *compiled.kernel_tables(),
            *rcompile._split_topic(
                rcompile._tokenize_topic(t, [k for k, _ in items], b),
                t["p"], t["s"]))
    return rcompile._headers_kernel(
        np, *compiled.kernel_tables(),
        rcompile._tokenize_headers(compiled.headers, [h for _, h in items], b))


@pytest.mark.parametrize("words", [1, 16, 128])
@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_batch_mask_decode_equals_the_row_decode(kind, words):
    rng = random.Random(words * 31 + len(kind))
    m, compiled, items = _wide_table(kind, words, rng)
    table = compiled.wild if kind == "topic" else compiled.headers
    assert table["mask_words"] == words and compiled.always == {"qall"}
    n = len(items)
    rows = _kernel_rows(compiled, items).copy()
    assert rows.shape == (rcompile._bucket(n, 16), words) and rows.shape[0] > n
    # rows past n are padding: set every bit, so that decoding one would
    # look up a queue the table does not have
    rows[n:] = 0xFFFFFFFF
    with pytest.raises(IndexError):
        compiled._decode_mask(rows[n])
    by_row = [compiled.always | compiled._decode_mask(rows[j])
              for j in range(n)]
    zero = [j for j in range(n) if not rows[j].any()]
    distinct = {rows[j].tobytes() for j in range(n)} - {bytes(4 * words)}
    assert zero and len(distinct) > 1
    got, decoded = compiled._decode_rows(rows, n)
    assert got == by_row and decoded == len(distinct)
    assert set(compiled._mask_memo) == distinct
    assert all(got[j] is compiled.always for j in zero)
    again, decoded = compiled._decode_rows(rows, n)
    assert decoded == 0 and all(a is b for a, b in zip(again, got))
    # and the whole route: exact | always | mask names, against the matcher
    routed = route_batch(compiled, items, "python")
    assert len(routed) == n
    for (key, headers), names in zip(items, routed):
        assert names == m.route(key, headers), (key, headers)
    if kind == "topic":
        # w0.k0: an exact pattern, the row w0.# and the lone '#' at once
        at_once = routed[items.index(("w0.k0", None))]
        assert {"qx0", "q0000", "qall"} <= at_once


def test_mask_memo_is_capped_and_answers_stay_equal():
    """More distinct masks than `_MEMO_CAP`: the memo is cleared, never
    grows past the cap, and every answer still equals the matcher's."""
    m = HeadersMatcher()
    for i in range(14):
        m.bind("", f"q{i}", {"x-match": "any", f"h{i}": 1})
    compiled = compile_exchange("headers", m.bindings())
    rng = random.Random(14)
    subsets = rng.sample(range(1, 1 << 14), rcompile._MEMO_CAP + 900)
    msgs = [{f"h{i}": 1 for i in range(14) if bits >> i & 1}
            for bits in subsets]
    seen = 0
    for start in range(0, len(msgs), 1024):
        chunk = msgs[start:start + 1024]
        got = route_batch(compiled, [("", h) for h in chunk], "python")
        for headers, names in zip(chunk, got):
            assert names == m.route("", headers)
        assert len(compiled._mask_memo) <= rcompile._MEMO_CAP
        seen += len(chunk)
    assert seen > rcompile._MEMO_CAP > len(compiled._mask_memo)


@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_both_backends_decode_one_batch_to_identical_sets(kind):
    m, compiled, items = _wide_table(kind, 16, random.Random(5))
    py = route_batch(compiled, items, "python")
    compiled._route_memo.clear()
    compiled._mask_memo.clear()
    jx = route_batch(compiled, items, "jax")
    assert compiled._mask_memo  # the jit kernel's rows were decoded anew
    assert py == jx
    assert all(type(names) is frozenset for names in py + jx)


def test_keys_with_one_mask_share_one_frozenset():
    """No exact hit and no `always`: the mask memo's own frozenset is the
    answer for every key that carries the mask, so the engine's queue
    cache hashes it once; an all-zero row is the one empty set."""
    m = TopicMatcher()
    m.bind("a.*", "q1")
    m.bind("a.#", "q2")
    m.bind("a.b", "q3")
    compiled = compile_exchange("topic", m.bindings())
    first = route_batch(compiled, [("a.x", None), ("a.y", None),
                                   ("a.b", None), ("zz", None)])
    assert first[0] is first[1] and first[0] == {"q1", "q2"}
    assert first[2] == {"q1", "q2", "q3"} and first[2] is not first[0]
    assert first[3] is rcompile._EMPTY
    later = route_batch(compiled, [("a.z", None), ("yy", None)])
    assert later[0] is first[0] and later[1] is rcompile._EMPTY
    # an exact hit on a row that matches nothing else is the exact set
    m.bind("solo", "q4")
    compiled = compile_exchange("topic", m.bindings())
    got = route_batch(compiled, [("solo", None)])
    assert got[0] is compiled.exact["solo"]


# ---------------------------------------------------------------------------
# what a launch hands over: the table resident per snapshot, the batch one
# array
# ---------------------------------------------------------------------------


def _small_table(kind: str):
    """(matcher, items) that reach the kernel: wildcard rows of every
    shape plus an exact pattern, or headers bindings of both modes."""
    if kind == "topic":
        m = TopicMatcher()
        for i, pattern in enumerate(
                ["a.*", "#.z", "w.k.#", "*.b.#", "a.b", "t.*.s"]):
            m.bind(pattern, f"q{i}")
        keys = ["a.b", "a.z", "w.k", "w.k.x.y", "x.b", "t.u.s", "", "m.n.o.z",
                "nowhere.at.all"]
        return m, [(k, None) for k in keys]
    m = HeadersMatcher()
    m.bind("", "q0", {"x-match": "all", "h": 1, "g": "s"})
    m.bind("", "q1", {"x-match": "any", "h": 2, "g": "t"})
    m.bind("", "q2", {"x-match": "any", "f": True})
    msgs = [{"h": 1, "g": "s"}, {"h": 1}, {"h": 2}, {"g": "t", "f": True},
            {}, None, {"other": 1}, {"h": 1, "g": "s", "f": True}]
    return m, [("", h) for h in msgs]


def _oracle(matcher, items):
    return [matcher.route(k, h) for k, h in items]


@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_a_snapshot_launched_twice_answers_alike_and_uploads_once(kind):
    from chanamq_tpu.utils.metrics import Metrics

    m, items = _small_table(kind)
    twin = route_batch(compile_exchange(kind, m.bindings()), items, "python")
    compiled = compile_exchange(kind, m.bindings())
    metrics = Metrics()
    assert compiled._resident is None  # nothing goes up before a launch
    first = route_batch(compiled, items, "jax", metrics)
    resident = compiled._resident
    host = compiled.kernel_tables()
    assert len(resident) == len(host)
    for on_device, on_host in zip(resident, host):
        assert not isinstance(on_device, np.ndarray)
        assert on_device.dtype == on_host.dtype
        assert np.array_equal(np.asarray(on_device), on_host)
    compiled._route_memo.clear()  # topic: or the key memo answers
    second = route_batch(compiled, items, "jax", metrics)
    assert first == second == twin
    assert [set(n) for n in first] == _oracle(m, items)
    assert metrics.router_kernel_launches == 2
    assert metrics.router_table_uploads == 1
    assert compiled._resident is resident
    # the numpy tables stay where they were, for the twin and the tests
    assert all(isinstance(t, np.ndarray) for t in compiled.kernel_tables())


def _mk_broker(loop, kind: str):
    """An exchange `ex` of `kind` with `_small_table`'s bindings."""
    broker = Broker()
    run = loop.run_until_complete
    run(broker.create_vhost("/"))
    run(broker.declare_exchange("/", "ex", kind))
    m, items = _small_table(kind)
    for key, queue, args in m.bindings():
        if queue not in broker.vhosts["/"].queues:
            run(broker.declare_queue("/", queue))
        run(broker.bind_queue("/", queue, "ex", key, args))
    broker.router.min_batch = 1
    return broker, items


def _flush(broker, items) -> list:
    entries = [("ex", key, BasicProperties(headers=headers), b"x", None,
                None, False) for key, headers in items]
    routes, _, _ = broker.router.route_pending("/", entries)
    return [{q.name for q in queues} for queues in routes]


@pytest.mark.parametrize("change", ["bind", "unbind", "queue_delete"])
@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_a_new_generation_answers_from_its_own_table(event_loop, kind, change):
    """The stale-table case: after a bind, an unbind or a queue delete the
    next launch must match against the NEW table on the device, while the
    snapshot a flush already holds keeps answering from its own."""
    broker, items = _mk_broker(event_loop, kind)
    run = event_loop.run_until_complete
    matcher = broker.vhosts["/"].exchanges["ex"].matcher
    before = _oracle(matcher, items)
    assert _flush(broker, items) == before
    old = broker.router._compiled[("/", "ex")]
    assert broker.metrics.router_table_uploads == 1

    if change == "bind":
        run(broker.declare_queue("/", "qn"))
        if kind == "topic":
            run(broker.bind_queue("/", "qn", "ex", "#.b"))
        else:
            run(broker.bind_queue("/", "qn", "ex", "",
                                  {"x-match": "any", "h": 1}))
    elif change == "unbind":
        key, queue, args = next(
            b for b in matcher.bindings() if b[1] == "q1")
        run(broker.unbind_queue("/", queue, "ex", key, args))
    else:
        run(broker.delete_queue("/", "q0"))
    after = _oracle(matcher, items)
    assert after != before  # the change is one these messages can see

    assert _flush(broker, items) == after
    new = broker.router._compiled[("/", "ex")]
    assert new is not old and new.generation > old.generation
    assert broker.metrics.router_table_uploads == 2
    assert new._resident is not None and new._resident is not old._resident
    # a flush that resolved the old snapshot before the change still routes
    # against the old table, on the device
    old._route_memo.clear()
    launches = broker.metrics.router_kernel_launches
    stale = route_batch(old, items, "jax", broker.metrics)
    assert [set(n) for n in stale] == before
    assert broker.metrics.router_kernel_launches == launches + 1
    assert broker.metrics.router_table_uploads == 2


def _parents_tokenizer(wild: dict, keys: list, b: int):
    """`_tokenize_topic` as it was while a launch carried three operands."""
    p, s, vocab = wild["p"], wild["s"], wild["vocab"]
    pre_m = np.full((b, p), rcompile.MISS, dtype=np.int32)
    suf_m = np.full((b, s), rcompile.MISS, dtype=np.int32)
    mlen = np.zeros(b, dtype=np.int32)
    for i, key in enumerate(keys):
        words = key.split(".") if key else [""]
        m = len(words)
        mlen[i] = m
        for j in range(min(m, p)):
            pre_m[i, j] = vocab.get(words[j], rcompile.MISS)
        for j in range(min(m, s)):
            suf_m[i, s - 1 - j] = vocab.get(words[m - 1 - j], rcompile.MISS)
    return pre_m, suf_m, mlen


@pytest.mark.parametrize("keys", [
    ["a", "c.d", ""],                               # shorter than p and s
    ["a.b.c.d", "x.b.c.y"],                         # p words: equal
    ["a.b", "e.f"],                                 # s words: equal
    ["a.b.c.d.e.f.g", "z.z.z.z.z.z.z.z.z.e.f"],     # longer than both
    [""],                                           # the empty key alone
    ["a.b.c.d", "", "e.f", "q", "a..f", "a.b.c.d.e.f"],
], ids=["shorter", "equal_p", "equal_s", "longer", "empty", "mixed"])
def test_the_packed_operand_slices_into_the_three_the_kernel_takes(keys):
    import jax

    m = TopicMatcher()
    m.bind("a.b.c.*", "q0")   # p = 4
    m.bind("#.e.f", "q1")     # s = 2
    m.bind("a.#.f", "q2")
    compiled = compile_exchange("topic", m.bindings())
    wild = compiled.wild
    p, s = wild["p"], wild["s"]
    assert (p, s) == (4, 2)
    b = rcompile._bucket(len(keys), 16)
    packed = rcompile._tokenize_topic(wild, keys, b)
    assert packed.shape == (b, p + s + 1) and packed.dtype == np.int32
    assert packed.flags["C_CONTIGUOUS"]
    want = _parents_tokenizer(wild, keys, b)
    for got, ref in zip(rcompile._split_topic(packed, p, s), want):
        assert got.shape == ref.shape and np.array_equal(got, ref)
    # and as the jitted wrapper slices it, on the device
    on_device = jax.jit(
        lambda x: rcompile._split_topic(x, p, s))(packed)
    for got, ref in zip(on_device, want):
        assert np.array_equal(np.asarray(got), ref)
    got = _route_all_backends(compiled, [(k, None) for k in keys])
    assert [set(n) for n in got] == [m.route(k) for k in keys]


@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_the_numpy_backend_never_imports_jax_and_never_uploads(kind):
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys\n"
        "from chanamq_tpu.router.compile import compile_exchange, route_batch\n"
        "from chanamq_tpu.utils.metrics import Metrics\n"
        f"kind = {kind!r}\n"
        "if kind == 'topic':\n"
        "    bindings = [('a.*', 'q0', None), ('#.z', 'q1', None)]\n"
        "    items = [(f'a.k{i}', None) for i in range(20)] + [('m.z', None)]\n"
        "else:\n"
        "    bindings = [('', 'q0', {'x-match': 'any', 'h': 1})]\n"
        "    items = [('', {'h': i % 2}) for i in range(20)]\n"
        "compiled = compile_exchange(kind, bindings)\n"
        "metrics = Metrics()\n"
        "out = route_batch(compiled, items, 'python', metrics)\n"
        "assert sum(1 for names in out if names) >= 10\n"
        "assert compiled._resident is None\n"
        "assert metrics.router_table_uploads == 0\n"
        "assert metrics.router_h2d_bytes == 0\n"
        "print('jax' in sys.modules)\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, PYTHONPATH=root))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "False"


@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_a_table_over_the_cap_raises_and_uploads_nothing(event_loop, kind):
    broker, items = _mk_broker(event_loop, kind)
    router, metrics = broker.router, broker.metrics
    router.max_wildcards = 2  # `_small_table` has more kernel rows
    matcher = broker.vhosts["/"].exchanges["ex"].matcher
    with pytest.raises(Uncompilable):
        compile_exchange(kind, matcher.bindings(), max_wildcards=2)
    assert _flush(broker, items) == _oracle(matcher, items)
    assert isinstance(router._compiled[("/", "ex")], str)  # the reason
    assert metrics.router_fallback_msgs == len(items)
    assert metrics.router_kernel_launches == 0
    assert metrics.router_table_uploads == 0
    assert metrics.router_h2d_bytes == 0


# ---------------------------------------------------------------------------
# engine: incremental recompile, generations, fallback, verify mode
# ---------------------------------------------------------------------------


def _mk_broker_with_topic(loop):
    broker = Broker()
    loop.run_until_complete(broker.create_vhost("/"))
    loop.run_until_complete(broker.declare_exchange("/", "ex", "topic"))
    loop.run_until_complete(broker.declare_queue("/", "q1"))
    loop.run_until_complete(broker.declare_queue("/", "q2"))
    loop.run_until_complete(broker.bind_queue("/", "q1", "ex", "a.*"))
    loop.run_until_complete(broker.bind_queue("/", "q2", "ex", "a.b"))
    return broker


def _entries(pairs):
    props = BasicProperties()
    return [(ex, rk, props, b"x", None, None, False) for ex, rk in pairs]


def test_engine_route_and_incremental_recompile(event_loop):
    broker = _mk_broker_with_topic(event_loop)
    router = broker.router
    router.min_batch = 1
    routes, _, _ = router.route_pending("/", _entries([("ex", "a.b")] * 4))
    assert sorted(q.name for q in routes[0]) == ["q1", "q2"]
    gen1 = router.generation
    assert broker.metrics.router_compiles == 1
    # routing again: same snapshot, no recompile
    router.route_pending("/", _entries([("ex", "a.c")]))
    assert router.generation == gen1
    # bind marks exactly this exchange dirty; next flush recompiles
    event_loop.run_until_complete(
        broker.bind_queue("/", "q2", "ex", "c.#"))
    routes, _, _ = router.route_pending("/", _entries([("ex", "c.x.y")]))
    assert [q.name for q in routes[0]] == ["q2"]
    assert router.generation == gen1 + 1
    assert broker.metrics.router_compiles == 2


def test_engine_python_backend_and_fallback(event_loop):
    broker = _mk_broker_with_topic(event_loop)
    router = broker.router
    router.min_batch = 1
    router.backend = "python"
    routes, _, _ = router.route_pending("/", _entries([("ex", "a.z")]))
    assert [q.name for q in routes[0]] == ["q1"]
    # an uncompilable table falls back to the matcher transparently
    event_loop.run_until_complete(
        broker.bind_queue("/", "q1", "ex", "#.mid.#"))
    before = broker.metrics.router_fallback_msgs
    routes, _, _ = router.route_pending("/", _entries([("ex", "x.mid.y")]))
    assert [q.name for q in routes[0]] == ["q1"]
    assert broker.metrics.router_fallback_msgs == before + 1


def test_kernel_launches_count_only_flushes_that_reach_the_device(event_loop):
    """router_batches counts memo hits, host dicts and kernel calls alike;
    router_kernel_launches counts the jitted calls alone."""
    broker = _mk_broker_with_topic(event_loop)
    router, metrics = broker.router, broker.metrics
    router.min_batch = 1
    router.route_pending("/", _entries([("ex", "a.b"), ("ex", "a.c")]))
    assert (metrics.router_batches, metrics.router_kernel_launches) == (1, 1)
    # the same keys again: the key memo serves them, no kernel call
    router.route_pending("/", _entries([("ex", "a.b"), ("ex", "a.c")]))
    assert (metrics.router_batches, metrics.router_kernel_launches) == (2, 1)
    # the numpy twin of the kernel is a batch, but not a device launch
    router.backend = "python"
    router.route_pending("/", _entries([("ex", "a.new")]))
    assert (metrics.router_batches, metrics.router_kernel_launches) == (3, 1)
    assert metrics.snapshot()["router_kernel_launches"] == 1


@pytest.mark.parametrize("backend,holds_device", [
    ("jax", True), ("python", False)])
def test_router_claims_the_device_at_construction(backend, holds_device,
                                                  caplog):
    """Backend jax names its device when the router is built (not at the
    first wildcard flush); backend python holds none; each says which."""
    with caplog.at_level("INFO", logger="chanamq.router"):
        broker = Broker(router_backend=backend)
    device = broker.router.device
    assert (device is not None) == holds_device
    if holds_device:
        assert device.platform == "cpu"  # conftest asked for it
        assert device.count >= 1 and device.kind
    assert f"backend={backend}" in caplog.text


def test_unknown_router_backend_is_a_config_error():
    from chanamq_tpu.config import ConfigError

    with pytest.raises(ConfigError, match="chana.mq.router.backend"):
        Broker(router_backend="numpy")


def test_engine_min_batch_falls_back(event_loop):
    broker = _mk_broker_with_topic(event_loop)
    router = broker.router
    router.min_batch = 8
    before = broker.metrics.router_fallback_msgs
    routes, _, _ = router.route_pending("/", _entries([("ex", "a.b")] * 3))
    assert broker.metrics.router_fallback_msgs == before + 3
    assert sorted(q.name for q in routes[0]) == ["q1", "q2"]
    assert broker.metrics.router_batches == 0


def test_engine_verify_mode_clean(event_loop):
    broker = _mk_broker_with_topic(event_loop)
    router = broker.router
    router.min_batch = 1
    router.verify = True
    router.route_pending(
        "/", _entries([("ex", k) for k in ("a.b", "a.x", "q", "", "a.b.c")]))
    assert broker.metrics.router_parity_mismatches == 0


def test_engine_defer_ok_gates(event_loop):
    broker = _mk_broker_with_topic(event_loop)
    router = broker.router
    assert router.defer_ok("/", "ex")
    assert not router.defer_ok("/", "")           # default exchange
    assert not router.defer_ok("/", "missing")    # no such exchange
    event_loop.run_until_complete(
        broker.declare_exchange("/", "alt-ex", "topic",
                                arguments={"alternate-exchange": "ex"}))
    assert not router.defer_ok("/", "alt-ex")     # alternate semantics
    event_loop.run_until_complete(broker.declare_exchange("/", "e2", "fanout"))
    assert router.defer_ok("/", "e2")
    event_loop.run_until_complete(
        broker.bind_exchange("/", "ex", "e2", "k"))
    assert router.defer_ok("/", "e2")             # e2e closure compiles
    # wildcard hop over a wildcard sub-closure cannot flatten: the walk stays
    event_loop.run_until_complete(broker.declare_exchange("/", "e3", "topic"))
    event_loop.run_until_complete(
        broker.bind_exchange("/", "ex", "e3", "x.*"))
    assert not router.defer_ok("/", "e3")         # uncompilable e2e graph


# ---------------------------------------------------------------------------
# end-to-end: deferred fused publishes through a live connection
# ---------------------------------------------------------------------------

pytest_plugins: list = []


@pytest.fixture
def server(event_loop):
    srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    event_loop.run_until_complete(srv.start())
    yield srv
    event_loop.run_until_complete(srv.stop())


def test_deferred_publish_end_to_end(event_loop, server):
    async def run():
        c = await AMQPClient.connect("127.0.0.1", server.bound_port)
        ch = await c.channel()
        await ch.exchange_declare("ex", "topic")
        await ch.queue_declare("q1")
        await ch.queue_bind("q1", "ex", "a.*.c")
        await ch.queue_bind("q1", "ex", "exact.key")
        await ch.confirm_select()
        for _ in range(100):
            ch.basic_publish(b"m", exchange="ex", routing_key="a.b.c")
        for _ in range(20):
            ch.basic_publish(b"m", exchange="ex", routing_key="miss")
        await ch.wait_unconfirmed_below(1)
        await c.close()

    event_loop.run_until_complete(run())
    metrics = server.broker.metrics
    assert metrics.router_batch_msgs >= 100
    assert metrics.router_batches >= 1
    assert metrics.router_parity_mismatches == 0
    q1 = server.broker.vhosts["/"].queues["q1"]
    assert q1.message_count == 100


def test_deferred_publish_fifo_with_nondeferrable(event_loop, server):
    """Deferred (topic) and non-deferrable (default-exchange) publishes on
    one channel must land in queue order — the flush-before-publish rule."""
    async def run():
        c = await AMQPClient.connect("127.0.0.1", server.bound_port)
        ch = await c.channel()
        await ch.exchange_declare("ex", "topic")
        await ch.queue_declare("q")
        await ch.queue_bind("q", "ex", "k.*")
        await ch.confirm_select()
        for i in range(30):
            if i % 3 == 2:
                # default exchange: never deferred
                ch.basic_publish(str(i).encode(), exchange="",
                                 routing_key="q")
            else:
                ch.basic_publish(str(i).encode(), exchange="ex",
                                 routing_key="k.x")
        await ch.wait_unconfirmed_below(1)
        got = []
        while True:
            msg = await ch.basic_get("q", no_ack=True)
            if msg is None:
                break
            got.append(int(msg.body))
        assert got == list(range(30))
        await c.close()

    event_loop.run_until_complete(run())


def test_router_disabled_still_routes(event_loop):
    async def run():
        srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
        await srv.start()
        srv.broker.router = None  # runtime-off: inline publish_sync path
        try:
            c = await AMQPClient.connect("127.0.0.1", srv.bound_port)
            ch = await c.channel()
            await ch.exchange_declare("ex", "topic")
            await ch.queue_declare("q")
            await ch.queue_bind("q", "ex", "a.#")
            await ch.confirm_select()
            for _ in range(25):
                ch.basic_publish(b"m", exchange="ex", routing_key="a.b")
            await ch.wait_unconfirmed_below(1)
            assert srv.broker.vhosts["/"].queues["q"].message_count == 25
            assert srv.broker.metrics.router_batches == 0
            await c.close()
        finally:
            await srv.stop()

    event_loop.run_until_complete(run())
