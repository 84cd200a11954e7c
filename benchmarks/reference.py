"""The benchmark's plain reference: what AMQP routing says each message must
reach, the seeded message stream, the comparison that decides `correct`, its
controls, and the percentile/rate arithmetic.

Nothing here imports the program (`chanamq_tpu`) or jax. The matchers are the
AMQP 0-9-1 definitions written out: a direct binding matches the identical
routing key, a fanout binding everything, a topic pattern is matched word by
word (`*` exactly one word, `#` zero or more), a headers binding by `x-match`
all/any over its arguments; over exchange-to-exchange bindings a message is
walked breadth first from the exchange it was published to, every hop
matched against its ORIGINAL routing key and headers (RabbitMQ's e2e
semantics). `*_matches` are the one-binding-one-message definitions and
`expected_sets_plain` the walk written out; `expected_sets` evaluates a whole
pool against a whole table with numpy, one binding at a time over all
messages (65,536 keys x 10,512 patterns is minutes of set-up in a Python
double loop), and the tests hold the two to each other and to the program's
matchers and graph walk.

`applied` resolves what else a configuration file states of its deployment
(durability, consumer acknowledgements, broker options) with its defaults,
for run.py and loadgen.py alike.
"""

from __future__ import annotations

import importlib
import json
import os
import random
import zlib

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# the message stream repeats after this many draws (a run publishes far
# fewer): seq -> pool entry is draws[seq % STREAM_LEN]
STREAM_LEN = 1 << 22


# -- files found by name -------------------------------------------------------


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_config(name: str, scale: str = "full") -> dict:
    """benchmarks/configs/<name>.json with one of its `scales` applied to
    `table` (the sizes of a CPU rehearsal; `full` is the file as written)."""
    cfg = load_json("configs", f"{name}.json")
    if scale != "full":
        cfg["table"].update(cfg["scales"][scale])
    return cfg


def load_traffic(name: str, scale: str = "full") -> dict:
    mix = load_json("traffic", f"{name}.json")
    if scale != "full":
        mix.update(mix["scales"][scale])
    return mix


APPLIED_DEFAULTS = {"durable": False, "delivery_mode": None,
                    "consumer_ack": None, "broker_options": {}}


def applied(cfg: dict) -> dict:
    """The configuration's `applied` section, every key present. Absent,
    each means what every run did before the section existed:

    durable         false: exchanges and queues are declared transient
    delivery_mode   null: the property is not sent (a topic publish carries
                    no properties at all); 2 marks every publish persistent
    consumer_ack    null: consumers subscribe `no_ack`; {"prefetch": n,
                    "multiple_every": k}: basic.qos(prefetch_count=n),
                    manual acks, one every k deliveries of a channel (with
                    `multiple` when k > 1) and one last at `stop`
    broker_options  {}: the broker starts with no option; `chana.mq.*` keys
                    reach it as a --config file
    """
    stated = cfg.get("applied", {})
    unknown = set(stated) - set(APPLIED_DEFAULTS)
    if unknown:
        raise ValueError(f"unknown keys in `applied`: {sorted(unknown)}")
    out = dict(APPLIED_DEFAULTS, **stated)
    if out["delivery_mode"] not in (None, 1, 2):
        raise ValueError(f"delivery_mode {out['delivery_mode']!r}")
    ack = out["consumer_ack"]
    if ack is not None and (
            set(ack) != {"prefetch", "multiple_every"}
            or ack["prefetch"] < 0 or ack["multiple_every"] < 1):
        raise ValueError(f"consumer_ack {ack!r}")
    stray = [k for k in out["broker_options"] if not k.startswith("chana.mq.")]
    if stray:
        raise ValueError(f"broker_options that are no chana.mq.* key: {stray}")
    return out


def table_module(cfg: dict):
    """benchmarks/tables/<generator>.py: `table(params)` and
    `pool(params, table, n, rng)` for one shape of deployment."""
    return importlib.import_module(f"tables.{cfg['table']['generator']}")


def build_table(cfg: dict) -> dict:
    """{"exchange", "type", "queues": [names], "bindings": [(key, queue,
    args-or-None)]} — a pure function of the configuration file. A graph
    adds "exchanges": [(name, type)] (every exchange, the published one
    among them), "queue_bindings": [(exchange, queue, key, args)] for queues
    bound to the other exchanges, and "exchange_bindings": [(source,
    destination, key, args)]; `exchange` stays the one published to and
    `bindings` its own."""
    return table_module(cfg).table(cfg["table"])


def build_pool(cfg: dict, table: dict, mix: dict) -> list:
    """`pool_size` distinct (routing_key, headers-or-None) entries from the
    mix's own `pool_seed`: every --seed sends the same set of messages, in
    another order (`stream_draws`), so a seed changes the arrivals and not
    the amount of work."""
    return table_module(cfg).pool(
        cfg["table"], table, mix["pool_size"], random.Random(mix["pool_seed"]))


def warmup_bursts(mix: dict) -> list:
    """Burst sizes the first producer sends alone, each confirmed before the
    next, before any producer free-runs: one per power-of-two batch bucket
    the router can see from this confirm window and the whole window last,
    so each kernel shape is compiled (or read from the cache) in set-up."""
    sizes, n = [], 16
    while n < mix["confirm_window"]:
        sizes.append(n)
        n *= 2
    return sizes + [mix["confirm_window"]]


def stream_draws(mix: dict, seed: int) -> np.ndarray:
    """Pool index of every stream position, from --seed. The first
    `hot_size` pool entries are the fleet that keeps sending (a draw falls
    among them with probability `hot_share`); the others are keys seen
    rarely, drawn uniformly. `hot_size` 0, the default, is a uniform draw
    over the whole pool. The first producer's warm-up positions hold
    entries that are all distinct and outside the fleet, so a burst of n
    messages puts n unseen keys before the router, whatever it has
    memoised."""
    rng = np.random.default_rng(seed)
    n, hot = mix["pool_size"], mix.get("hot_size", 0)
    draws = rng.integers(hot, n, size=STREAM_LEN, dtype=np.int32)
    if hot:
        fleet = rng.random(STREAM_LEN) < mix["hot_share"]
        draws[fleet] = rng.integers(0, hot, size=int(fleet.sum()),
                                    dtype=np.int32)
    opening = sum(warmup_bursts(mix))
    # (a pool smaller than the opening, as in a rehearsal, goes round again)
    draws[:opening * mix["producers"]:mix["producers"]] = hot + np.resize(
        rng.permutation(n - hot), opening)
    return draws


# -- the definitions -----------------------------------------------------------


def direct_matches(binding_key: str, key: str) -> bool:
    """AMQP direct match: the binding's key is the routing key."""
    return binding_key == key


def fanout_matches() -> bool:
    """AMQP fanout match: every binding takes every message."""
    return True


def topic_matches(pattern: str, key: str) -> bool:
    """AMQP topic match of one pattern against one routing key."""
    pat, words = pattern.split("."), key.split(".")

    def at(i: int, j: int) -> bool:
        if i == len(pat):
            return j == len(words)
        if pat[i] == "#":
            return any(at(i + 1, k) for k in range(j, len(words) + 1))
        if j == len(words):
            return False
        return pat[i] in ("*", words[j]) and at(i + 1, j + 1)

    return at(0, 0)


def headers_matches(args: dict, headers: "dict | None") -> bool:
    """AMQP headers match: every (`x-match` all, the default) or any one
    (`any`) of the binding's arguments is present and equal in the message.
    A binding with no arguments matches everything under `all`, nothing
    under `any`."""
    headers = headers or {}
    want = {k: v for k, v in args.items() if k != "x-match"}
    hits = [k in headers and headers[k] == v for k, v in want.items()]
    if str(args.get("x-match", "all")).lower() == "any":
        return any(hits)
    return all(hits)


def binding_matches(kind: str, binding_key: str, args: "dict | None",
                    key: str, headers: "dict | None") -> bool:
    """One binding of an exchange of type `kind` against one message."""
    if kind == "direct":
        return direct_matches(binding_key, key)
    if kind == "fanout":
        return fanout_matches()
    if kind == "topic":
        return topic_matches(binding_key, key)
    if kind == "headers":
        return headers_matches(args or {}, headers)
    raise ValueError(f"no reference matcher for a {kind!r} exchange")


def table_exchanges(table: dict) -> list:
    """[(name, type)] of every exchange of a table: a table that lists none
    is its one exchange."""
    return table.get("exchanges", [(table["exchange"], table["type"])])


def exchange_graph(table: dict) -> dict:
    """{exchange: (type, [(key, queue, args)], [(key, destination, args)])}."""
    graph = {name: (kind, [], []) for name, kind in table_exchanges(table)}
    graph[table["exchange"]][1].extend(table["bindings"])
    for exchange, queue, key, args in table.get("queue_bindings", []):
        graph[exchange][1].append((key, queue, args))
    for source, destination, key, args in table.get("exchange_bindings", []):
        graph[source][2].append((key, destination, args))
    return graph


def expected_sets_plain(table: dict, pool: list) -> list:
    """The definitions applied pair by pair (tests and small tables): each
    message walked breadth first from the published exchange, every hop
    matched against its original key and headers, an exchange visited once
    (so a cycle ends), a destination that is not declared leading nowhere,
    a queue reached by several paths counted once."""
    graph = exchange_graph(table)
    out = []
    for key, headers in pool:
        queues: set = set()
        visited: set = set()
        frontier = [table["exchange"]]
        while frontier:
            hop = []
            for name in frontier:
                if name in visited or name not in graph:
                    continue
                visited.add(name)
                kind, to_queues, to_exchanges = graph[name]
                queues.update(q for k, q, args in to_queues
                              if binding_matches(kind, k, args, key, headers))
                hop.extend(d for k, d, args in to_exchanges
                           if binding_matches(kind, k, args, key, headers))
            frontier = hop
        out.append(frozenset(queues))
    return out


# -- the same, a binding at a time over the whole pool -------------------------


def _narrow(token, bits: int) -> int:
    return zlib.crc32(repr(token).encode()) & ((1 << bits) - 1)


def _topic_hits(patterns: list, keys: list,
                id_bits: "int | None" = None) -> "list[np.ndarray]":
    """For each pattern the indexes of the keys it matches. A pattern with
    no wildcard matches the identical key and no other, so those go through
    a dict; the others are walked word by word over all keys at once (one
    `#` at most; more fall to `topic_matches`). With `id_bits` the words
    are compared by a hash of that many bits (the control)."""
    split = [k.split(".") for k in keys]
    vocab: dict = {}
    if id_bits is not None:
        vocab = {w: _narrow(w, id_bits) for ws in split for w in ws}
        vocab.update({t: _narrow(t, id_bits) for pat in patterns
                      for t in pat.split(".")})
    width = max(len(w) for w in split)
    words = np.full((len(keys), width), -1, dtype=np.int64)
    lens = np.zeros(len(keys), dtype=np.int64)
    for i, ws in enumerate(split):
        lens[i] = len(ws)
        for j, w in enumerate(ws):
            words[i, j] = vocab.setdefault(w, len(vocab))
    position: dict = {}
    for i, key in enumerate(keys):
        position.setdefault(key, []).append(i)
    rows = np.arange(len(keys))
    none = np.zeros(0, dtype=np.int64)
    out = []
    for pattern in patterns:
        toks = pattern.split(".")
        if "*" not in toks and "#" not in toks:
            out.append(np.array(position.get(pattern, none), dtype=np.int64))
            continue
        if toks.count("#") > 1:
            out.append(np.array([i for i, k in enumerate(keys)
                                 if topic_matches(pattern, k)], dtype=np.int64))
            continue
        if "#" in toks:
            cut = toks.index("#")
            head, tail = toks[:cut], toks[cut + 1:]
            hit = lens >= len(head) + len(tail)
        else:
            head, tail = toks, []
            hit = lens == len(head)
        for j, tok in enumerate(head):
            if tok != "*":
                hit &= words[:, min(j, width - 1)] == vocab.get(tok, -2)
        for j, tok in enumerate(reversed(tail)):
            if tok != "*":
                col = np.clip(lens - 1 - j, 0, width - 1)
                hit &= words[rows, col] == vocab.get(tok, -2)
        out.append(np.nonzero(hit)[0])
    return out


def _headers_hits(bindings: list, pool: list,
                  id_bits: "int | None" = None) -> "list[np.ndarray]":
    """For each binding the indexes of the messages it matches. `have`
    holds one column per (header, value) pair a message may carry; with
    `id_bits` a pair's column is a hash of that many bits, so pairs that
    collide match (the control)."""
    column: dict = {}

    def col(name, value) -> int:
        pair = (name, type(value).__name__, value)
        if id_bits is not None:
            return _narrow(pair, id_bits)
        return column.setdefault(pair, len(column))

    cells = [(i, col(k, v)) for i, (_, headers) in enumerate(pool)
             for k, v in (headers or {}).items()]
    wants = [[col(k, v) for k, v in (args or {}).items() if k != "x-match"]
             for _, _, args in bindings]
    width = (1 << id_bits) if id_bits is not None else max(1, len(column))
    have = np.zeros((len(pool), width), dtype=bool)
    if cells:
        have[tuple(np.array(cells).T)] = True
    out = []
    for (_, _, args), want in zip(bindings, wants):
        if str((args or {}).get("x-match", "all")).lower() == "any":
            hit = have[:, want].any(axis=1)
        else:
            hit = have[:, want].all(axis=1)
        out.append(np.nonzero(hit)[0])
    return out


class Expected:
    """The queues each pool entry must reach, as CSR over queue ids."""

    def __init__(self, offsets: np.ndarray, queue_ids: np.ndarray) -> None:
        self.offsets = offsets
        self.queue_ids = queue_ids

    def of(self, i: int) -> frozenset:
        return frozenset(
            self.queue_ids[self.offsets[i]:self.offsets[i + 1]].tolist())

    def pairs(self, seqs: np.ndarray, entries: np.ndarray) -> np.ndarray:
        """queue<<32 | seq for every delivery due to messages `seqs` that
        carry pool entries `entries`, sorted."""
        counts = self.offsets[entries + 1] - self.offsets[entries]
        total = int(counts.sum())
        first = np.repeat(self.offsets[entries], counts)
        within = np.arange(total) - np.repeat(
            np.cumsum(counts) - counts, counts)
        queues = self.queue_ids[first + within].astype(np.uint64)
        out = (queues << np.uint64(32)) | np.repeat(
            seqs.astype(np.uint64), counts)
        out.sort()
        return out


def _hits(kind: str, bindings: list, pool: list,
          id_bits: "int | None") -> "list[np.ndarray]":
    """For each (key, target, args) binding of one exchange of type `kind`
    the indexes of the pool entries it matches."""
    if kind == "topic":
        return _topic_hits([b[0] for b in bindings], [p[0] for p in pool],
                           id_bits)
    if kind == "headers":
        return _headers_hits(bindings, pool, id_bits)
    if kind == "fanout":
        return [np.arange(len(pool))] * len(bindings)
    if kind == "direct":
        def name(key):
            return key if id_bits is None else _narrow(key, id_bits)
        position: dict = {}
        for i, (key, _) in enumerate(pool):
            position.setdefault(name(key), []).append(i)
        none: list = []
        return [np.array(position.get(name(b[0]), none), dtype=np.int64)
                for b in bindings]
    raise ValueError(f"no reference matcher for a {kind!r} exchange")


def expected_sets(table: dict, pool: list,
                  id_bits: "int | None" = None) -> Expected:
    """What the reference says every pool entry reaches. `id_bits` is the
    control's matcher (see `control_pairs`). Over a graph, `at[exchange]`
    marks the entries that reach each exchange: it starts as all of them at
    the published exchange and grows along the exchange bindings until
    nothing moves, which is the breadth-first walk of every entry at once
    (what a hop matches depends on the message alone, never on its path)."""
    queue_id = {q: i for i, q in enumerate(table["queues"])}
    n_queues = len(queue_id)
    graph = exchange_graph(table)
    at = {name: np.zeros(len(pool), dtype=bool) for name in graph}
    at[table["exchange"]][:] = True
    hops = [(source, destination, hit)
            for source, (kind, _, to_exchanges) in graph.items()
            for (_, destination, _), hit in zip(
                to_exchanges, _hits(kind, to_exchanges, pool, id_bits))
            if destination in graph]
    moved = True
    while moved:
        moved = False
        for source, destination, hit in hops:
            new = hit[at[source][hit] & ~at[destination][hit]]
            if new.size:
                at[destination][new] = moved = True
    reach = [np.zeros(0, dtype=np.int64)]
    for name, (kind, to_queues, _) in graph.items():
        here = None if at[name].all() else at[name]
        for (_, queue, _), hit in zip(
                to_queues, _hits(kind, to_queues, pool, id_bits)):
            if here is not None:
                hit = hit[here[hit]]
            reach.append(hit * n_queues + queue_id[queue])
    reach = np.unique(np.concatenate(reach))
    entry, queues = reach // n_queues, reach % n_queues
    offsets = np.zeros(len(pool) + 1, dtype=np.int64)
    np.cumsum(np.bincount(entry, minlength=len(pool)), out=offsets[1:])
    return Expected(offsets, queues)


# -- the comparison ------------------------------------------------------------

# every number compared is a count of broken promises; the configuration
# states exactly-once to exactly the bound queues, so every limit is 0.
# `unsettled` is compared only where the configuration's consumers
# acknowledge: the messages the broker still holds, ready or unacked, after
# the consumers' last ack
LIMITS = {"unconfirmed": 0, "missing": 0, "unexpected": 0, "duplicates": 0,
          "unsettled": 0}


def compare(expected_pairs: np.ndarray, delivered_pairs: np.ndarray,
            published: int, confirmed: int) -> "tuple[dict, np.ndarray]":
    """The numbers that decide `correct`, and the seqs of messages that a
    queue missed, got twice, or got without being bound to them.

    Both arrays hold queue<<32|seq, one element per delivery."""
    delivered = np.sort(delivered_pairs)
    once, counts = np.unique(delivered, return_counts=True)
    missing = np.setdiff1d(expected_pairs, once, assume_unique=True)
    unexpected = np.setdiff1d(once, expected_pairs, assume_unique=True)
    doubled = once[counts > 1]
    numbers = {
        "unconfirmed": int(published - confirmed),
        "missing": int(missing.size),
        "unexpected": int(unexpected.size),
        "duplicates": int((counts - 1).sum()),
    }
    bad = np.concatenate([missing, unexpected, doubled]) & np.uint64(
        0xFFFFFFFF)
    return numbers, np.unique(bad)


def is_correct(numbers: dict) -> bool:
    return all(value <= LIMITS[name] for name, value in numbers.items())


def compared_report(numbers: dict) -> dict:
    return {name: {"value": numbers[name], "limit": LIMITS[name]}
            for name in LIMITS if name in numbers}


# -- controls: the reference in the program's place, one guarantee broken ------

CONTROLS = ("narrow_ids", "at_least_once", "at_most_once")
# the kernels compare int32 ids of words and of (header, value) pairs; the
# step below that a later PR could be tempted by is a narrower id
CONTROL_ID_BITS = 8


def control_pairs(control: str, table: dict, pool: list, seqs: np.ndarray,
                  entries: np.ndarray, exact: Expected,
                  confirm_window: int) -> np.ndarray:
    """The deliveries a broker with one weaker guarantee would make of the
    same confirmed publishes:

    narrow_ids     the matcher at the precision below the one the tables are
                   compiled in: words and header values compared by 8-bit
                   ids where the program uses int32, so two that collide
                   match (an approximate answer where it was exact)
    at_least_once  the last confirm window of each run is delivered again,
                   as after a reconnect without deduplication
    at_most_once   the last confirm window is confirmed and never delivered
    """
    pairs = exact.pairs(seqs, entries)
    if control == "narrow_ids":
        return expected_sets(table, pool, CONTROL_ID_BITS).pairs(
            seqs, entries)
    tail = exact.pairs(seqs[-confirm_window:], entries[-confirm_window:])
    if control == "at_least_once":
        return np.concatenate([pairs, tail])
    if control == "at_most_once":
        return np.setdiff1d(pairs, tail, assume_unique=True)
    raise ValueError(f"unknown control {control!r}")


# -- arithmetic ----------------------------------------------------------------


def percentile_ms(latency_ns: np.ndarray, q: float) -> float:
    """The q-th percentile of all latencies pooled, in milliseconds."""
    return float(np.percentile(latency_ns, q)) / 1e6


def rate_per_s(count: int, seconds: float) -> float:
    """All the work over all the time of the window."""
    return count / seconds
