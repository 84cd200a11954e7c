"""The benchmark's plain reference: what AMQP routing says each message must
reach, the seeded message stream, the comparison that decides `correct`, its
controls, and the percentile/rate arithmetic.

Nothing here imports the program (`chanamq_tpu`) or jax. The matchers are the
AMQP 0-9-1 definitions written out: a topic pattern is matched word by word
(`*` exactly one word, `#` zero or more), a headers binding by `x-match`
all/any over its arguments. `topic_matches` / `headers_matches` are the
one-pattern-one-message definitions; `expected_sets` evaluates a whole pool
against a whole table with numpy, one binding at a time over all messages
(65,536 keys x 10,512 patterns is minutes of set-up in a Python double
loop), and the tests hold the two to each other and to the program's
matchers.
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


def table_module(cfg: dict):
    """benchmarks/tables/<generator>.py: `table(params)` and
    `pool(params, table, n, rng)` for one shape of deployment."""
    return importlib.import_module(f"tables.{cfg['table']['generator']}")


def build_table(cfg: dict) -> dict:
    """{"exchange", "type", "queues": [names], "bindings": [(key, queue,
    args-or-None)]} — a pure function of the configuration file."""
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


def expected_sets_plain(table: dict, pool: list) -> list:
    """The definitions applied pair by pair (tests and small tables)."""
    out = []
    for key, headers in pool:
        if table["type"] == "topic":
            out.append(frozenset(q for pat, q, _ in table["bindings"]
                                 if topic_matches(pat, key)))
        else:
            out.append(frozenset(q for _, q, args in table["bindings"]
                                 if headers_matches(args or {}, headers)))
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


def expected_sets(table: dict, pool: list,
                  id_bits: "int | None" = None) -> Expected:
    """What the reference says every pool entry reaches. `id_bits` is the
    control's matcher (see `control_pairs`)."""
    queue_id = {q: i for i, q in enumerate(table["queues"])}
    bindings = table["bindings"]
    kind = table["type"]
    if kind == "topic":
        hits = _topic_hits([b[0] for b in bindings], [p[0] for p in pool],
                           id_bits)
    elif kind == "headers":
        hits = _headers_hits(bindings, pool, id_bits)
    else:
        raise ValueError(f"no reference matcher for a {kind!r} exchange")
    n_queues = len(queue_id)
    reach = np.unique(np.concatenate(
        [hit * n_queues + queue_id[queue]
         for (_, queue, _), hit in zip(bindings, hits)]
        + [np.zeros(0, dtype=np.int64)]))
    entry, queues = reach // n_queues, reach % n_queues
    offsets = np.zeros(len(pool) + 1, dtype=np.int64)
    np.cumsum(np.bincount(entry, minlength=len(pool)), out=offsets[1:])
    return Expected(offsets, queues)


# -- the comparison ------------------------------------------------------------

# every number compared is a count of broken promises; the configuration
# states exactly-once to exactly the bound queues, so every limit is 0
LIMITS = {"unconfirmed": 0, "missing": 0, "unexpected": 0, "duplicates": 0}


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
    return all(numbers[name] <= limit for name, limit in LIMITS.items())


def compared_report(numbers: dict) -> dict:
    return {name: {"value": numbers[name], "limit": LIMITS[name]}
            for name in LIMITS}


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
