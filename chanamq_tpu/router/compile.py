"""Binding-table compiler + batch match kernels for the tensorized router.

This module is broker-free and pure: it turns one exchange's binding list
(the output of ``Matcher.bindings()``) into a ``CompiledExchange`` — host
dictionaries for the parts where a hash lookup already wins, and dense
tokenized matrices for the parts where a data-parallel kernel wins — and
evaluates whole publish batches against it.

Compilation strategy (a dict probe needs no launch, and a launch costs the
event loop 1.5 ms or more whatever it carries: PERF.md §5):

- **Exact patterns** (direct bindings; topic patterns without wildcards)
  stay a host dict ``routing_key -> queue names``. A dict probe is ~0.1µs;
  no kernel beats that, and a dense table over a million exact patterns
  would be a memory blowout for zero gain.
- **Always-match rows** (fanout bindings, the lone ``#`` topic pattern,
  empty x-match=all headers bindings) fold into one host set.
- **Wildcard topic patterns** and **headers bindings** become tokenized
  int32 matrices plus uint32 queue-bitmask rows, evaluated for the whole
  batch in ONE kernel call: match booleans ``[B, N]`` are expanded against
  the mask rows and OR-reduced into per-message destination bitmasks
  ``[B, mask_words]``. The same kernel body runs under ``jax.jit``
  (backend="jax") or plain numpy (backend="python" — the runtime-selectable
  pure-Python fallback; also what parity tests diff against jit).

Token encoding: literal words get vocab ids >= 0; ``STAR`` marks ``*``,
``PAD`` fills a row past its pattern's length, and message words absent
from the vocab (or past the message's length) are ``MISS``. The positional
match condition is ``(pat == tok) | (pat < 0)``: a negative pattern cell is
STAR or PAD and matches any position, while MISS (< 0 too, but only ever on
the *message* side) never equals a literal id. Length predicates do the
rest: a no-``#`` pattern needs ``m == plen``; a single-``#`` pattern splits
into a left-aligned prefix and a RIGHT-aligned suffix (compared against the
right-aligned last words of the message, so no dynamic gather is needed)
and requires ``m >= plen + slen``.

Not everything compiles. Patterns with more than one ``#``, headers
bindings with unhashable values, and tables past the wildcard/queue caps
raise ``Uncompilable`` — the caller keeps the Python matcher as the
always-available fallback for that exchange.

All array dims (pattern rows, prefix/suffix width, batch size, header
counts) are padded up to power-of-two buckets so jit retraces stay bounded
as tables and batches grow.

What a launch hands the device (backend="jax"): the batch, as ONE int32
array (a topic batch packs its prefix words, suffix words and word count
into ``[b, p+s+1]``; the jitted wrapper slices it), and nothing else. A
snapshot's tables go up once, at its first launch, and stay on the device
for the snapshot's life (``CompiledExchange._resident``): a snapshot is
immutable and is replaced, never edited, so a launch can only ever see its
own generation's table. On a TPU every host array handed to a jitted call
costs the calling thread ~0.1 ms, whatever its size (PERF.md section 6,
PR 32).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Iterable, Optional

import numpy as np

from .. import device

STAR = -1   # pattern cell: '*' (matches exactly one word)
PAD = -2    # pattern cell: beyond this pattern's length
MISS = -3   # message cell: out-of-vocab word, or beyond the message length

# a pattern prefix/suffix deeper than this is compiled nowhere: fall back
MAX_PATTERN_WORDS = 32

_EMPTY: frozenset = frozenset()

# the kernels' table operands (keys of ``wild`` / ``headers``), in the
# kernels' argument order; the batch's operands follow them
_TOPIC_TABLES = ("pre", "suf", "plen", "slen", "has_hash", "masks")
_HEADERS_TABLES = ("req", "rcount", "is_all", "masks")

# decoded (mask -> names) and routed (key -> names) memo caps, per compiled
# snapshot; snapshots are immutable so entries never go stale, the cap only
# bounds memory against hostile key cardinality
_MEMO_CAP = 8192


class Uncompilable(Exception):
    """This binding table cannot be tensorized; use the Python matcher."""

    def __init__(self, reason: str) -> None:
        super().__init__(reason)
        self.reason = reason


def _bucket(n: int, floor: int = 4) -> int:
    """Next power-of-two >= max(n, floor): bounds distinct jit trace shapes."""
    b = floor
    while b < n:
        b <<= 1
    return b


class CompiledExchange:
    """Immutable compiled snapshot of one exchange's binding table."""

    __slots__ = ("kind", "generation", "exact", "always", "bit_names",
                 "wild", "headers", "_route_memo", "_mask_memo", "_resident")

    def __init__(self, kind: str, generation: int) -> None:
        self.kind = kind
        self.generation = generation
        # routing_key -> frozenset of queue names (exact patterns)
        self.exact: dict[str, frozenset] = {}
        # queues every message matches (fanout / '#' / empty x-match=all)
        self.always: frozenset = _EMPTY
        # bitmask bit index -> queue name (kernel destinations only)
        self.bit_names: tuple = ()
        self.wild: Optional[dict] = None      # topic wildcard tables
        self.headers: Optional[dict] = None   # headers-exchange tables
        # bounded key memo, topic only: bare routing key -> names (the
        # match is a pure function of the key within this compiled
        # generation)
        self._route_memo: dict = {}
        # bounded mask memo, topic and headers: a kernel row's bytes ->
        # always | the names of its bits, decoded once per distinct mask
        self._mask_memo: dict = {}
        # kernel_tables() as device arrays: put on the chip by the first
        # backend="jax" launch through this snapshot (``_launch``) and
        # handed to every launch after; freed with the snapshot
        self._resident: Optional[tuple] = None

    @property
    def kernel_rows(self) -> int:
        if self.wild is not None:
            return self.wild["n"]
        if self.headers is not None:
            return self.headers["n"]
        return 0

    def kernel_tables(self) -> tuple:
        """The match kernel's table operands, in its argument order: the
        numpy arrays the compiler built, which the numpy twin reads."""
        if self.wild is not None:
            return tuple(self.wild[k] for k in _TOPIC_TABLES)
        return tuple(self.headers[k] for k in _HEADERS_TABLES)

    # -- mask decode -------------------------------------------------------

    def _decode_mask(self, row: np.ndarray) -> frozenset:
        names = []
        bit_names = self.bit_names
        for wi, w in enumerate(row.tolist()):
            base = wi << 5
            while w:
                low = w & -w
                names.append(bit_names[base + low.bit_length() - 1])
                w ^= low
        return frozenset(names)

    def _decode_rows(self, rows: np.ndarray, n: int) -> tuple:
        """The kernel's first ``n`` mask rows as ``always | names``, a
        frozenset a row, and how many masks had to be decoded for it. One
        ``tobytes`` for the launch; a row then costs a bytes slice and a
        memo hit, an all-zero row not even that. Rows with one mask share
        one frozenset, whose hash the queue cache computes once."""
        width = rows.shape[1] * rows.itemsize
        buf = rows[:n].tobytes()
        zero = bytes(width)
        memo = self._mask_memo
        always = self.always or _EMPTY  # the one empty set, whoever built it
        out = []
        decoded = 0
        for j, off in enumerate(range(0, n * width, width)):
            mk = buf[off:off + width]
            if mk == zero:
                out.append(always)
                continue
            names = memo.get(mk)
            if names is None:
                names = self._decode_mask(rows[j])
                if always:
                    names = always | names
                decoded += 1
                if len(memo) >= _MEMO_CAP:
                    memo.clear()
                memo[mk] = names
            out.append(names)
        return out, decoded


def compile_exchange(
    kind: str,
    bindings: Iterable[tuple[str, str, Optional[dict]]],
    *,
    generation: int = 0,
    max_wildcards: int = 512,
    max_queues: int = 4096,
) -> CompiledExchange:
    """Compile one exchange's ``Matcher.bindings()`` list. Raises
    ``Uncompilable`` when the table can't be tensorized faithfully."""
    kind = kind.lower()
    ce = CompiledExchange(kind, generation)
    if kind == "direct":
        exact: dict[str, set] = {}
        for key, queue, _ in bindings:
            exact.setdefault(key, set()).add(queue)
        ce.exact = {k: frozenset(v) for k, v in exact.items()}
        return ce
    if kind == "fanout":
        ce.always = frozenset(q for _, q, _ in bindings)
        return ce
    if kind == "topic":
        _compile_topic(ce, bindings, max_wildcards, max_queues)
        return ce
    if kind == "headers":
        _compile_headers(ce, bindings, max_wildcards, max_queues)
        return ce
    raise Uncompilable(f"unknown exchange type {kind!r}")


# -- topic -----------------------------------------------------------------


def _compile_topic(ce, bindings, max_wildcards: int, max_queues: int) -> None:
    exact: dict[str, set] = {}
    always: set = set()
    wild: dict[str, set] = {}  # pattern -> queues
    for key, queue, _ in bindings:
        toks = key.split(".")
        nhash = toks.count("#")
        if nhash == 0 and "*" not in toks:
            exact.setdefault(key, set()).add(queue)
        elif toks == ["#"]:
            always.add(queue)  # '#' alone matches every key
        elif nhash > 1:
            raise Uncompilable("multi-# pattern")
        else:
            wild.setdefault(key, set()).add(queue)
    ce.exact = {k: frozenset(v) for k, v in exact.items()}
    ce.always = frozenset(always)
    _build_wild_table(ce, wild, max_wildcards, max_queues)


def _build_wild_table(ce, wild: dict, max_wildcards: int,
                      max_queues: int) -> None:
    """Tokenize wildcard topic patterns (pattern -> queue-name set) into
    the kernel matrices. Shared by the single-exchange topic compile and
    the e2e closure compile (compile_effective)."""
    if not wild:
        return
    if len(wild) > max_wildcards:
        raise Uncompilable("wildcard pattern count over cap")
    bit_names = tuple(sorted({q for qs in wild.values() for q in qs}))
    if len(bit_names) > max_queues:
        raise Uncompilable("kernel queue count over cap")
    bit_of = {q: i for i, q in enumerate(bit_names)}
    vocab: dict[str, int] = {}
    rows = []
    for pattern, queues in wild.items():
        toks = pattern.split(".")
        if "#" in toks:
            hi = toks.index("#")
            pre_toks, suf_toks, has_hash = toks[:hi], toks[hi + 1:], True
        else:
            pre_toks, suf_toks, has_hash = toks, [], False
        if len(pre_toks) > MAX_PATTERN_WORDS or len(suf_toks) > MAX_PATTERN_WORDS:
            raise Uncompilable("pattern too deep")
        pre = [STAR if t == "*" else vocab.setdefault(t, len(vocab))
               for t in pre_toks]
        suf = [STAR if t == "*" else vocab.setdefault(t, len(vocab))
               for t in suf_toks]
        rows.append((pre, suf, has_hash, queues))
    n = _bucket(len(rows))
    p = _bucket(max((len(r[0]) for r in rows), default=1), 2)
    s = _bucket(max((len(r[1]) for r in rows), default=1), 2)
    mask_words = (len(bit_names) + 31) >> 5
    pre_t = np.full((n, p), PAD, dtype=np.int32)
    suf_t = np.full((n, s), PAD, dtype=np.int32)
    plen = np.zeros(n, dtype=np.int32)
    slen = np.zeros(n, dtype=np.int32)
    has_h = np.zeros(n, dtype=bool)
    masks = np.zeros((n, mask_words), dtype=np.uint32)
    for i, (pre, suf, hh, queues) in enumerate(rows):
        pre_t[i, :len(pre)] = pre
        # RIGHT-aligned: compared against the message's last-S words
        if suf:
            suf_t[i, s - len(suf):] = suf
        plen[i] = len(pre)
        slen[i] = len(suf)
        has_h[i] = hh
        for q in queues:
            b = bit_of[q]
            masks[i, b >> 5] |= np.uint32(1 << (b & 31))
        # padding rows past len(rows) keep all-zero masks: harmless
    ce.bit_names = bit_names
    ce.wild = {"n": len(rows), "vocab": vocab, "p": p, "s": s,
               "pre": pre_t, "suf": suf_t, "plen": plen, "slen": slen,
               "has_hash": has_h, "masks": masks, "mask_words": mask_words}


def compile_effective(
    exact: dict,
    always: Iterable[str],
    wild: dict,
    *,
    generation: int = 0,
    max_wildcards: int = 512,
    max_queues: int = 4096,
) -> CompiledExchange:
    """Compile a FLATTENED e2e closure (TensorRouter._closure_bindings):
    ``exact`` maps routing keys (string equality — covers direct bindings
    and wildcard-free topic patterns) to queue-name sets, ``always`` is
    the unconditional set (fanout members, lone-'#' patterns), ``wild``
    maps genuine topic wildcard patterns to queue-name sets. Compiled as
    kind "topic" because the topic evaluation path (exact dict + always +
    wildcard kernel) is the universal shape the closure folds into."""
    ce = CompiledExchange("topic", generation)
    ce.exact = {k: frozenset(v) for k, v in exact.items()}
    ce.always = frozenset(always)
    _build_wild_table(ce, dict(wild), max_wildcards, max_queues)
    return ce


def topic_match(pattern: str, key: str) -> bool:
    """One AMQP topic pattern against one concrete key, as a pure
    function ('*' = exactly one word, '#' = zero or more). Used at
    closure-compile time to evaluate hop-predicate conjunctions against
    known keys — never on the publish path."""
    pt = pattern.split(".")
    kt = key.split(".")
    memo: dict[tuple[int, int], bool] = {}

    def m(i: int, j: int) -> bool:
        got = memo.get((i, j))
        if got is not None:
            return got
        if i == len(pt):
            out = j == len(kt)
        elif pt[i] == "#":
            # zero words, or absorb one and stay on the '#'
            out = m(i + 1, j) or (j < len(kt) and m(i, j + 1))
        elif j == len(kt):
            out = False
        else:
            out = (pt[i] == "*" or pt[i] == kt[j]) and m(i + 1, j + 1)
        memo[(i, j)] = out
        return out

    return m(0, 0)


def _topic_kernel(xp, pre_t, suf_t, plen, slen, has_h, masks,
                  pre_m, suf_m, mlen):
    # [B,N,P]: positional match; a negative pattern cell (STAR/PAD) always
    # matches, and MISS on the message side never equals a literal id
    pm = (pre_t[None, :, :] == pre_m[:, None, :]) | (pre_t[None, :, :] < 0)
    sm = (suf_t[None, :, :] == suf_m[:, None, :]) | (suf_t[None, :, :] < 0)
    need = plen[None, :] + slen[None, :]
    len_ok = xp.where(has_h[None, :],
                      mlen[:, None] >= need,
                      mlen[:, None] == plen[None, :])
    ok = pm.all(axis=2) & sm.all(axis=2) & len_ok                 # [B,N]
    hit = masks[None, :, :] * ok[:, :, None].astype(xp.uint32)    # [B,N,W]
    return xp.bitwise_or.reduce(hit, axis=1)                      # [B,W]


def _split_topic(packed, p: int, s: int) -> tuple:
    """A topic launch's one batch operand ``[b, p+s+1]`` as the kernel's
    three: the first ``p`` words, the last ``s`` words right-aligned, the
    word count. Basic slices: views into a numpy array (the tokenizer fills
    it through them, the numpy twin reads them), static slices under jit."""
    return packed[:, :p], packed[:, p:p + s], packed[:, p + s]


def _tokenize_topic(wild: dict, keys: list, b: int) -> np.ndarray:
    p, s, vocab = wild["p"], wild["s"], wild["vocab"]
    packed = np.full((b, p + s + 1), MISS, dtype=np.int32)
    pre_m, suf_m, mlen = _split_topic(packed, p, s)
    mlen[:] = 0
    get = vocab.get
    for i, key in enumerate(keys):
        words = key.split(".") if key else [""]
        m = len(words)
        mlen[i] = m
        for j in range(min(m, p)):
            pre_m[i, j] = get(words[j], MISS)
        for j in range(min(m, s)):
            suf_m[i, s - 1 - j] = get(words[m - 1 - j], MISS)
    return packed


# -- headers ---------------------------------------------------------------


def _compile_headers(ce, bindings, max_wildcards: int, max_queues: int) -> None:
    always: set = set()
    rows = []  # (required {h: v}, is_all, queue)
    for _, queue, args in bindings:
        args = dict(args or {})
        is_all = str(args.pop("x-match", "all")).lower() != "any"
        if not args:
            if is_all:
                always.add(queue)  # empty all-binding matches everything
            continue  # empty any-binding can never match: no row
        for h, v in args.items():
            try:
                hash(v)
            except TypeError:
                raise Uncompilable("unhashable headers binding value")
        if len(args) > MAX_PATTERN_WORDS:
            raise Uncompilable("headers binding too wide")
        rows.append((args, is_all, queue))
    ce.always = frozenset(always)
    if not rows:
        return
    if len(rows) > max_wildcards:
        raise Uncompilable("headers binding count over cap")
    bit_names = tuple(sorted({q for _, _, q in rows}))
    if len(bit_names) > max_queues:
        raise Uncompilable("kernel queue count over cap")
    bit_of = {q: i for i, q in enumerate(bit_names)}
    vocab: dict[tuple, int] = {}  # (header, value) -> pair id
    n = _bucket(len(rows))
    r = _bucket(max(len(a) for a, _, _ in rows), 2)
    mask_words = (len(bit_names) + 31) >> 5
    req = np.full((n, r), PAD, dtype=np.int32)
    rcount = np.zeros(n, dtype=np.int32)
    is_all_v = np.zeros(n, dtype=bool)
    masks = np.zeros((n, mask_words), dtype=np.uint32)
    for i, (args, is_all, queue) in enumerate(rows):
        pids = [vocab.setdefault((h, v), len(vocab)) for h, v in args.items()]
        req[i, :len(pids)] = pids
        rcount[i] = len(pids)
        is_all_v[i] = is_all
        b = bit_of[queue]
        masks[i, b >> 5] |= np.uint32(1 << (b & 31))
    ce.bit_names = bit_names
    ce.headers = {"n": len(rows), "vocab": vocab, "r": r, "req": req,
                  "rcount": rcount, "is_all": is_all_v, "masks": masks,
                  "mask_words": mask_words}


def _headers_kernel(xp, req, rcount, is_all, masks, pids):
    # req [N,R] vs message pair ids pids [B,H]
    eq = req[None, :, :, None] == pids[:, None, None, :]           # [B,N,R,H]
    hitp = eq.any(axis=3) & (req[None, :, :] != PAD)               # [B,N,R]
    cnt = hitp.sum(axis=2, dtype=xp.int32)
    ok = xp.where(is_all[None, :], cnt == rcount[None, :], cnt > 0)
    hit = masks[None, :, :] * ok[:, :, None].astype(xp.uint32)
    return xp.bitwise_or.reduce(hit, axis=1)


def _tokenize_headers(table: dict, headers_list: list, b: int):
    vocab = table["vocab"]
    get = vocab.get
    per_msg = []
    hmax = 1
    for headers in headers_list:
        pids = []
        if headers:
            for h, v in headers.items():
                try:
                    pid = get((h, v))
                except TypeError:
                    continue  # unhashable message value never equals a
                    # (hashable) compiled binding value
                if pid is not None:
                    pids.append(pid)
        per_msg.append(pids)
        if len(pids) > hmax:
            hmax = len(pids)
    h = _bucket(hmax, 2)
    out = np.full((b, h), MISS, dtype=np.int32)
    for i, pids in enumerate(per_msg):
        out[i, :len(pids)] = pids
    return out


# -- batch evaluation ------------------------------------------------------

_JIT = None  # (topic_match, headers_match, put): built by the first launch


def _jit_kernels() -> tuple:
    """The two jitted kernels, and ``put``: host arrays onto the device
    this process claimed."""
    global _JIT
    if _JIT is None:
        import jax
        import jax.numpy as jnp

        # functions with names, not lambdas: a call's host event reads
        # PjitFunction(topic_match), its module jit_topic_match, and the
        # ops carry the scope, so a trace tells the two kernels apart
        def topic_match(pre_t, suf_t, plen, slen, has_h, masks, packed):
            with jax.named_scope("router.topic_match"):
                return _topic_kernel(
                    jnp, pre_t, suf_t, plen, slen, has_h, masks,
                    *_split_topic(packed, pre_t.shape[1], suf_t.shape[1]))

        def headers_match(*args):
            with jax.named_scope("router.headers_match"):
                return _headers_kernel(jnp, *args)

        _JIT = (jax.jit(topic_match), jax.jit(headers_match),
                functools.partial(jax.device_put, device=jax.devices()[0]))
    return _JIT


def _launch(compiled: CompiledExchange, batch: np.ndarray, keys: int,
            t_tok: int, metrics) -> tuple:
    """One jitted kernel call and the wait for its rows, on the calling
    thread (the event loop's), stamped once at each boundary: ``t_tok`` is
    when the tokenizer started, ``keys`` how many rows carry a real key.
    The call is handed ``batch``, one host array, and nothing else: the
    snapshot's tables are on the device from its first launch on, whose
    dispatch holds their upload. Returns ``(rows, t_rows)``, ``t_rows``
    being when the rows were on the host, from where the caller times its
    decode. The jitted call and ``np.asarray`` write JAX's own events into
    a profiler trace, so no span is opened here."""
    topic, headers, put = _jit_kernels()
    kern = topic if compiled.kind == "topic" else headers
    t_call = time.perf_counter_ns()
    tables = compiled._resident
    uploaded: tuple = ()
    if tables is None:
        uploaded = compiled.kernel_tables()
        tables = compiled._resident = put(uploaded)
    result = kern(*tables, batch)
    t_back = time.perf_counter_ns()
    rows = np.asarray(result)
    t_rows = time.perf_counter_ns()
    if metrics is not None:
        metrics.router_kernel_launches += 1
        metrics.router_tokenize_ns += t_call - t_tok
        metrics.router_dispatch_ns += t_back - t_call
        metrics.router_wait_ns += t_rows - t_back
        metrics.router_kernel_keys += keys
        metrics.router_kernel_rows += rows.shape[0]
        metrics.router_h2d_bytes += batch.nbytes
        if uploaded:
            metrics.router_table_uploads += 1
            metrics.router_h2d_bytes += sum(a.nbytes for a in uploaded)
    return rows, t_rows


def route_batch(
    compiled: CompiledExchange,
    items: list,
    backend: str = "jax",
    metrics: Any = None,
) -> list:
    """Route a batch through a compiled snapshot.

    ``items`` is a list of ``(routing_key, headers-or-None)``; the return
    is an aligned list of frozensets of queue names. backend="jax" runs the
    match kernels under jit; backend="python" runs the identical kernel
    body on numpy (no jax import at all). ``metrics`` (the broker's
    registry) counts each jitted kernel call in ``router_kernel_launches``
    — the one series that tells a flush that reached the device from one
    the key memo, a host dict or the numpy twin served — and, with it,
    what that launch cost the calling thread (``_launch``, which also puts
    the snapshot's tables on the device the first time). The stretches
    either side of the call carry ``device.span`` names, flat: lookup,
    tokenize, (JAX's own two events), decode."""
    kind = compiled.kind
    if kind == "fanout":
        always = compiled.always
        return [always] * len(items)
    if kind == "direct":
        exact = compiled.exact
        return [exact.get(k, _EMPTY) for k, _ in items]

    if kind == "topic":
        # a topic result is a pure function of the routing key, so the
        # memo is keyed on the key alone: steady-state routing (bounded
        # key cardinality, the common AMQP shape) is one dict hit per
        # message and only never-seen keys pay tokenize + kernel
        with device.span("router.lookup"):
            wild = compiled.wild
            memo = compiled._route_memo
            out = [None] * len(items)
            miss: dict = {}  # unique unseen keys -> their positions
            for i, (key, _) in enumerate(items):
                names = memo.get(key)
                if names is None:
                    miss.setdefault(key, []).append(i)
                else:
                    out[i] = names
            if not miss:
                return out
            if len(memo) + len(miss) >= _MEMO_CAP:
                memo.clear()
            if wild is None:
                for key, idxs in miss.items():
                    names = compiled.exact.get(key, _EMPTY) | compiled.always
                    memo[key] = names
                    for i in idxs:
                        out[i] = names
                return out
            uniq = list(miss)
            b = _bucket(len(uniq), 16)
        t_tok = time.perf_counter_ns()
        with device.span("router.tokenize"):
            packed = _tokenize_topic(wild, uniq, b)
        t_rows = 0
        if backend == "jax":
            rows, t_rows = _launch(compiled, packed, len(uniq), t_tok, metrics)
        else:
            rows = _topic_kernel(
                np, *compiled.kernel_tables(),
                *_split_topic(packed, wild["p"], wild["s"]))
        with device.span("router.decode"):
            masked, decoded = compiled._decode_rows(rows, len(uniq))
            exact = compiled.exact.get
            for (key, idxs), names in zip(miss.items(), masked):
                hit = exact(key)
                if hit is not None:
                    names = hit | names if names else hit
                memo[key] = names
                for i in idxs:
                    out[i] = names
        if t_rows and metrics is not None:
            metrics.router_decode_ns += time.perf_counter_ns() - t_rows
            metrics.router_mask_decodes += decoded
        return out

    if kind == "headers":
        table = compiled.headers
        if table is None:
            return [compiled.always] * len(items)
        b = _bucket(len(items), 16)
        t_tok = time.perf_counter_ns()
        with device.span("router.tokenize"):
            pids = _tokenize_headers(table, [h for _, h in items], b)
        t_rows = 0
        if backend == "jax":
            rows, t_rows = _launch(compiled, pids, len(items), t_tok, metrics)
        else:
            rows = _headers_kernel(np, *compiled.kernel_tables(), pids)
        with device.span("router.decode"):
            out, decoded = compiled._decode_rows(rows, len(items))
        if t_rows and metrics is not None:
            metrics.router_decode_ns += time.perf_counter_ns() - t_rows
            metrics.router_mask_decodes += decoded
        return out

    raise Uncompilable(f"unknown exchange type {kind!r}")
