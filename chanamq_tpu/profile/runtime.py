"""The per-message cost ledger and its aggregate view.

Stage indices are append-only (the Prometheus series and the admin
payload key off the names; reordering would silently re-label recorded
history on a scrape boundary). Two granularities coexist:

- **fine stages** mirror the trace seams (route, enqueue, wal-append,
  deliver, ...) and count *messages* in ``stage_calls``, so
  ``ns / calls`` reads directly as µs per message for that stage; they
  are wall windows (== CPU whenever the loop isn't preempted);
- **top-level stages** (``ingress-cycle``, ``dispatch``,
  ``cluster-push``) wrap whole event-loop work windows measured in
  **loop-thread CPU** (``time.thread_time_ns``), with any top-level
  window that ran inside an awaiting window subtracted back out
  (connection.py's ingress seam), so their sum never double-counts and
  is immune to CPU steal from sibling processes. The attribution claim
  is ``busy_ns / loop_cpu_ns`` — both visible in ``snapshot()``.

Fine stages nest inside top-level ones by design (route happens inside
an ingress cycle); only top-level stages are summed for attribution.
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Optional

import numpy as np

from .. import loopbooks

STAGES = (
    "ingress-parse",   # 0  native frame scan, per read-chunk pass
    "route",           # 1  binding resolution (cache, matcher, or kernel)
    "enqueue",         # 2  Message build + store insert + queue.push fanout
    "wal-append",      # 3  WAL frame encode + ingest (pre-commit)
    "wal-commit",      # 4  group-commit write+fsync window (wall, batched)
    "cluster-push",    # 5  origin-side push-batch encode + flush
    "deliver",         # 6  dispatch-pass delivery rendering loop
    "settle",          # 7  ack/reject store cleanup + unrefer
    "flow-throttle",   # 8  publish-gate park window (wall, per episode)
    "dispatch",        # 9  whole coalesced dispatch pass (top-level)
    "ingress-cycle",   # 10 whole read-chunk consume cycle (top-level)
    "gc",              # 11 collector pauses (loopbooks.GC, always on)
    "tx-commit",       # 12 Tx.Commit staged replay: scope open -> sealed
)
(INGRESS_PARSE, ROUTE, ENQUEUE, WAL_APPEND, WAL_COMMIT, CLUSTER_PUSH,
 DELIVER, SETTLE, FLOW_THROTTLE, DISPATCH, INGRESS_CYCLE, GC,
 TX_COMMIT) = range(13)

SUBSYSTEMS = (
    "broker", "router", "broker", "wal", "wal", "cluster",
    "broker", "broker", "flow", "broker", "broker", "runtime",
    "broker",
)

# stages whose windows tile the event loop without overlapping: their sum
# is the measured busy time the attribution ratio divides by process CPU
TOP_LEVEL = frozenset({INGRESS_CYCLE, DISPATCH, CLUSTER_PUSH})


class ProfileRuntime:
    """Fixed accumulators + the sampler and the stall watchdog around them.

    ``stage_ns`` / ``stage_calls`` are fixed int64 numpy vectors; seams
    add into them directly (``prof.stage_ns[profile.ROUTE] += dt``) so
    the enabled hot path is two array adds, no method call, no dict, no
    allocation. Everything else (snapshot math, subsystem rollup) runs
    on the admin path only. The ``gc`` stage has no seam: it reads the
    collector's always-on counters (``loopbooks.GC``) when it is asked.
    """

    def __init__(
        self,
        node: str = "local",
        metrics=None,
        *,
        sample_hz: int = 0,
        slow_callback_ms: int = 100,
        ring_size: int = 64,
        broker=None,
    ) -> None:
        self.node = node
        self.metrics = metrics
        self.broker = broker
        self.sample_hz = max(0, int(sample_hz))
        self.slow_callback_ms = max(0, int(slow_callback_ms))
        self.ring_size = max(1, int(ring_size))
        self.stage_ns = np.zeros(len(STAGES), dtype=np.int64)
        self.stage_calls = np.zeros(len(STAGES), dtype=np.int64)
        # attribution denominators since enable: loop-thread CPU (the
        # busy ratio's), process CPU and wall (context). thread_time is
        # per-thread, so _tcpu0_ns is only meaningful against reads from
        # the same thread — start() re-stamps it on the loop thread and
        # snapshot() runs there too (the admin server shares the loop)
        self._tcpu0_ns = time.thread_time_ns()
        self._cpu0_ns = time.process_time_ns()
        self._wall0_ns = time.perf_counter_ns()
        # the watchdog's input: the timed selector under the loop, whose
        # busy_since_ns says how long the running turn has lasted (None:
        # no loop, or one loopbooks did not build: no watchdog)
        self.loop_books: Optional[loopbooks.TimedSelector] = None
        self.loop_thread_id = threading.get_ident()
        self.sampler = None
        self._started = False

    # -- lifecycle ----------------------------------------------------------

    def start(self, loop: Optional[asyncio.AbstractEventLoop] = None) -> None:
        """Arm the off-ledger parts. Callable without a running loop (unit
        tests drive the ledger alone); the watchdog only watches a loop
        built over the timed selector (loopbooks.new_event_loop)."""
        if self._started:
            return
        self._started = True
        self.loop_thread_id = threading.get_ident()
        self._tcpu0_ns = time.thread_time_ns()
        if self.slow_callback_ms > 0:
            self.loop_books = loopbooks.selector_of(loop)
        if self.sample_hz > 0 or self.loop_books is not None:
            from .sampler import Sampler

            self.sampler = Sampler(self)
            self.sampler.start()

    def stop(self) -> None:
        if not self._started:
            return
        self._started = False
        self.loop_books = None
        if self.sampler is not None:
            self.sampler.shutdown()
            self.sampler = None

    # -- cold-path helper (tests, non-seam callers) --------------------------

    def note(self, stage: int, dt_ns: int, calls: int = 1) -> None:
        self.stage_ns[stage] += dt_ns
        self.stage_calls[stage] += calls

    # -- aggregate view ------------------------------------------------------

    def stage_totals(self) -> "tuple[np.ndarray, np.ndarray]":
        """``(stage_ns, stage_calls)`` with the ``gc`` stage brought up to
        the collector's counters: what every reader of the ledger takes."""
        books = loopbooks.GC
        self.stage_ns[GC] = books.gc_pause_ns
        self.stage_calls[GC] = books.gc_collections
        return self.stage_ns, self.stage_calls

    def _router_block(self) -> dict:
        """The route stage seen from inside: the integers /admin/overview
        serves, and what one device launch costs the loop by them."""
        m = self.metrics
        launches = m.router_kernel_launches
        block = {"kernel_launches": launches, **m.router_launch()}
        if launches and m.router_kernel_rows:
            us = 1e-3 / launches
            block["per_launch"] = {
                "tokenize_us": round(m.router_tokenize_ns * us, 1),
                "dispatch_us": round(m.router_dispatch_ns * us, 1),
                "wait_us": round(m.router_wait_ns * us, 1),
                "decode_us": round(m.router_decode_ns * us, 1),
                "keys": round(m.router_kernel_keys / launches, 1),
                "useful_row_pct": round(
                    100.0 * m.router_kernel_keys / m.router_kernel_rows, 1),
                "h2d_bytes": round(m.router_h2d_bytes / launches),
                "table_resident_pct": round(
                    100.0 * (1 - m.router_table_uploads / launches), 2),
                "mask_memo_hit_pct": round(
                    100.0 * (1 - m.router_mask_decodes / m.router_kernel_keys),
                    1),
            }
        if m.router_closure_flattens:
            compiles = m.router_closure_compiles
            block["closure"] = {
                "compiles": compiles,
                "flattens": m.router_closure_flattens,
                "ms_per_compile": round(
                    m.router_closure_flatten_ns * 1e-6 / compiles, 3)
                if compiles else None,
            }
        return block

    def snapshot(self) -> dict:
        """The /admin/profile payload: per-stage and per-subsystem µs plus
        the attribution ratio. Pure reads — safe on the admin path."""
        ns, calls = self.stage_totals()
        loop_cpu_ns = time.thread_time_ns() - self._tcpu0_ns
        cpu_ns = time.process_time_ns() - self._cpu0_ns
        wall_ns = time.perf_counter_ns() - self._wall0_ns
        stages = {}
        subsystems: dict = {}
        busy_ns = 0
        for i, name in enumerate(STAGES):
            n, c = int(ns[i]), int(calls[i])
            top = i in TOP_LEVEL
            stages[name] = {
                "subsystem": SUBSYSTEMS[i],
                "ns": n,
                "calls": c,
                "us_per_call": round(n / c / 1000.0, 3) if c else None,
                "top_level": top,
            }
            if top:
                busy_ns += n
            if not top and i != GC:
                # subsystem rollup from the fine stages only (the
                # top-level windows contain them; summing both would
                # double-count the same microseconds)
                sub = subsystems.setdefault(
                    SUBSYSTEMS[i], {"ns": 0, "calls": 0})
                sub["ns"] += n
                sub["calls"] += c
        out = {
            # follow the cluster's rename of the node tag (trace does the
            # same): "local" until ClusterNode.start names this node
            "node": (self.broker.trace_node
                     if self.broker is not None else self.node),
            "stages": stages,
            "subsystems": subsystems,
            "busy_ns": busy_ns,
            "loop_cpu_ns": loop_cpu_ns,
            "process_cpu_ns": cpu_ns,
            "wall_ns": wall_ns,
            "attributed_pct": (
                round(busy_ns / loop_cpu_ns * 100.0, 1)
                if loop_cpu_ns > 0 else None),
            "gc": {
                "pauses": loopbooks.GC.gc_collections,
                "pause_ns": loopbooks.GC.gc_pause_ns,
                "max_pause_ns": loopbooks.GC.gc_max_pause_ns,
                "full_pauses": loopbooks.GC.gc_full_collections,
                "full_pause_ns": loopbooks.GC.gc_full_pause_ns,
            },
        }
        if self.metrics is not None:
            out["router"] = self._router_block()
        sampler = self.sampler
        if sampler is not None:
            out["sampler"] = {
                "hz": self.sample_hz,
                "samples": sampler.samples,
                "distinct_stacks": len(sampler.stacks),
            }
            out["slow_callbacks"] = {
                "threshold_ms": self.slow_callback_ms,
                "count": sampler.slow_count,
                "recent": list(sampler.ring),
            }
        else:
            out["sampler"] = {"hz": self.sample_hz, "samples": 0,
                              "distinct_stacks": 0}
            out["slow_callbacks"] = {
                "threshold_ms": self.slow_callback_ms,
                "count": 0, "recent": []}
        return out

    def stage_detail(self, name: str) -> Optional[dict]:
        if name not in STAGES:
            return None
        i = STAGES.index(name)
        ns, calls = self.stage_totals()
        c = int(calls[i])
        n = int(ns[i])
        return {
            "stage": name,
            "subsystem": SUBSYSTEMS[i],
            "ns": n,
            "calls": c,
            "us_per_call": round(n / c / 1000.0, 3) if c else None,
            "top_level": i in TOP_LEVEL,
        }

    def collapsed(self) -> str:
        """Folded stacks in flamegraph collapsed format (one ``stack
        count`` line each), hottest first."""
        sampler = self.sampler
        if sampler is None:
            return ""
        return sampler.collapsed()
