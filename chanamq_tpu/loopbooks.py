"""The event loop's own books: what its one thread did with the second,
counted from inside, always on.

``new_event_loop()`` builds the loop every broker process runs on
(``server.main()``; ``tests/conftest.py``): a plain
``asyncio.SelectorEventLoop`` over a ``TimedSelector``, a
``selectors.DefaultSelector`` whose ``select`` is the only thing
overridden. One call of ``select`` is one turn of the loop, so the
selector can tell, with two clock reads a turn and nothing per message,

- a loop that **waited** (``select`` entered with ``timeout != 0``:
  nothing was ready) from a loop that worked (``loop_idle_ns``,
  ``loop_idle_waits``, ``loop_turns``); a poll with ``timeout == 0`` is a
  turn's own cost and counts as busy;
- a **turn** that took too long: from one ``select``'s return to the
  next one's entry, every callback of the tick, with whatever kept the
  thread off the CPU meanwhile (``loop_slow_turns``,
  ``loop_slow_turn_ns`` over ``SLOW_TURN_NS``; ``loop_max_turn_ns``),
  and among those a **stall** (``loop_stalls``, ``loop_stall_ns`` over
  ``STALL_TURN_NS``): a busy deployment's ordinary turn, or one full
  pass of the collector, can pass the first; only a process that stood
  still passes the second. A loop stopped and run again books the pause
  between as one turn.

``busy_since_ns`` is the stall watchdog's input (profile/sampler.py): the
stamp of the last return, 0 while the loop waits inside ``select``.

The collector's pauses are the process's, not a loop's: ``watch_gc()``
installs the one ``gc.callbacks`` hook of the package (``Broker()`` calls
it) and ``GC`` keeps its counters. A pause falls inside whatever span
allocated, so it has counters and no span.

Under a ``jax.profiler`` session a wait is a ``loop.idle`` span on the
loop's line, so the trace reader's ``nothing traced`` holds only work no
span names. ``Metrics.snapshot()`` serves every counter here by name
(``COUNTERS``), 0 for a loop built otherwise.
"""

from __future__ import annotations

import asyncio
import gc
import selectors
import weakref
from time import perf_counter_ns
from typing import Optional

from . import device

# a turn over this is a slow turn: the default of
# chana.mq.profile.slow-callback-ms, a constant here
SLOW_TURN_NS = 100_000_000
# a turn over this is a stall. The longest turns of a sound loop are a
# flush of 1,000 publishes at a fan-out of 17.6 (~70 ms) with a full
# collection over deep queues inside it (113-211 ms); the stalls the
# benchmark's watch thread reports are 2-3.6 s (PERF.md 7.1)
STALL_TURN_NS = 500_000_000

LOOP_COUNTERS = (
    "loop_turns", "loop_idle_ns", "loop_idle_waits",
    "loop_slow_turns", "loop_slow_turn_ns",
    "loop_stalls", "loop_stall_ns", "loop_max_turn_ns",
)
GC_COUNTERS = (
    "gc_pause_ns", "gc_collections", "gc_full_collections",
    "gc_full_pause_ns", "gc_max_pause_ns",
)
COUNTERS = LOOP_COUNTERS + GC_COUNTERS
# the two high-water marks are gauges to a scraper, every other name a
# counter (rest/admin.py's list takes SUMS)
MAXIMA = ("loop_max_turn_ns", "gc_max_pause_ns")
SUMS = tuple(name for name in COUNTERS if name not in MAXIMA)


class TimedSelector(selectors.DefaultSelector):
    """The platform's selector, with ``select`` timed."""

    def __init__(self) -> None:
        super().__init__()
        self.loop_turns = 0
        self.loop_idle_ns = 0
        self.loop_idle_waits = 0
        self.loop_slow_turns = 0
        self.loop_slow_turn_ns = 0
        self.loop_stalls = 0
        self.loop_stall_ns = 0
        self.loop_max_turn_ns = 0
        # when the running turn began (the last return of select, or the
        # entry of a poll); 0 before the first turn and while waiting.
        # Read by the watchdog's thread: one int, GIL-atomic
        self.busy_since_ns = 0

    def select(self, timeout=None):
        entered = perf_counter_ns()
        self.loop_turns += 1
        since = self.busy_since_ns
        if since:
            turn = entered - since
            if turn > SLOW_TURN_NS:
                self.loop_slow_turns += 1
                self.loop_slow_turn_ns += turn
                if turn > STALL_TURN_NS:
                    self.loop_stalls += 1
                    self.loop_stall_ns += turn
            if turn > self.loop_max_turn_ns:
                self.loop_max_turn_ns = turn
        if timeout == 0:
            # callbacks are ready: the poll belongs to the next turn
            self.busy_since_ns = entered
            return super().select(0)
        self.busy_since_ns = 0
        with device.span("loop.idle"):
            ready = super().select(timeout)
        returned = perf_counter_ns()
        self.loop_idle_ns += returned - entered
        self.loop_idle_waits += 1
        self.busy_since_ns = returned
        return ready


# loop -> its selector, without reaching into the loop's private fields
_selectors: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def new_event_loop() -> asyncio.AbstractEventLoop:
    """The ``loop_factory`` of ``asyncio.run`` and of the tests' fixture."""
    selector = TimedSelector()
    loop = asyncio.SelectorEventLoop(selector)
    _selectors[loop] = selector
    return loop


def selector_of(
        loop: Optional[asyncio.AbstractEventLoop] = None,
) -> Optional[TimedSelector]:
    """The timed selector under ``loop`` (default: the running loop), or
    None for a loop this module did not build, or no loop."""
    if loop is None:
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return None
    return _selectors.get(loop)


class GcBooks:
    """The collector's pauses since ``watch_gc()``: every generation,
    generation 2 apart (a full collection walks every container the
    process holds: deep queues make it long), and the longest."""

    def __init__(self) -> None:
        self.gc_pause_ns = 0
        self.gc_collections = 0
        self.gc_full_collections = 0
        self.gc_full_pause_ns = 0
        self.gc_max_pause_ns = 0
        self._started_ns = 0

    def on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._started_ns = perf_counter_ns()
            return
        started = self._started_ns
        if not started:
            return  # hooked between a start and its stop
        self._started_ns = 0
        pause = perf_counter_ns() - started
        self.gc_pause_ns += pause
        self.gc_collections += 1
        if info["generation"] == 2:
            self.gc_full_collections += 1
            self.gc_full_pause_ns += pause
        if pause > self.gc_max_pause_ns:
            self.gc_max_pause_ns = pause


GC = GcBooks()


def watch_gc() -> None:
    """Install the hook, once a process."""
    if GC.on_gc not in gc.callbacks:
        gc.callbacks.append(GC.on_gc)


def snapshot() -> dict:
    """Every counter of ``COUNTERS`` by name: the running loop's (0 when
    the caller is on no loop, or on one built otherwise) and the
    collector's."""
    selector = selector_of()
    out = {name: getattr(selector, name, 0) for name in LOOP_COUNTERS}
    for name in GC_COUNTERS:
        out[name] = getattr(GC, name)
    return out
