"""Continuous performance observability (``chana.mq.profile.*``).

Two coupled parts and the page over them:

- a **per-message cost ledger**: the hot-path seams that already carry
  trace spans (ingress-parse / route / enqueue / wal-append / wal-commit /
  cluster-push / deliver / settle, PR 5) accumulate aggregate per-stage
  CPU-ns and invocation counts into fixed numpy accumulators. There is no
  sampling decision on the hot path: every seam is gated on the same
  module-level ``ACTIVE is None`` check chaos and trace use, and the
  per-message stages accumulate at batch granularity wherever a batch
  exists (router flush, dispatch pass, scan pass), so the enabled cost is
  paid per batch, not per message (what it comes to on the chip's host:
  ROADMAP.md D7).
- a **sampling wall profiler + stall attribution**: an off-loop thread
  samples ``sys._current_frames()`` into folded-stack counts (flamegraph
  collapsed format at ``GET /admin/profile/stacks``) and doubles as the
  event-loop watchdog that captures the stack and duration of any
  callback stalling the loop past ``chana.mq.profile.slow-callback-ms``.
  It reads the stamp the loop's timed selector keeps
  (``loopbooks.TimedSelector.busy_since_ns``); no heartbeat task runs
  on the loop, and a loop built otherwise has no watchdog.
- the aggregate view at ``GET /admin/profile``: µs/msg by stage and by
  subsystem plus the fraction of process CPU the ledger attributes, and a
  ``router`` block: the launch counters ``Metrics`` keeps whether the
  ledger is on or not (``chanamq_router_{tokenize,dispatch,wait,decode,
  route}_ns``, ``_kernel_{keys,rows}``, ``_h2d_bytes``, ``_table_uploads``,
  ``_mask_decodes``;
  stamped in router/compile.py ``_launch`` and ``route_batch``) and, under
  ``per_launch``, the ``route`` stage split per device launch by them.
  The ledger's ``route`` window and ``router_route_ns`` are one pair of
  stamps.

The collector's hook and the loop's waits and turns are NOT the
profile's: ``chanamq_tpu/loopbooks.py`` counts them always
(``Metrics``: ``gc_pause_ns`` answers "is the loop collecting",
``loop_idle_ns`` "is it waiting", ``loop_slow_turns`` / ``loop_stalls`` /
``loop_max_turn_ns`` "is it starved or stuck in one callback"), and the ledger's ``gc`` stage,
this page's ``gc`` block and ``chanamq_profile_gc_*`` read those counters.

The hierarchy of the loop's work lives here (``ingress-cycle`` ⊃ ``route``
⊃ …). The flat names a ``jax.profiler`` trace shows on the loop's thread
(``device.span``: ``conn.ingress``, ``router.lookup``, …) are a different
outlet of the same seams, on the device trace's clock.

Like ``trace`` and ``chaos``: disabled (the default) costs one module
attribute load + ``is None`` per seam.
"""

from __future__ import annotations

from typing import Optional

from .runtime import (  # noqa: F401 — re-exported page for the seams
    CLUSTER_PUSH, DELIVER, DISPATCH, ENQUEUE, FLOW_THROTTLE, GC,
    INGRESS_CYCLE, INGRESS_PARSE, ROUTE, SETTLE, STAGES, SUBSYSTEMS,
    TOP_LEVEL, TX_COMMIT, WAL_APPEND, WAL_COMMIT, ProfileRuntime,
)

# The gate. Hot-path seams do `prof = profile.ACTIVE` then
# `if prof is not None:` — one module attribute load when disabled.
ACTIVE: Optional[ProfileRuntime] = None


def install(runtime: ProfileRuntime) -> ProfileRuntime:
    global ACTIVE
    ACTIVE = runtime
    return runtime


def clear() -> None:
    global ACTIVE
    if ACTIVE is not None:
        ACTIVE.stop()
    ACTIVE = None


def enable_from_config(config, broker) -> ProfileRuntime:
    """Boot-time wiring (``chana.mq.profile.enabled``): build the runtime
    from the knobs, hang it off the broker for the admin surface, install
    the gate, and start the sampler and the watchdog."""
    runtime = ProfileRuntime(
        metrics=broker.metrics,
        sample_hz=config.int("chana.mq.profile.sample-hz"),
        slow_callback_ms=config.int("chana.mq.profile.slow-callback-ms"),
        ring_size=config.int("chana.mq.profile.ring-size"),
        broker=broker,
    )
    broker.profile = runtime
    install(runtime)
    runtime.start()
    return runtime
