"""The loop's own books (PR 39): the timed selector's waits and turns, the
collector's pauses, the writers' socket writes and the render, the
checkpoint's waits. Always-on counters of `Metrics`, served by name on
every surface, and two flat spans (`loop.idle`, `conn.egress_write`) in a
profiler's trace. conftest.py builds every test's loop with the broker's own
factory, so these run on the one path.
"""

import asyncio
import gc
import json
import os
import time

import pytest

from chanamq_tpu import loopbooks, native_ext, profile
from chanamq_tpu.broker.broker import Broker
from chanamq_tpu.broker.server import BrokerServer
from chanamq_tpu.client import AMQPClient
from chanamq_tpu.profile.runtime import ProfileRuntime
from chanamq_tpu.rest.admin import AdminServer
from chanamq_tpu.store.api import StoredMessage
from chanamq_tpu.store.sqlite import SqliteStore
from chanamq_tpu.utils.metrics import Metrics
from chanamq_tpu.wal import WalStore

from test_router_trace import (
    SPANS, _flat_spans_on_the_loops_line, _http, _traced)

pytestmark = pytest.mark.asyncio

EGRESS = ("egress_render_ns", "egress_write_ns", "egress_writev_calls",
          "egress_write_spills")
CHECKPOINT = ("wal_checkpoint_drain_ns", "wal_checkpoint_flush_ns",
              "wal_checkpoint_sync_ns", "wal_checkpoint_ns")
NEW = loopbooks.COUNTERS + EGRESS + CHECKPOINT


def _loop_counters() -> dict:
    return {k: v for k, v in loopbooks.snapshot().items()
            if k in loopbooks.LOOP_COUNTERS}


async def test_a_loop_built_by_the_factory_counts_its_turns_and_its_waits():
    books = loopbooks.selector_of()
    assert isinstance(books, loopbooks.TimedSelector)
    await asyncio.sleep(0)  # the turn the test's set-up ran in ends here
    before = _loop_counters()
    t0 = time.perf_counter_ns()
    await asyncio.sleep(0.2)
    asleep = time.perf_counter_ns() - t0
    after = _loop_counters()
    idle = after["loop_idle_ns"] - before["loop_idle_ns"]
    # asleep for 0.2 s, it books 0.2 s +- 20% of waiting
    assert 0.8 * 0.2e9 <= idle <= asleep
    assert idle >= 0.8 * asleep
    assert after["loop_idle_waits"] > before["loop_idle_waits"]
    assert after["loop_turns"] > before["loop_turns"]
    assert after["loop_slow_turns"] == before["loop_slow_turns"]
    assert after["loop_slow_turn_ns"] == before["loop_slow_turn_ns"]
    assert after["loop_stalls"] == before["loop_stalls"]
    # Metrics serves the selector's own integers
    served = Metrics().snapshot()
    for name in loopbooks.LOOP_COUNTERS:
        assert served[name] == getattr(books, name), name
    # a poll (callbacks are ready) is a turn and no wait
    before = _loop_counters()
    for _ in range(5):
        await asyncio.sleep(0)
    after = _loop_counters()
    assert after["loop_turns"] >= before["loop_turns"] + 5
    assert after["loop_idle_waits"] == before["loop_idle_waits"]
    assert after["loop_idle_ns"] == before["loop_idle_ns"]


def test_a_loop_built_otherwise_reads_zero_and_no_key_is_missing():
    async def read() -> dict:
        await asyncio.sleep(0.01)
        return Metrics().snapshot()

    plain = asyncio.new_event_loop()
    try:
        assert loopbooks.selector_of(plain) is None
        served = plain.run_until_complete(read())
    finally:
        plain.close()
    assert [served[name] for name in loopbooks.LOOP_COUNTERS] == [0] * 8
    # and on no loop at all
    assert loopbooks.selector_of() is None
    off_loop = Metrics().snapshot()
    assert all(off_loop[name] == 0 for name in loopbooks.LOOP_COUNTERS)
    assert set(NEW) <= set(off_loop)


async def test_a_callback_that_blocks_books_one_slow_turn():
    await asyncio.sleep(0)
    before = _loop_counters()
    time.sleep(0.15)  # one long callback: this task's step
    await asyncio.sleep(0.01)
    after = _loop_counters()
    assert after["loop_slow_turns"] == before["loop_slow_turns"] + 1
    took = after["loop_slow_turn_ns"] - before["loop_slow_turn_ns"]
    assert 150_000_000 <= took < 1_000_000_000
    assert after["loop_max_turn_ns"] >= took
    # a slow turn is no stall: a busy broker's ordinary turn can be one
    assert (after["loop_stalls"], after["loop_stall_ns"]) == \
        (before["loop_stalls"], before["loop_stall_ns"])
    # under the threshold (100 ms, a constant) a turn is not slow
    assert loopbooks.SLOW_TURN_NS == 100_000_000
    time.sleep(0.03)
    await asyncio.sleep(0.01)
    assert _loop_counters()["loop_slow_turns"] == after["loop_slow_turns"]


async def test_a_process_that_stood_still_books_a_stall():
    """Over STALL_TURN_NS (500 ms, a constant: above a sound loop's longest
    turn, under the stalls the benchmark's watch thread reports) a slow turn
    is also a stall: the counter `loop_slow_turn_share` reads."""
    assert loopbooks.SLOW_TURN_NS < loopbooks.STALL_TURN_NS == 500_000_000
    await asyncio.sleep(0)
    before = _loop_counters()
    time.sleep(0.55)
    await asyncio.sleep(0.01)
    after = _loop_counters()
    assert after["loop_stalls"] == before["loop_stalls"] + 1
    stood = after["loop_stall_ns"] - before["loop_stall_ns"]
    assert 550_000_000 <= stood < 2_000_000_000
    # a stall is a slow turn too, the same wall, and the longest so far
    assert after["loop_slow_turns"] == before["loop_slow_turns"] + 1
    assert after["loop_slow_turn_ns"] - before["loop_slow_turn_ns"] == stood
    assert after["loop_max_turn_ns"] >= stood


async def test_the_collector_is_counted_with_the_profile_off_and_read_by_it_on():
    assert profile.ACTIVE is None
    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    Broker(router_backend="python")  # a second broker: still one hook
    assert gc.callbacks.count(loopbooks.GC.on_gc) == 1
    books = loopbooks.GC
    before = {name: getattr(books, name) for name in loopbooks.GC_COUNTERS}
    gc.collect()
    assert books.gc_collections == before["gc_collections"] + 1
    assert books.gc_full_collections == before["gc_full_collections"] + 1
    assert books.gc_pause_ns > before["gc_pause_ns"]
    full = books.gc_full_pause_ns - before["gc_full_pause_ns"]
    assert full == books.gc_pause_ns - before["gc_pause_ns"]
    assert books.gc_max_pause_ns >= full > 0
    gc.collect(0)  # a young collection is a collection, not a full one
    assert books.gc_collections == before["gc_collections"] + 2
    assert books.gc_full_collections == before["gc_full_collections"] + 1
    # the profile on: its page reads the same integers, and hooks nothing
    await server.start()
    rt = ProfileRuntime(metrics=server.broker.metrics, slow_callback_ms=0,
                        broker=server.broker)
    server.broker.profile = rt
    rt.start()
    admin = AdminServer(server.broker, port=0)
    await admin.start()
    gc.disable()  # no collection between the two reads but this one
    try:
        gc.collect()
        _, body = await _http(admin.bound_port, "/admin/profile")
        page = json.loads(body)
        _, body = await _http(admin.bound_port, "/admin/overview")
        served = json.loads(body)["metrics"]
        assert page["gc"] == {
            "pauses": served["gc_collections"],
            "pause_ns": served["gc_pause_ns"],
            "max_pause_ns": served["gc_max_pause_ns"],
            "full_pauses": served["gc_full_collections"],
            "full_pause_ns": served["gc_full_pause_ns"]}
        assert served["gc_collections"] == books.gc_collections
        assert page["stages"]["gc"]["calls"] == served["gc_collections"]
        assert page["stages"]["gc"]["ns"] == served["gc_pause_ns"]
        assert gc.callbacks.count(books.on_gc) == 1
    finally:
        gc.enable()
        rt.stop()
        server.broker.profile = None
        await admin.stop()
        await server.stop()


async def test_a_delivery_advances_the_render_and_the_writers_counters():
    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    await server.start()
    metrics = server.broker.metrics
    try:
        c = await AMQPClient.connect("127.0.0.1", server.bound_port)
        ch = await c.channel()
        await ch.queue_declare("q")
        got = []
        await ch.basic_consume("q", got.append, no_ack=True)
        assert metrics.egress_render_ns == 0
        # the handshake's and the declares' replies left through writev
        wrote = metrics.egress_writev_calls
        assert wrote > 0 and metrics.egress_write_ns > 0
        for i in range(40):
            ch.basic_publish(b"x" * 32, routing_key="q")
        for _ in range(300):
            if len(got) == 40:
                break
            await asyncio.sleep(0.01)
        assert len(got) == 40
        assert metrics.egress_render_ns > 0
        assert metrics.egress_writev_calls > wrote
        assert metrics.egress_write_spills == 0
        await c.close()
    finally:
        await server.stop()


async def test_a_full_kernel_buffer_counts_a_spill(monkeypatch):
    """writev meets EAGAIN: the rest goes to the transport, which owns the
    socket's writability, and the spill is counted once a hand-over."""
    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    await server.start()
    metrics = server.broker.metrics
    try:
        c = await AMQPClient.connect("127.0.0.1", server.bound_port)
        ch = await c.channel()
        await ch.queue_declare("q")
        got = []
        await ch.basic_consume("q", got.append, no_ack=True)
        calls = metrics.egress_writev_calls

        def full(fd, bufs):
            raise BlockingIOError

        with monkeypatch.context() as patched:
            patched.setattr(os, "writev", full)
            ch.basic_publish(b"x" * 32, routing_key="q")
            for _ in range(300):
                if got:
                    break
                await asyncio.sleep(0.01)
        assert len(got) == 1  # the transport delivered what writev could not
        assert metrics.egress_write_spills >= 1
        assert metrics.egress_writev_calls > calls  # a refused call is a call
        await c.close()
    finally:
        await server.stop()


def _msg(i: int) -> StoredMessage:
    return StoredMessage(id=i, properties_raw=b"\x01", body=b"body%d" % i,
                         exchange="ex", routing_key="rk", refer_count=1)


async def _log_some(store: WalStore, start: int) -> None:
    lo = store.mark()
    for i in range(start, start + 50):
        store.insert_message_nowait(_msg(i))
        store.insert_queue_msg_nowait("/", "q", i + 1, i, 5, None)
    await store.flush([(lo, store.mark())])


async def test_a_checkpoint_books_its_waits_and_a_failed_one_what_it_reached(
        tmp_path):
    store = WalStore(SqliteStore(str(tmp_path / "store.db")), flush_ms=1.0,
                     checkpoint_ms=3_600_000.0)
    await store.open()
    m = store.metrics
    try:
        await _log_some(store, 0)
        assert [getattr(m, name) for name in CHECKPOINT] == [0] * 4
        await store._checkpoint_once()
        assert m.wal_checkpoints == 1
        parts = [m.wal_checkpoint_drain_ns, m.wal_checkpoint_flush_ns,
                 m.wal_checkpoint_sync_ns]
        assert all(part > 0 for part in parts), parts
        assert m.wal_checkpoint_ns == sum(parts)
        # the flush fails: the drain it reached is booked, nothing after
        await _log_some(store, 100)

        async def broken() -> None:
            raise OSError("disk gone")

        flush, store._inner.flush = store._inner.flush, broken
        try:
            with pytest.raises(OSError):
                await store._checkpoint_once()
        finally:
            store._inner.flush = flush
        assert m.wal_checkpoints == 1
        drained = m.wal_checkpoint_drain_ns - parts[0]
        assert drained > 0
        assert m.wal_checkpoint_flush_ns == parts[1]
        assert m.wal_checkpoint_sync_ns == parts[2]
        assert m.wal_checkpoint_ns == sum(parts) + drained
        # served under their names
        served = m.snapshot()
        for name in CHECKPOINT:
            assert served[name] == getattr(m, name)
    finally:
        await store.close()


async def test_every_new_name_is_on_every_surface_exactly_once():
    assert len(set(NEW)) == len(NEW) == 21
    # sums are counters to a scraper; the two high-water marks are gauges
    listed = AdminServer._PROM_COUNTERS
    assert set(NEW) - listed == set(loopbooks.MAXIMA)
    # the registry's own key list names each once
    snap_keys = list(Metrics().snapshot())
    for name in NEW:
        assert snap_keys.count(name) == 1, name
    broker = Broker(router_backend="python")
    admin = AdminServer(broker, port=0)
    await admin.start()
    try:
        broker.metrics.egress_write_ns = 4321
        status, body = await _http(admin.bound_port, "/admin/overview")
        assert status == 200
        served = json.loads(body)["metrics"]
        assert set(NEW) <= set(served)
        assert served["egress_write_ns"] == 4321
        # on the loop that serves it, the loop's counters are live
        assert served["loop_turns"] > 0
        status, body = await _http(admin.bound_port, "/metrics")
        lines = body.decode().splitlines()
        for name in NEW:
            kind = "gauge" if name in loopbooks.MAXIMA else "counter"
            assert lines.count(f"# TYPE chanamq_{name} {kind}") == 1, name
            samples = [ln for ln in lines
                       if ln.startswith(f"chanamq_{name} ")]
            assert len(samples) == 1, (name, samples)
        assert "chanamq_egress_write_ns 4321" in lines
        # the two names the profile's hook fed are aliases now
        assert any(ln.startswith("chanamq_profile_gc_pauses_total ")
                   for ln in lines)
    finally:
        await admin.stop()


LOOP_SPANS = ("loop.idle", "conn.egress_write")


@pytest.mark.skipif(not native_ext.pipeline_available(),
                    reason="the router batches only behind the native scan")
def test_a_profiler_trace_holds_the_loops_two_spans_flat(event_loop, tmp_path):
    """The benchmark's traced run rehearsed on the CPU: the waits and the
    writers' writes are on the loop's line under their two names, flat among
    the others and inside none of them."""

    async def drive(port: int) -> None:
        c = await AMQPClient.connect("127.0.0.1", port)
        ch = await c.channel()
        await ch.exchange_declare("ex", "topic")
        await ch.queue_declare("q1")
        await ch.queue_bind("q1", "ex", "a.*.c")
        got = []
        await ch.basic_consume("q1", got.append, no_ack=True)
        await ch.confirm_select()
        for burst in range(3):
            for i in range(64):
                ch.basic_publish(b"m", exchange="ex",
                                 routing_key=f"a.{burst}-{i}.c")
            await ch.wait_unconfirmed_below(1)
            await asyncio.sleep(0.02)  # the loop waits
        for _ in range(200):
            if len(got) == 192:
                break
            await asyncio.sleep(0.01)
        assert len(got) == 192
        await c.close()

    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    books = loopbooks.selector_of(event_loop)
    waits, turns = books.loop_idle_waits, books.loop_turns
    event_loop.run_until_complete(_traced(tmp_path, server, drive))
    metrics = server.broker.metrics
    assert metrics.router_kernel_launches >= 1
    on_loop, spans = _flat_spans_on_the_loops_line(
        str(tmp_path), SPANS + LOOP_SPANS)
    count = {name: sum(1 for e in spans if e[0] == name)
             for name in LOOP_SPANS}
    # a span a wait and a span a writer wake-up, never one a message
    assert 3 <= count["loop.idle"] <= books.loop_idle_waits - waits
    assert count["loop.idle"] < books.loop_turns - turns
    assert 1 <= count["conn.egress_write"] <= metrics.egress_writev_calls
    # inside no other event of the loop's line, JAX's own included
    ours = [e for e in spans if e[0] in LOOP_SPANS]
    for name, start, end in ours:
        assert not [e for e in on_loop if e[0] != name
                    and e[1] <= start and end <= e[2]], name
    # the waits the trace shows are waits the counter booked
    traced_idle = sum(e[2] - e[1] for e in ours if e[0] == "loop.idle")
    assert 0 < traced_idle <= books.loop_idle_ns
