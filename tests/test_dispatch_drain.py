"""The dispatch drain is held to a pass and a flush a queue.

`Broker.drain_dispatch` runs the passes of every queue that became ready in
a loop tick from one callback and renders each connection's deliveries once
when the last pass has run. Before it, every queue had a callback of its own
and every pass ended with a flush of the connections it had written to. Here
the same seeded state is built twice, on `test_dispatch_run`'s `World`, and
dispatched (a) by the drain and (b) pass by pass with a flush after each,
which the test alone emulates by standing in for the broker's drain: the
product has no switch. Both worlds must write the same bytes to every
connection and end in the same state.
"""

import asyncio

import pytest

from chanamq_tpu.broker.channel import Consumer, DispatchDrain
from chanamq_tpu.broker.connection import WRITE_HIGH_WATERMARK

from test_dispatch_run import PerMessageConsumer, World


def flush_after_each_pass(broker):
    """Stand in for the broker's drain with what ran before it: every
    scheduled queue's pass in the order scheduled, each followed by a flush
    of the connections that hold deliveries."""

    def drain():
        ready, broker.dispatch_ready = broker.dispatch_ready, []
        for queue in ready:
            one = DispatchDrain(broker)
            queue._dispatch(one)
            one.close()
            for conn in list(broker.egress_dirty):
                conn.flush_egress()
            broker.egress_dirty.clear()

    broker.drain_dispatch = drain


class HookConsumer(Consumer):
    """A consumer whose deliveries first call `hook(queue, qm)`: what a
    listener, a confirm or a fault does in the middle of a pass."""

    __slots__ = ("hook",)

    def deliver(self, queue, qm):
        self.hook(queue, qm)
        return super().deliver(queue, qm)


def hooked(w, queue, channel, hook, no_ack=True):
    cls, w.consumer_cls = w.consumer_cls, HookConsumer
    try:
        consumer = w.consume(queue, channel, no_ack=no_ack)
    finally:
        w.consumer_cls = cls
    consumer.hook = hook
    return consumer


# -- the cases: each builds a state in a World and returns the rounds that
# follow the first settle (callables run one after another, a settle after
# each) -----------------------------------------------------------------------


def mixed_world(w):
    """Many queues over three connections and several channels: plain
    no_ack (head runs), acked under a prefetch, priority and two-consumer
    queues, published to in random subsets; then acks, requeues and more
    publishes, so that passes come from every door."""
    rng = w.rng
    conns = [w.conn(), w.conn(frame_max=131072), w.conn(frame_max=8192)]
    acked_channels = []
    queues = []
    for i in range(rng.randrange(24, 40)):
        kind = rng.choice(("plain", "plain", "plain", "acked", "priority",
                           "two"))
        kw = {"arguments": {"x-max-priority": 4}} if kind == "priority" else {}
        queue = w.queue(f"{kind}{i}", **kw)
        queues.append(queue)
        conn = rng.choice(conns)
        ch = w.channel(conn, 1 + rng.randrange(4))
        if kind == "acked":
            ch = w.channel(conn, 9)
            ch.prefetch_count_consumer = 3
            acked_channels.append(ch)
            w.consume(queue, ch, no_ack=False)
        elif kind == "two":
            w.consume(queue, ch)
            w.consume(queue, w.channel(rng.choice(conns), 5 + rng.randrange(3)))
        else:
            w.consume(queue, ch)

    def publish_round():
        for _ in range(rng.randrange(60, 120)):
            props = ({"priority": rng.randrange(6)}
                     if rng.random() < 0.3 else {})
            w.publish(rng.sample(queues, rng.randrange(1, 6)), **props)

    def settle_some():
        for ch in acked_channels:
            for tag in sorted(ch.unacked):
                delivery = ch.unacked[tag]
                if rng.random() < 0.7:
                    ch.ack(delivery)
                elif rng.random() < 0.5:
                    ch.requeue(delivery)

    publish_round()
    return [settle_some, publish_round, settle_some, settle_some]


def frame_in_the_middle(w):
    """Three queues on one connection; the second queue's pass sends a frame
    of its own (as a confirm or Connection.Unblocked does) before its first
    delivery: it must follow the first queue's deliveries on the wire."""
    conn = w.conn()
    queues = [w.queue(f"m{i}") for i in range(3)]
    sent = []

    def frame(queue, qm):
        if not sent:
            sent.append(qm.offset)
            conn.send_bytes(b"<confirm>")

    w.consume(queues[0], w.channel(conn, 1))
    hooked(w, queues[1], w.channel(conn, 2), frame)
    w.consume(queues[2], w.channel(conn, 3))
    for i, queue in enumerate(queues):
        for _ in range(6):
            w.publish([queue], body=b"%d" % i * 50)
    return []


def saturated_connection(w):
    """Eight queues of 100 kB messages onto one connection: the write
    watermark (4 MiB, buffered records counted) stops the passes."""
    conn = w.conn(frame_max=131072)
    for i in range(8):
        queue = w.queue(f"big{i}")
        no_ack = i % 2 == 0
        w.consume(queue, w.channel(conn, 1 + i), no_ack=no_ack)
        for _ in range(10):
            w.publish([queue], body=b"b" * 100_000)
    return []


def several_buffers_a_tick(w):
    """Sixty queues, acked and plain in turn, of 1 kB messages onto two
    connections: a tick buffers more than two pooled buffers a connection."""
    conns = [w.conn(), w.conn(frame_max=131072)]
    for i in range(60):
        queue = w.queue(f"k{i}")
        w.consume(queue, w.channel(conns[i % 2], 1 + i % 5), no_ack=i % 3 > 0)
        for _ in range(20):
            w.publish([queue], body=bytes([65 + i % 26]) * 1000)
    return []


CASES = {build.__name__: build for build in (
    mixed_world, frame_in_the_middle, saturated_connection,
    several_buffers_a_tick)}


async def _dispatched(case, seed, emulate_parent, consumer_cls=Consumer):
    w = World(consumer_cls, seed)
    if w.broker.egress_encoder is None:
        pytest.skip("native egress encoder not built")
    if emulate_parent:
        flush_after_each_pass(w.broker)
    rounds = CASES[case](w)
    await w.settle()
    for step in rounds:
        step()
        await w.settle()
    return w


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 2147483659])
@pytest.mark.parametrize("case", sorted(CASES))
async def test_the_drain_writes_what_a_flush_a_pass_wrote(case, seed):
    drain = await _dispatched(case, seed, False)
    parent = await _dispatched(case, seed, True)
    got, want = drain.state(), parent.state()
    for key in want:
        assert got[key] == want[key], key
    assert want["delivered"][0] > 0 and want["encoder_fallbacks"] == 0
    m, p = drain.broker.metrics, parent.broker.metrics
    assert 0 < m.dispatch_drains <= m.dispatch_passes
    assert p.dispatch_drains == 0  # the stand-in counts none
    for conn in drain.conns:
        assert not conn._egress_pending and not conn._egress_bytes
    assert not drain.broker.egress_dirty and not drain.broker.dispatch_ready


async def test_the_per_message_world_drains_alike():
    """The same equality with the head run excluded (every delivery through
    egress_deliver), so that both ways of buffering are held to it."""
    for case in ("mixed_world", "several_buffers_a_tick"):
        drain = await _dispatched(case, 11, False, PerMessageConsumer)
        parent = await _dispatched(case, 11, True, PerMessageConsumer)
        assert drain.broker.metrics.dispatch_run_msgs == 0
        assert drain.state() == parent.state()


async def test_the_cases_show_what_they_say():
    w = await _dispatched("frame_in_the_middle", 5, False)
    wire = b"".join(bytes(part) for part in w.conns[0]._out)
    at = wire.index(b"<confirm>")
    # after all six deliveries of the first queue, before any of the others
    assert wire.count(b"0" * 50, 0, at) == 6
    assert wire.count(b"1" * 50, 0, at) == wire.count(b"2" * 50, 0, at) == 0
    assert wire.count(b"1" * 50, at) == wire.count(b"2" * 50, at) == 6
    m = w.broker.metrics
    assert m.dispatch_drains == 1 and m.dispatch_passes == 3
    # two renders where a flush a pass made three: the first queue's six
    # ahead of the frame, the other twelve when the drain ends
    assert (m.native_egress_batches, m.native_egress_msgs) == (2, 18)

    w = await _dispatched("saturated_connection", 5, False)
    conn = w.conns[0]
    assert conn.write_saturated
    assert conn._out_bytes >= WRITE_HIGH_WATERMARK
    # 4 MiB // (100,000 + framing) and the one that crosses it, whichever
    # queues they came from; the rest wait for the writer
    assert w.broker.metrics.delivered_msgs == 42
    assert sum(len(q.messages) for q in w.queues) == 80 - 42


@pytest.mark.parametrize("no_ack", [True, False], ids=["run", "per_message"])
async def test_a_batch_never_outgrows_a_pooled_buffer(no_ack, monkeypatch):
    """A tick that buffers several pooled buffers' worth on one connection
    renders into the pool in several batches: the pending batch is flushed
    before the record that would not fit, by the head run and by
    egress_deliver alike (acknowledging consumers that take no run)."""
    w = World(Consumer if no_ack else PerMessageConsumer, 3)
    enc = w.broker.egress_encoder
    if enc is None:
        pytest.skip("native egress encoder not built")
    batches = []
    encode_packed = enc.encode_packed

    def recording(parts, n, frame_max, nbytes):
        res = encode_packed(parts, n, frame_max, nbytes)
        batches.append((n, nbytes))
        return res

    monkeypatch.setattr(enc, "encode_packed", recording)
    conn = w.conn()
    for i in range(50):
        queue = w.queue(f"e{i}")
        w.consume(queue, w.channel(conn, 1 + i % 7), no_ack=no_ack)
        for _ in range(16):
            w.publish([queue], body=b"e" * 1000)
    await asyncio.sleep(0)  # one tick: the one drain
    assert not any(q._dispatch_scheduled for q in w.queues)
    assert not conn._egress_pending and not w.broker.egress_dirty
    m = w.broker.metrics
    assert m.dispatch_drains == 1 and m.dispatch_passes == 50
    assert m.delivered_msgs == m.native_egress_msgs == 800
    assert m.dispatch_run_msgs == (800 if no_ack else 0)
    total = sum(nbytes for _n, nbytes in batches)
    assert total == conn._out_bytes > 3 * enc.buf_bytes
    # early flushes: more than one batch, each within a pooled buffer and
    # each (but the last) filled to within one record of it
    assert len(batches) == -(-total // max(b[1] for b in batches)) >= 4
    assert all(nbytes <= enc.buf_bytes for _n, nbytes in batches)
    assert all(nbytes > enc.buf_bytes - 1200 for _n, nbytes in batches[:-1])
    # (whether a batch found a free slot is the pool's state, which the
    # process's earlier tests share: the writer task that returns slots
    # never runs in a World)
    assert m.native_egress_fallbacks == 0
    assert m.native_egress_batches == len(batches)
    for slot in conn._out_pooled:
        enc.release(slot)


async def test_a_queue_scheduled_inside_a_pass_runs_in_the_next_drain():
    w = World(Consumer, 1)
    conn = w.conn()
    first, second = w.queue("first"), w.queue("second")
    w.consume(second, w.channel(conn, 2))

    def feed_second(queue, qm):
        w.publish([second], body=b"from-the-pass")  # schedules `second`
        second.schedule_dispatch()  # and again: still one entry

    hooked(w, first, w.channel(conn, 1), feed_second)
    drains = []
    drain_dispatch = w.broker.drain_dispatch

    def recording():
        drains.append([q.name for q in w.broker.dispatch_ready])
        drain_dispatch()

    w.broker.drain_dispatch = recording
    for _ in range(3):
        w.publish([first], body=b"x")
    assert w.broker.dispatch_ready == [first] and not second._dispatch_scheduled
    await asyncio.sleep(0)
    # the first drain ran `first` alone; `second` waits for the next tick
    assert drains == [["first"]]
    assert first.n_delivered == 3 and second.n_delivered == 0
    assert second._dispatch_scheduled and w.broker.dispatch_ready == [second]
    await asyncio.sleep(0)
    assert drains == [["first"], ["second"]]
    assert second.n_delivered == 3 and not second._dispatch_scheduled
    await w.settle()
    assert drains == [["first"], ["second"]]
    assert w.broker.metrics.dispatch_drains == w.broker.metrics.dispatch_passes == 2


async def test_a_pass_that_raises_stops_no_other_pass():
    w = World(Consumer, 2)
    conn = w.conn()
    queues = [w.queue(f"r{i}") for i in range(5)]

    def fault(queue, qm):
        raise RuntimeError("planted in the pass of r2")

    for i, queue in enumerate(queues):
        ch = w.channel(conn, 1 + i)
        if i == 2:
            hooked(w, queue, ch, fault)
        else:
            w.consume(queue, ch, no_ack=i != 3)
    for queue in queues:
        for _ in range(4):
            w.publish([queue], body=queue.name.encode() * 10)
    reported = []
    loop = asyncio.get_event_loop()
    loop.set_exception_handler(lambda _loop, context: reported.append(context))
    try:
        await asyncio.sleep(0)
    finally:
        loop.set_exception_handler(None)
    assert len(reported) == 1
    assert isinstance(reported[0]["exception"], RuntimeError)
    assert "r2" in reported[0]["message"]
    assert [q.n_delivered for q in queues] == [4, 4, 0, 4, 4]
    assert not any(q._dispatch_scheduled for q in queues)
    assert not w.broker.dispatch_ready and not w.broker.egress_dirty
    # the drain's closing flush ran: every delivery made is rendered
    assert not conn._egress_pending
    wire = b"".join(bytes(part) for part in conn._out)
    for name in ("r0", "r1", "r3", "r4"):
        assert wire.count(name.encode() * 10) == 4
    m = w.broker.metrics
    assert m.dispatch_drains == 1 and m.dispatch_passes == 4
    # the queue whose pass raised is not stuck: its next message runs a pass
    queues[2].consumers[0].hook = lambda queue, qm: None
    w.publish([queues[2]], body=b"again")
    assert queues[2]._dispatch_scheduled
    await w.settle()
    assert queues[2].n_delivered == 4
