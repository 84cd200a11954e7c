"""An ack run is held to the per-frame path.

`AMQPConnection._consume_scan` settles a basic.ack frame and the acks of
the same channel that follow it in the read batch as one run
(`AMQPConnection._ack_run`); whatever the run does not take goes through
`_fused_ack`, frame by frame, at its place in the batch. Here the same
seeded state is built twice, with deliveries outstanding: in one world the
ack frames are fed as one batch of the native scanner through
`_consume_scan`, in the other each frame goes by itself through
`_fused_ack` (or, where that declines it, the generic command path), as
`_consume_scan` handled it before the run. Both worlds must end in the same
state: the unacked and outstanding maps, the prefetch counts, the gauges,
the refcounts and resident bytes, the ack counters, the order of the queues
the acks scheduled, the flow stage and where it moved, the channel errors
and the bytes written, the passes the acks scheduled included.
"""

import asyncio
import random
import struct

import pytest

from chanamq_tpu import native_ext, trace
from chanamq_tpu.amqp.frame import Frame
from chanamq_tpu.amqp.properties import BasicProperties
from chanamq_tpu.broker.broker import Broker
from chanamq_tpu.broker.channel import ChannelMode, Consumer, ServerChannel
from chanamq_tpu.broker.connection import AMQPConnection, ChannelError
from chanamq_tpu.broker.entities import Queue
from chanamq_tpu.streams.queue import StreamQueue
from chanamq_tpu.trace.runtime import TraceRuntime


class _Writer:
    transport = None

    def write(self, data):  # pragma: no cover - the writer task never runs
        pass


def ack_frame(channel_id, tag, multiple=False):
    payload = struct.pack(">HHQB", 60, 80, tag, 1 if multiple else 0)
    return Frame(1, channel_id, payload).to_bytes()


class World:
    """One broker, connections, queues and acknowledging consumers built by
    hand (no sockets: what is written stays in the connection's output
    list), and the ack frames a case sends."""

    def __init__(self, seed, **broker_kw):
        self.rng = random.Random(seed)
        self.broker = Broker(router_enabled=False, **broker_kw)
        self.conns = []
        self.queues = []
        self.consumers = []
        self.messages = []
        self.frames = []  # (connection, frame bytes)
        self.stages = []  # (old, new, acked_msgs) at each flow transition
        self.ready = []  # the queues the acks scheduled, in order
        self.broker.flow_stage_listeners.add(
            lambda old, new: self.stages.append(
                (old, new, self.broker.metrics.acked_msgs)))

    def conn(self):
        conn = AMQPConnection(self.broker, None, _Writer(), frame_max=4096)
        conn._opened = True
        self.conns.append(conn)
        return conn

    def channel(self, conn, channel_id=1):
        if channel_id not in conn.channels:
            conn.channels[channel_id] = ServerChannel(conn, channel_id)
        return conn.channels[channel_id]

    def queue(self, name, cls=Queue, **kw):
        queue = cls(self.broker, "/", name, **kw)
        self.queues.append(queue)
        return queue

    def consume(self, queue, channel, arguments=None):
        consumer = Consumer(f"ctag-{queue.name}", channel, queue, False,
                            False, arguments)
        channel.consumers[consumer.tag] = consumer
        queue.add_consumer(consumer)
        self.consumers.append(consumer)
        return consumer

    def publish(self, queues, body=None, **props):
        rng = self.rng
        if body is None:
            body = bytes(rng.getrandbits(8) for _ in range(
                rng.choice((0, 1, 12, 12, 12, 200))))
        properties = BasicProperties(**props)
        message = self.broker.push_local(
            list(queues), properties, body, "ex", "rk.%d" % rng.randrange(9),
            properties.encode_header(len(body)), None, None)
        self.messages.append(message)
        return message

    def acks(self, channel, tags=None):
        """Ack frames for `tags` (default: every outstanding delivery of
        the channel, in a seeded order)."""
        if tags is None:
            tags = sorted(channel.unacked)
            self.rng.shuffle(tags)
        return [(channel.connection, ack_frame(channel.id, tag))
                for tag in tags]

    async def settle(self):
        for _ in range(400):
            await asyncio.sleep(0)
            if not any(q._dispatch_scheduled or getattr(q, "_hydrating", 0)
                       for q in self.queues):
                break
        for conn in self.conns:
            conn.flush_egress()

    async def feed(self, as_run):
        """Send the case's ack frames: each connection's as one read batch
        through `_consume_scan` (as_run), or frame by frame as
        `_consume_scan` handled an ack before the run."""
        by_conn = {}
        for conn, frame in self.frames:
            by_conn.setdefault(conn, []).append(frame)
        for conn, frames in by_conn.items():
            if as_run:
                parser = native_ext.NativeFrameParser(conn.frame_max)
                assert await conn._consume_scan(
                    parser.scan_batches(b"".join(frames)))
            else:
                for frame in frames:
                    assert await per_frame(conn, frame)
        self.ready = [q.name for q in self.broker.dispatch_ready]

    def state(self):
        m = self.broker.metrics
        flow = self.broker.flow
        return {
            "wire": [b"".join(bytes(part) for part in conn._out)
                     for conn in self.conns],
            "channels": [sorted(conn.channels) for conn in self.conns],
            "closing": [sorted(conn._closing_channels) for conn in self.conns],
            "unacked": [{cid: [(tag, d.consumer_tag, d.queued.offset)
                               for tag, d in ch.unacked.items()]
                         for cid, ch in conn.channels.items()}
                        for conn in self.conns],
            "tx": [{cid: ([(op[0], op[1].delivery_tag) for op in ch.tx_ops],
                          ch.tx_held_count, ch.tx_held_size)
                    for cid, ch in conn.channels.items()}
                   for conn in self.conns],
            "outstanding": [sorted(q.outstanding) for q in self.queues],
            "queues": [(q.name, q.n_delivered, q.n_acked, q.ready_bytes,
                        [qm.offset for qm in q.messages])
                       for q in self.queues],
            "prefetch_held": [(c.unacked_count, c.unacked_size)
                              for c in self.consumers],
            "queue_unacked": self.broker.queue_unacked,
            "queue_depth": self.broker.queue_depth,
            "acked": (m.acked_msgs, [c.acked_msgs for c in self.conns]),
            "resident": self.broker.resident_bytes,
            "refs": [(msg.refer_count, msg.accounted, msg.persisted,
                      msg.paged) for msg in self.messages],
            "delivered": (m.delivered_msgs, m.delivered_bytes),
            "ready": self.ready,
            "stages": self.stages,
            "ladder": (m.flow_escalations, m.flow_deescalations,
                       self.broker.blocked,
                       None if flow is None else flow.stage),
        }


async def per_frame(conn, raw):
    """One frame as `_consume_scan`'s loop body handled it before the ack
    run: the fused ack where the fast path is open, else the generic
    command path; a channel error closes the channel as the loop does."""
    cid = int.from_bytes(raw[1:3], "big")
    payload = raw[7:-1]
    if (conn._fast_path and cid not in conn._assembler._partial
            and not conn._held and not conn.broker.blocked
            and not conn._throttled):
        try:
            if conn._fused_ack(raw, 7, cid):
                return True
        except ChannelError as exc:
            await conn._soft_close_channel(cid, exc)
            return not conn.closing
    out = conn._assembler.feed_one(Frame(1, cid, payload))
    if out is None:
        return True
    if conn._route_pending:
        conn._flush_route_pending()
    return await conn._run_command(out)


# -- the cases: each builds deliveries outstanding in a World, lays out the
# ack frames, and says how many deliveries the runs must settle ----------


def _queues_on(w, channel, k, prefix="q"):
    queues = [w.queue(f"{prefix}{channel.id}-{i}") for i in range(k)]
    for queue in queues:
        w.consume(queue, channel)
    return queues


def _publish_over(w, queues, n):
    for _ in range(n):
        w.publish(w.rng.sample(queues, w.rng.choice((1, 1, 1, 2))))


async def plain_run(w):
    ch = w.channel(w.conn())
    _publish_over(w, _queues_on(w, ch, 8), 60)
    await w.settle()
    w.frames = w.acks(ch)
    return len(w.frames)


async def two_channels_interleaved(w):
    conn = w.conn()
    channels = [w.channel(conn, 1), w.channel(conn, 2)]
    for ch in channels:
        _publish_over(w, _queues_on(w, ch, 5), 30)
    await w.settle()
    left = [w.acks(ch) for ch in channels]
    turn = 0
    while left[0] or left[1]:
        take = w.rng.randrange(1, 6)
        w.frames += left[turn][:take]
        del left[turn][:take]
        turn ^= 1
    return len(w.frames)


async def unknown_tag_mid_run(w):
    ch = w.channel(w.conn())
    _publish_over(w, _queues_on(w, ch, 6), 40)
    await w.settle()
    frames = w.acks(ch)
    k = w.rng.randrange(5, len(frames) - 5)
    # a tag never issued: the channel closes there, with every ack before
    # it settled and the rest of the batch on a closing channel
    w.frames = frames[:k] + [(ch.connection, ack_frame(1, 10_000))] + \
        frames[k:]
    return k


async def multiple_mid_run(w):
    ch = w.channel(w.conn())
    _publish_over(w, _queues_on(w, ch, 6), 40)
    await w.settle()
    tags = sorted(ch.unacked)
    k = w.rng.randrange(10, 20)
    # singles above the multiple's range, the multiple, then the rest
    first = tags[k + 5:k + 15]
    rest = tags[k + 15:] + tags[k + 1:k + 5]
    w.frames = (w.acks(ch, first)
                + [(ch.connection, ack_frame(1, tags[k], multiple=True))]
                + w.acks(ch, rest))
    return len(first) + len(rest)


async def tx_channel(w):
    ch = w.channel(w.conn())
    _publish_over(w, _queues_on(w, ch, 4), 20)
    await w.settle()
    ch.mode = ChannelMode.TX
    w.frames = w.acks(ch)
    return 0


async def durable_persisted(w):
    ch = w.channel(w.conn())
    queues = [w.queue(f"d{i}", durable=True) for i in range(4)]
    for queue in queues:
        w.consume(queue, ch)
    for _ in range(20):
        w.publish([w.rng.choice(queues)], delivery_mode=2)
    await w.settle()
    assert all(msg.persisted for msg in w.messages)
    w.frames = w.acks(ch)
    return 0


async def stream_ack_in_the_middle(w):
    ch = w.channel(w.conn())
    queues = _queues_on(w, ch, 4)
    stream = w.queue("stream", cls=StreamQueue, durable=False,
                     arguments={"x-queue-type": "stream"})
    w.consume(stream, ch, arguments={"x-stream-offset": "first"})
    _publish_over(w, queues, 12)
    for _ in range(3):
        w.publish([stream])
    _publish_over(w, queues, 12)
    await w.settle()
    assert len(stream.outstanding) == 3
    tags = sorted(ch.unacked)
    streamed = [t for t in tags if ch.unacked[t].queue is stream]
    classic = [t for t in tags if t not in streamed]
    k = w.rng.randrange(3, len(classic) - 3)
    # a run, the stream's acks one by one, and a run again
    w.frames = w.acks(ch, classic[:k] + streamed + classic[k:])
    return len(classic)


async def trace_sampler_on(w):
    ch = w.channel(w.conn())
    _publish_over(w, _queues_on(w, ch, 4), 20)
    await w.settle()
    w.trace = TraceRuntime(sample_rate=0.0)
    w.frames = w.acks(ch)
    return 0


async def release_reaches_room_down(w):
    # high watermark 3,000, low 1,500: stage 1 (page) enters over 1,800
    # accounted bytes and exits at 900. Twenty bodies of 100 bytes reach
    # it; their releases, one a last reference, leave it at the ack that
    # takes the total to 900, as the per-frame path does
    ch = w.channel(w.conn())
    queues = _queues_on(w, ch, 4)
    for i in range(20):
        w.publish([queues[i % 4]], body=b"%02d" % i * 50)
    await w.settle()
    assert w.broker.flow.stage == 1 and not w.broker.blocked
    w.frames = w.acks(ch)
    return len(w.frames) - 1


async def prefetch_blocked_resumed(w):
    # a prefetch of 5 a consumer: each queue holds five outstanding and
    # the rest ready; the run's acks schedule the passes that deliver the
    # next five, in first-ack order
    ch = w.channel(w.conn())
    ch.prefetch_count_consumer = 5
    queues = _queues_on(w, ch, 3)
    for queue in queues:
        for _ in range(12):
            w.publish([queue])
    await w.settle()
    assert [len(q.outstanding) for q in queues] == [5, 5, 5]
    w.frames = w.acks(ch)
    return 15


CASES = {case.__name__: (case, broker_kw) for case, broker_kw in (
    (plain_run, {"memory_high_watermark": 1 << 30}),
    (two_channels_interleaved, {}),
    (unknown_tag_mid_run, {}),
    (multiple_mid_run, {}),
    (tx_channel, {}),
    (durable_persisted, {}),
    (stream_ack_in_the_middle, {}),
    (trace_sampler_on, {}),
    (release_reaches_room_down, {"memory_high_watermark": 3000,
                                 "memory_low_watermark": 1500}),
    (prefetch_blocked_resumed, {}),
)}


async def _both(case, seed, monkeypatch):
    """The case's state built in both worlds and its acks fed: the run's
    world, the per-frame world, and what the case said of the runs."""
    if not native_ext.available():
        pytest.skip("native scanner not built")
    build, broker_kw = CASES[case]
    worlds = []
    for as_run in (True, False):
        w = World(seed, **broker_kw)
        expected = await build(w)
        with monkeypatch.context() as patch:
            tracer = getattr(w, "trace", None)
            if tracer is not None:
                patch.setattr(trace, "ACTIVE", tracer)
            await w.feed(as_run)
        await w.settle()
        worlds.append(w)
    return worlds[0], worlds[1], expected


@pytest.mark.parametrize("seed", [0, 1, 2147483659])
@pytest.mark.parametrize("case", sorted(CASES))
async def test_the_ack_run_is_the_per_frame_path(case, seed, monkeypatch):
    run, ref, expected = await _both(case, seed, monkeypatch)
    got, want = run.state(), ref.state()
    for key in want:
        assert got[key] == want[key], key
    assert ref.broker.metrics.ack_run_msgs == ref.broker.metrics.ack_runs == 0
    m = run.broker.metrics
    assert m.ack_run_msgs == expected
    assert (m.ack_runs == 0) == (expected == 0)
    # an ack settled outside a transaction is timed, in a run or not
    assert (m.settle_ns > 0) == (m.acked_msgs > 0)


async def test_the_cases_stop_where_they_say(monkeypatch):
    """What each case is there to show, read from the run's world."""
    w, _, k = await _both("unknown_tag_mid_run", 5, monkeypatch)
    m = w.broker.metrics
    # the run settles the k acks before the unknown tag; the channel then
    # closes (406) and the acks after it fall on a closing channel
    assert (m.ack_runs, m.ack_run_msgs, m.acked_msgs) == (1, k, k)
    assert w.conns[0]._closing_channels == {1}
    assert b"unknown delivery tag 10000" in w.state()["wire"][0]

    w, _, n = await _both("multiple_mid_run", 5, monkeypatch)
    m = w.broker.metrics
    assert m.ack_runs == 2 and m.acked_msgs == n + 11

    w, _, n = await _both("two_channels_interleaved", 5, monkeypatch)
    assert 2 < w.broker.metrics.ack_runs < n

    w, _, n = await _both("stream_ack_in_the_middle", 5, monkeypatch)
    m = w.broker.metrics
    assert m.acked_msgs == n + 3 and m.ack_runs == 2
    assert w.queues[-1].committed == {"ctag-stream": 3}

    w, ref, n = await _both("release_reaches_room_down", 5, monkeypatch)
    m = w.broker.metrics
    # the release that takes the total to 900 is made at its own ack,
    # between two runs; the stage moves at the same ack in both worlds
    assert m.ack_runs == 2 and m.acked_msgs == n + 1
    assert w.stages[-1] == ref.stages[-1] == (1, 0, 11)
    assert w.broker.resident_bytes == 0

    w, _, _ = await _both("prefetch_blocked_resumed", 5, monkeypatch)
    assert w.broker.metrics.ack_runs == 1
    assert sorted(w.ready) == ["q1-0", "q1-1", "q1-2"]
    assert [len(q.outstanding) for q in w.queues] == [5, 5, 5]
    assert [len(q.messages) for q in w.queues] == [2, 2, 2]

    for case in ("tx_channel", "durable_persisted", "trace_sampler_on"):
        w, _, _ = await _both(case, 5, monkeypatch)
        assert w.broker.metrics.ack_runs == 0, case
