"""The head run of a dispatch pass is held to the per-message path.

`ServerChannel.deliver_run` delivers the head messages of a queue to its one
plain consumer in one loop, `no_ack` or acknowledging (on a transient
queue); whatever it cannot prove it leaves to the per-message loop of
`Queue._dispatch`. Here the same seeded state is built twice and dispatched
(a) with the run and (b) with the run excluded by the test alone: the
queue's consumers are a trivial subclass of `Consumer`, which the type
condition (`takes_runs`) excludes; the product has no switch. Both worlds
must write the same bytes to every connection and end in the same state,
the deliveries left outstanding and the prefetch counts among it; then
every delivery is acknowledged in both and the states, refcounts and
resident bytes included, must agree again. The cases that the run must not
take at all show `dispatch_run_msgs` at 0.
"""

import asyncio
import random

import pytest

from chanamq_tpu import events, trace
from chanamq_tpu.amqp.properties import BasicProperties
from chanamq_tpu.broker.broker import Broker
from chanamq_tpu.broker.channel import Consumer, ServerChannel
from chanamq_tpu.broker.connection import AMQPConnection
from chanamq_tpu.broker.entities import Queue
from chanamq_tpu.cluster.node import RemoteConsumer
from chanamq_tpu.trace.runtime import TraceRuntime
from chanamq_tpu.utils.metrics import Histogram


class PerMessageConsumer(Consumer):
    """A plain consumer in all but type: `takes_runs` is False for it."""

    __slots__ = ()


class _Writer:
    transport = None

    def write(self, data):  # pragma: no cover - the writer task never runs
        pass


class World:
    """One broker with connections, queues and consumers built by hand (no
    sockets: what a pass writes stays in the connection's output list)."""

    def __init__(self, consumer_cls, seed, **broker_kw):
        self.rng = random.Random(seed)
        self.consumer_cls = consumer_cls
        self.broker = Broker(router_enabled=False, **broker_kw)
        self.conns = []
        self.queues = []
        self.consumers = []
        self.messages = []
        # what a case does once the first passes have run
        self.after = []

    def conn(self, frame_max=4096):
        conn = AMQPConnection(self.broker, None, _Writer(),
                              frame_max=frame_max)
        self.conns.append(conn)
        return conn

    def channel(self, conn, channel_id=1):
        if channel_id not in conn.channels:
            conn.channels[channel_id] = ServerChannel(conn, channel_id)
        return conn.channels[channel_id]

    def queue(self, name, **kw):
        queue = Queue(self.broker, "/", name, **kw)
        self.queues.append(queue)
        return queue

    def consume(self, queue, channel, no_ack=True, arguments=None):
        consumer = self.consumer_cls(
            f"ctag-{queue.name}-{len(self.consumers)}", channel, queue,
            no_ack, False, arguments)
        channel.consumers[consumer.tag] = consumer
        queue.add_consumer(consumer)
        self.consumers.append(consumer)
        return consumer

    def publish(self, queues, body=None, **props):
        """One message to `queues`, as the broker's publish paths push it;
        properties, header and exchange/key slices vary by the seed."""
        rng = self.rng
        if body is None:
            body = bytes(rng.getrandbits(8) for _ in range(
                rng.choice((0, 1, 12, 12, 12, 200, 5000, 9000))))
        if not props and rng.random() < 0.5:
            props = {"content_type": "text/plain", "delivery_mode": 1,
                     "headers": {"k": rng.randrange(1000)}}
        properties = BasicProperties(**props)
        exchange = rng.choice(("ex", "amq.topic", ""))
        key = "rk.%d" % rng.randrange(100)
        header = (properties.encode_header(len(body))
                  if rng.random() < 0.7 else None)
        exrk = None
        if rng.random() < 0.7:
            ex, rk = exchange.encode(), key.encode()
            exrk = bytes((len(ex),)) + ex + bytes((len(rk),)) + rk
        message = self.broker.push_local(
            list(queues), properties, body, exchange, key, header, None, exrk)
        self.messages.append(message)
        return message

    async def settle(self):
        """Run the scheduled passes (and hydrations) until nothing is due."""
        for _ in range(400):
            await asyncio.sleep(0)
            if not any(q._dispatch_scheduled or q._hydrating
                       for q in self.queues):
                break
        for conn in self.conns:
            conn.flush_egress()

    async def ack_all(self):
        """Acknowledge every outstanding delivery, each channel's in tag
        order, until the passes the acks schedule leave none."""
        for _ in range(50):
            pending = [(ch, tag) for conn in self.conns
                       for ch in conn.channels.values()
                       for tag in sorted(ch.unacked)]
            if not pending:
                return
            for ch, tag in pending:
                ch.ack(ch.unacked[tag])
            await self.settle()
        raise AssertionError("deliveries still outstanding")

    def state(self):
        m = self.broker.metrics
        return {
            "wire": [b"".join(bytes(part) for part in conn._out)
                     for conn in self.conns],
            "out_bytes": [conn._out_bytes for conn in self.conns],
            "conn_delivered": [conn.delivered_msgs for conn in self.conns],
            "tags": [{cid: ch._delivery_tag for cid, ch in conn.channels.items()}
                     for conn in self.conns],
            "queues": [(q.name, q.n_delivered, q.ready_bytes, q.last_consumed,
                        [qm.offset for qm in q.messages], len(q.outstanding))
                       for q in self.queues],
            "unacked": [{cid: [(tag, d.consumer_tag, d.queued.offset,
                                d.no_ack) for tag, d in ch.unacked.items()]
                         for cid, ch in conn.channels.items()}
                        for conn in self.conns],
            "outstanding": [[(offset, d.delivery_tag)
                             for offset, d in q.outstanding.items()]
                            for q in self.queues],
            "prefetch_held": [(c.unacked_count, c.unacked_size)
                              for c in self.consumers],
            "queue_unacked": self.broker.queue_unacked,
            "acked": self.broker.metrics.acked_msgs,
            "queue_depth": self.broker.queue_depth,
            "resident": self.broker.resident_bytes,
            "refs": [(msg.refer_count, msg.accounted, msg.body is None)
                     for msg in self.messages],
            "delivered": (m.delivered_msgs, m.delivered_bytes),
            "hist_count": m.publish_to_deliver_us.count,
            "hist_buckets": sum(m.publish_to_deliver_us.buckets),
            "slow": (m.flow_slow_consumers,
                     [(c.slow, c.buffered_bytes) for c in self.consumers]),
            "passes": m.dispatch_passes,
            "encoder_fallbacks": m.native_egress_fallbacks,
            "ladder": (m.flow_escalations, m.flow_deescalations,
                       self.broker.blocked,
                       None if self.broker.flow is None
                       else self.broker.flow.stage),
        }


# -- the cases: each builds a state in a World and says how many deliveries
# the run must have made (None: at least one; 0: the run must not engage) ---


def plain_run(w):
    ch = w.channel(w.conn())
    queue = w.queue("plain")
    w.consume(queue, ch)
    for _ in range(w.rng.randrange(20, 60)):
        w.publish([queue])
    return len(queue.messages)


def several_queues_two_connections(w):
    conns = [w.conn(), w.conn(frame_max=131072)]
    queues = []
    for i in range(6):
        queue = w.queue(f"q{i}")
        w.consume(queue, w.channel(conns[i % 2], 1 + i % 3))
        queues.append(queue)
    n = 0
    for _ in range(80):
        targets = w.rng.sample(queues, w.rng.randrange(1, 5))
        w.publish(targets)
        n += len(targets)
    return n


def _with_ttl(w, queue):
    w.publish([queue], expiration="86400000")


def ttl_in_the_middle(w):
    ch = w.channel(w.conn())
    queue = w.queue("ttl")
    w.consume(queue, ch)
    for i in range(30):
        if i == 9:
            _with_ttl(w, queue)
            queue.messages[-1].expire_at_ms -= 2 * 86_400_000  # expired
        elif i == 19:
            _with_ttl(w, queue)  # a TTL that has a day to run
        else:
            w.publish([queue])
    return 9


def dead_head(w):
    ch = w.channel(w.conn())
    queue = w.queue("dead")
    w.consume(queue, ch)
    for _ in range(12):
        w.publish([queue])
    queue.messages[0].dead = True
    return 0


def passivated_in_the_middle(w):
    # queue_max_resident 8: the ninth message on is paged out as it is
    # pushed; the pass stops there, hydrates, and goes on in a later pass
    ch = w.channel(w.conn())
    queue = w.queue("paged")
    w.consume(queue, ch)
    for _ in range(30):
        w.publish([queue])
    assert queue.messages[8].message.body is None
    return None


def fanout_last_reference(w):
    conn = w.conn()
    queues = [w.queue(f"f{i}") for i in range(4)]
    for i, queue in enumerate(queues):
        w.consume(queue, w.channel(conn, 1 + i))
    for _ in range(25):
        w.publish(queues)
        w.publish(w.rng.sample(queues, 2))
    return 25 * 6


def write_watermark_mid_run(w):
    ch = w.channel(w.conn(frame_max=131072))
    queue = w.queue("big")
    w.consume(queue, ch)
    for _ in range(60):
        w.publish([queue], body=b"b" * 100_000)
    return 42  # 4 MiB // (100,000 + framing), and the one that crosses it


def consumer_buffer_mid_run(w):
    ch = w.channel(w.conn())
    queue = w.queue("slow")
    w.consume(queue, ch)
    for _ in range(30):
        w.publish([queue], body=b"s" * 100)
    return 10


def channel_flow_off(w):
    ch = w.channel(w.conn())
    ch.flow_active = False
    queue = w.queue("stopped")
    w.consume(queue, ch)
    for _ in range(10):
        w.publish([queue])
    return 0


def channel_closed(w):
    ch = w.channel(w.conn())
    ch.closed = True
    queue = w.queue("closed")
    w.consume(queue, ch)
    for _ in range(10):
        w.publish([queue])
    return 0


def requeued_head(w):
    # entries back at the head with lower offsets and the redelivered mark
    ch = w.channel(w.conn())
    queue = w.queue("requeued")
    for _ in range(20):
        w.publish([queue])
    for _ in range(5):
        qm = queue.messages.pop()
        qm.redelivered = True
        queue.messages.appendleft(qm)
    queue.last_consumed = 17
    w.consume(queue, ch)
    return 20


def listener_writes_mid_run(w):
    # the memory gate reopens while the run delivers: its listener writes to
    # the consumer's own connection (as Connection.Unblocked does), which
    # flushes what the run has buffered so far
    conn = w.conn()
    ch = w.channel(conn)
    queue = w.queue("gated")
    w.broker.blocked_listeners.add(
        lambda blocked: conn.send_bytes(b"<blocked>" if blocked
                                        else b"<unblocked>"))
    w.consume(queue, ch)
    for _ in range(40):
        w.publish([queue], body=b"g" * 100)
    assert w.broker.blocked
    return 40


def durable_queue(w):
    ch = w.channel(w.conn())
    queue = w.queue("durable", durable=True)
    w.consume(queue, ch)
    for _ in range(20):
        w.publish([queue], delivery_mode=2)
    return 20


# -- the drain: a channel's head run carried across the passes of one drain


def many_queues_one_channel(w):
    # one drain of forty queues consumed on one channel: one head run
    # carries the channel's tags and the connection's batch through them
    ch = w.channel(w.conn())
    queues = [w.queue(f"one{i}") for i in range(40)]
    for queue in queues:
        w.consume(queue, ch)
    for _ in range(120):
        w.publish([w.rng.choice(queues)])
    return 120


def two_channels_interleaved(w):
    # two channels of one connection alternate in the ready list: each
    # pass hands the other channel's run over and opens its own
    conn = w.conn()
    channels = [w.channel(conn, 1), w.channel(conn, 2)]
    queues = [w.queue(f"alt{i}") for i in range(12)]
    for i, queue in enumerate(queues):
        w.consume(queue, channels[i % 2])
    n = 0
    for queue in queues:
        for _ in range(w.rng.randrange(1, 5)):
            w.publish([queue])
            n += 1
    return n


def ttl_head_then_run_queues(w):
    # the first queue's head run stops at a TTL message and its per-message
    # loop takes the rest; the queues of the same channel after it in the
    # drain open the channel's run again
    ch = w.channel(w.conn())
    first = w.queue("ttl-first")
    rest = [w.queue(f"after{i}") for i in range(3)]
    for queue in (first, *rest):
        w.consume(queue, ch)
    for i in range(10):
        if i == 4:
            _with_ttl(w, first)  # a day to run: delivered one by one
        else:
            w.publish([first])
    for queue in rest:
        for _ in range(6):
            w.publish([queue])
    return 4 + 18


def batch_full_mid_drain(w):
    # 2 kB bodies over thirty queues of one channel: the pending batch
    # outgrows a pooled buffer more than once inside the one drain
    ch = w.channel(w.conn())
    queues = [w.queue(f"full{i}") for i in range(30)]
    for queue in queues:
        w.consume(queue, ch)
        for _ in range(10):
            w.publish([queue], body=bytes([65 + w.rng.randrange(26)]) * 2000)
    return 300


def releases_cross_the_exit_mid_drain(w):
    # listener_writes_mid_run over eight queues of one channel: the last
    # references the run keeps would reach the ladder's exit thresholds
    # (stage 2's at 1,500 resident bytes, stage 1's at 900) in later passes
    # of the drain; the release that reaches one is made at its own
    # message, and the listeners write between the same two deliveries
    conn = w.conn()
    ch = w.channel(conn)
    w.broker.blocked_listeners.add(
        lambda blocked: conn.send_bytes(b"<blocked>" if blocked
                                        else b"<unblocked>"))
    w.broker.flow_stage_listeners.add(
        lambda old, new: conn.send_bytes(b"<stage %d>" % new))
    queues = [w.queue(f"gate{i}") for i in range(8)]
    for queue in queues:
        w.consume(queue, ch)
    for i in range(40):
        w.publish([queues[i % 8]], body=b"%03d" % i * 33 + b"!")
    assert w.broker.blocked
    return 40


def fanout_last_reference_in_a_later_pass(w):
    # each message goes to two to five of six queues of one channel: its
    # last reference falls in the pass of the last of them, later in the
    # drain than its first delivery; the accountant is on, at stage 0
    ch = w.channel(w.conn())
    queues = [w.queue(f"fan{i}") for i in range(6)]
    for queue in queues:
        w.consume(queue, ch)
    n = 0
    for _ in range(60):
        targets = w.rng.sample(queues, w.rng.randrange(2, 6))
        w.publish(targets)
        n += len(targets)
    return n


# -- acknowledging consumers: the run makes each delivery outstanding, and
# stops where a prefetch budget refuses the next message


def acked_consumer(w):
    conn = w.conn()
    queue = w.queue("q")
    for _ in range(12):
        w.publish([queue])
    w.consume(queue, w.channel(conn), no_ack=False)
    return 12


def acked_plain_run(w):
    conns = [w.conn(), w.conn(frame_max=131072)]
    queues = []
    for i in range(5):
        queue = w.queue(f"ack{i}")
        w.consume(queue, w.channel(conns[i % 2], 1 + i % 2), no_ack=False)
        queues.append(queue)
    n = 0
    for _ in range(60):
        targets = w.rng.sample(queues, w.rng.randrange(1, 4))
        w.publish(targets)
        n += len(targets)
    return n


def acked_count_prefetch_mid_run(w):
    # a per-consumer prefetch of 5 over 20 messages: each pass takes five,
    # and the acks open the window for the next five
    ch = w.channel(w.conn())
    ch.prefetch_count_consumer = 5
    queue = w.queue("window")
    w.consume(queue, ch, no_ack=False)
    for _ in range(20):
        w.publish([queue])
    return 5


def acked_size_prefetch_oversized(w):
    # a per-consumer prefetch of 1,000 bytes: three of 100 pass, the 5,000
    # byte one waits for the window to empty and then passes alone
    # (RabbitMQ's one oversized delivery while nothing is outstanding)
    ch = w.channel(w.conn())
    ch.prefetch_size_consumer = 1000
    queue = w.queue("bytes")
    w.consume(queue, ch, no_ack=False)
    for size in (100, 100, 100, 5000, 100, 100, 100, 100, 100):
        w.publish([queue], body=b"z" * size)
    return 3


def acked_global_prefetch_several_queues(w):
    # a channel-global prefetch of 7 over four queues of one channel: the
    # first queues' passes take seven, the later ones find it spent
    ch = w.channel(w.conn())
    ch.prefetch_count_global = 7
    queues = [w.queue(f"glob{i}") for i in range(4)]
    for queue in queues:
        w.consume(queue, ch, no_ack=False)
        for _ in range(5):
            w.publish([queue])
    return 7


def acked_global_size_prefetch(w):
    # a channel-global byte prefetch over three queues with seeded bodies:
    # the window is the channel's, its bytes summed over every delivery
    ch = w.channel(w.conn())
    ch.prefetch_size_global = 3000
    queues = [w.queue(f"gbytes{i}") for i in range(3)]
    for queue in queues:
        w.consume(queue, ch, no_ack=False)
        for _ in range(6):
            w.publish([queue], body=b"y" * w.rng.randrange(1, 1500))
    return None


def acked_and_no_ack_interleaved(w):
    # a no_ack channel and an acknowledging one of one connection alternate
    # in the ready list: each pass hands the other's run over
    conn = w.conn()
    channels = [w.channel(conn, 1), w.channel(conn, 2)]
    queues = [w.queue(f"mix{i}") for i in range(10)]
    for i, queue in enumerate(queues):
        w.consume(queue, channels[i % 2], no_ack=i % 2 == 0)
    n = 0
    for queue in queues:
        for _ in range(w.rng.randrange(1, 6)):
            w.publish([queue])
            n += 1
    return n


def acked_channel_closed_outstanding(w):
    # the channel closes with eight run-made deliveries outstanding and
    # twelve messages still ready: the eight go back ahead of them, in
    # offset order, marked redelivered
    ch = w.channel(w.conn())
    ch.prefetch_count_consumer = 8
    queue = w.queue("closing")
    w.consume(queue, ch, no_ack=False)
    for _ in range(20):
        w.publish([queue])
    w.after.append(ch.release_all)
    return 8


def acked_durable_queue(w):
    # on a durable queue a delivery writes its unack row: one by one
    ch = w.channel(w.conn())
    queue = w.queue("acked-durable", durable=True)
    w.consume(queue, ch, no_ack=False)
    for _ in range(20):
        w.publish([queue], delivery_mode=2)
    return 0


EQUIVALENT = {build.__name__: (build, broker_kw) for build, broker_kw in (
    (plain_run, {}),
    (several_queues_two_connections, {}),
    (ttl_in_the_middle, {}),
    (dead_head, {}),
    (passivated_in_the_middle, {"queue_max_resident": 8}),
    (fanout_last_reference, {}),
    (write_watermark_mid_run, {}),
    (consumer_buffer_mid_run, {"flow_consumer_buffer": 1000}),
    (channel_flow_off, {}),
    (channel_closed, {}),
    (requeued_head, {}),
    (listener_writes_mid_run, {"memory_high_watermark": 3000,
                               "memory_low_watermark": 1500}),
    (durable_queue, {}),
    (many_queues_one_channel, {}),
    (two_channels_interleaved, {}),
    (ttl_head_then_run_queues, {}),
    (batch_full_mid_drain, {}),
    (releases_cross_the_exit_mid_drain, {"memory_high_watermark": 3000,
                                         "memory_low_watermark": 1500}),
    (fanout_last_reference_in_a_later_pass,
     {"memory_high_watermark": 1 << 30}),
    (acked_consumer, {}),
    (acked_plain_run, {}),
    (acked_count_prefetch_mid_run, {}),
    (acked_size_prefetch_oversized, {}),
    (acked_global_prefetch_several_queues, {}),
    (acked_global_size_prefetch, {}),
    (acked_and_no_ack_interleaved, {}),
    (acked_channel_closed_outstanding, {}),
    (acked_durable_queue, {}),
)}


async def _both(case, seed):
    """The case's state built and dispatched in both worlds: the run's
    world, the per-message world, and what the case said of the run."""
    build, broker_kw = EQUIVALENT[case]
    worlds = []
    for cls in (Consumer, PerMessageConsumer):
        w = World(cls, seed, **broker_kw)
        if w.broker.egress_encoder is None:
            pytest.skip("native egress encoder not built")
        expected = build(w)
        await w.settle()
        for step in w.after:
            step()
        await w.settle()
        worlds.append(w)
    return worlds[0], worlds[1], expected


def _same(run, ref):
    got, want = run.state(), ref.state()
    for key in want:
        assert got[key] == want[key], key
    assert want["encoder_fallbacks"] == 0
    assert want["hist_count"] == want["hist_buckets"] == want["delivered"][0]
    return want


@pytest.mark.parametrize("seed", [0, 1, 2147483659])
@pytest.mark.parametrize("case", sorted(EQUIVALENT))
async def test_the_run_is_the_per_message_path(case, seed):
    run, ref, expected = await _both(case, seed)
    assert ref.broker.metrics.dispatch_run_msgs == 0
    want = _same(run, ref)
    m = run.broker.metrics
    in_run = m.dispatch_run_msgs
    if expected is None:
        assert 0 < in_run <= want["delivered"][0]
    else:
        assert in_run == expected
    assert m.dispatch_run_unacked <= in_run
    assert want["queue_unacked"] == sum(len(q) for q in want["outstanding"])
    # every delivery acknowledged in both worlds, and the passes the acks
    # scheduled run, the run's world again through its head runs
    await run.ack_all()
    await ref.ack_all()
    want = _same(run, ref)
    assert want["queue_unacked"] == 0 and not any(want["outstanding"])
    assert want["prefetch_held"] == [(0, 0)] * len(run.consumers)


async def test_the_cases_stop_where_they_say():
    """What each mid-run case is there to show, read from the run's world."""
    w, _, _ = await _both("write_watermark_mid_run", 5)
    assert w.conns[0].write_saturated and len(w.queues[0].messages) == 18
    w, _, _ = await _both("consumer_buffer_mid_run", 5)
    assert w.consumers[0].slow and w.broker.metrics.flow_slow_consumers == 1
    assert w.consumers[0].buffered_bytes == 1000
    w, _, _ = await _both("ttl_in_the_middle", 5)
    assert w.broker.metrics.delivered_msgs == 29  # the expired one dropped
    assert w.broker.metrics.dispatch_run_msgs == 9
    w, _, _ = await _both("dead_head", 5)
    assert w.broker.metrics.delivered_msgs == 11
    w, _, _ = await _both("passivated_in_the_middle", 5)
    assert w.broker.metrics.delivered_msgs == 30
    assert w.broker.metrics.dispatch_run_msgs == 30
    w, _, _ = await _both("fanout_last_reference", 5)
    assert w.broker.resident_bytes == 0
    assert all(msg.refer_count == 0 for msg in w.messages)
    w, _, _ = await _both("listener_writes_mid_run", 5)
    wire = b"".join(bytes(part) for part in w.conns[0]._out)
    assert not w.broker.blocked
    # the unblock lands between two deliveries, not after the last
    assert 0 < wire.index(b"<unblocked>") < wire.rindex(b"g" * 100)

    w, _, _ = await _both("many_queues_one_channel", 5)
    m = w.broker.metrics
    assert m.dispatch_drains == 1 and m.dispatch_run_setups == 1
    w, _, _ = await _both("two_channels_interleaved", 5)
    assert w.broker.metrics.dispatch_run_setups == 12  # one a pass
    w, _, _ = await _both("ttl_head_then_run_queues", 5)
    m = w.broker.metrics
    assert m.dispatch_drains == 1 and m.dispatch_run_setups == 2
    w, _, _ = await _both("batch_full_mid_drain", 5)
    m = w.broker.metrics
    assert m.dispatch_run_setups == 1 and m.native_egress_batches >= 3
    w, _, _ = await _both("releases_cross_the_exit_mid_drain", 5)
    wire = b"".join(bytes(part) for part in w.conns[0]._out)
    # queue i % 8 holds message i: the 25th release (queue 4's last message,
    # 36) leaves 1,500 bytes, the 31st (queue 6's first, 6) 900, each
    # written right after its own delivery; the wire
    # opens with the escalations the publishes made
    assert wire.startswith(b"<stage 1><blocked><stage 2>")
    assert (wire.index(b"036" * 33) < wire.index(b"<unblocked>")
            < wire.rindex(b"<stage 1>") < wire.index(b"005" * 33))
    assert (wire.index(b"006" * 33) < wire.index(b"<stage 0>")
            < wire.index(b"014" * 33))
    w, _, _ = await _both("fanout_last_reference_in_a_later_pass", 5)
    m = w.broker.metrics
    assert m.dispatch_run_setups == 1 and m.dispatch_run_releases == 60
    assert w.broker.resident_bytes == 0
    assert all(msg.refer_count == 0 for msg in w.messages)


async def test_the_acked_cases_stop_where_they_say():
    """Where each acknowledging case's budget binds, read from the run's
    world before and after every delivery is acknowledged."""
    w, ref, _ = await _both("acked_count_prefetch_mid_run", 5)
    m = w.broker.metrics
    consumer, queue = w.consumers[0], w.queues[0]
    assert (m.dispatch_run_unacked, m.dispatch_run_credit_stops) == (5, 1)
    assert (consumer.unacked_count, len(queue.messages)) == (5, 15)
    assert w.broker.queue_unacked == 5 and len(queue.outstanding) == 5
    await w.ack_all()
    await ref.ack_all()
    # four windows of five: the first three end at the budget, the last
    # where the queue does
    assert m.dispatch_run_msgs == m.dispatch_run_unacked == 20
    assert m.dispatch_run_credit_stops == 3
    assert m.acked_msgs == ref.broker.metrics.acked_msgs == 20
    assert ref.broker.metrics.dispatch_run_credit_stops == 0

    w, ref, _ = await _both("acked_size_prefetch_oversized", 5)
    m = w.broker.metrics
    assert w.consumers[0].unacked_size == 300
    await w.ack_all()
    await ref.ack_all()
    # 100 x 3 | 5,000 alone | 100 x 5
    assert m.dispatch_run_credit_stops == 2 and m.dispatch_run_msgs == 9
    assert w.broker.resident_bytes == ref.broker.resident_bytes == 0

    w, _, _ = await _both("acked_global_prefetch_several_queues", 5)
    m = w.broker.metrics
    # the second queue's pass spends the window, and it and the two after
    # it stop at the budget, each after a hand-over that closed the run
    assert m.dispatch_drains == 1 and m.dispatch_run_setups == 3
    assert m.dispatch_run_credit_stops == 3
    assert [len(q.outstanding) for q in w.queues] == [5, 2, 0, 0]

    w, _, _ = await _both("acked_and_no_ack_interleaved", 5)
    m = w.broker.metrics
    assert m.dispatch_run_setups == 10  # one a pass
    acked = sum(len(q.outstanding) for q in w.queues)
    assert 0 < acked == m.dispatch_run_unacked < m.dispatch_run_msgs

    w, _, _ = await _both("acked_channel_closed_outstanding", 5)
    queue = w.queues[0]
    offsets = [qm.offset for qm in queue.messages]
    assert offsets == sorted(offsets) and len(offsets) == 20
    assert [qm.redelivered for qm in queue.messages] == [True] * 8 + [False] * 12
    assert w.broker.queue_unacked == 0 and not queue.outstanding
    assert w.broker.metrics.dispatch_run_unacked == 8

    w, _, _ = await _both("acked_durable_queue", 5)
    m = w.broker.metrics
    assert m.dispatch_run_msgs == m.dispatch_run_setups == 0
    assert m.dispatch_run_unacked == 0 and len(w.queues[0].outstanding) == 20


async def test_the_head_run_counters():
    """dispatch_run_setups counts one head run a consuming channel a drain;
    dispatch_run_releases counts the last references released after their
    message, in one step, and none of those released at their own."""
    w = World(Consumer, 3)
    if w.broker.egress_encoder is None:
        pytest.skip("native egress encoder not built")
    conns = [w.conn(), w.conn()]
    channels = [w.channel(conns[0]), w.channel(conns[1])]
    queues = [w.queue(f"c{i}") for i in range(10)]
    for i, queue in enumerate(queues):
        w.consume(queue, channels[i % 2])
    m = w.broker.metrics
    for drains in (1, 2):
        for queue in queues:
            for _ in range(3):
                w.publish([queue], body=b"c" * 10)
        await asyncio.sleep(0)  # one tick: one drain
        assert m.dispatch_drains == drains
        assert m.dispatch_run_setups == 2 * drains
        assert m.dispatch_run_releases == m.dispatch_run_msgs == 30 * drains
        assert conns[0]._head_run is conns[1]._head_run is None
    assert w.broker.resident_bytes == 0

    # 40 messages of 100 bytes on one queue, released from 4,000 resident
    # bytes: the 25th release reaches stage 2's exit (1,500) and the 31st
    # stage 1's (900); those two are made at their message, each after a
    # hand-over that closes the run, which then opens again
    w, ref, _ = await _both("listener_writes_mid_run", 5)
    m = w.broker.metrics
    assert m.dispatch_drains == 1 and m.dispatch_run_setups == 3
    assert m.dispatch_run_releases == 40 - 2
    assert m.flow_deescalations == ref.broker.metrics.flow_deescalations == 2
    assert ref.broker.metrics.dispatch_run_setups == 0
    assert ref.broker.metrics.dispatch_run_releases == 0


# -- the run is not taken ---------------------------------------------------


class _Tap:
    tap_bindings = True

    def __init__(self):
        self.taps = 0

    def tap_publish(self, *args):
        pass

    def tap_deliver(self, *args):
        self.taps += 1


class _Cluster:
    membership = None


class _Tenant:
    def __init__(self):
        self.latency_hist = Histogram()


def _one_queue(w, n=12, **queue_kw):
    conn = w.conn()
    queue = w.queue("q", **queue_kw)
    for _ in range(n):
        w.publish([queue])
    return conn, queue


def two_consumers(w, monkeypatch):
    conn, queue = _one_queue(w)
    w.consume(queue, w.channel(conn, 1))
    w.consume(queue, w.channel(conn, 2))


def priority_queue(w, monkeypatch):
    conn, queue = _one_queue(w, arguments={"x-max-priority": 4})
    w.consume(queue, w.channel(conn))


def consumer_with_priority(w, monkeypatch):
    conn, queue = _one_queue(w)
    w.consume(queue, w.channel(conn), arguments={"x-priority": 3})


def single_active_consumer(w, monkeypatch):
    conn, queue = _one_queue(w, arguments={"x-single-active-consumer": True})
    w.consume(queue, w.channel(conn))


def remote_consumer(w, monkeypatch):
    _conn, queue = _one_queue(w)
    remote = RemoteConsumer(_Cluster(), "remote", queue, True, "peer", 1000)
    queue.add_consumer(remote)
    w.remote = remote


def active_trace(w, monkeypatch):
    conn, queue = _one_queue(w)
    w.consume(queue, w.channel(conn))
    monkeypatch.setattr(trace, "ACTIVE", TraceRuntime(sample_rate=0.0))


def firehose_tap(w, monkeypatch):
    conn, queue = _one_queue(w)
    w.consume(queue, w.channel(conn))
    w.tap = _Tap()
    monkeypatch.setattr(events, "FIREHOSE", w.tap)


def tenant_latency_histogram(w, monkeypatch):
    conn, queue = _one_queue(w)
    conn.tenant = w.tenant = _Tenant()
    w.consume(queue, w.channel(conn))


def no_native_encoder(w, monkeypatch):
    conn, queue = _one_queue(w)
    assert conn._egress is None
    w.consume(queue, w.channel(conn))


NOT_TAKEN = {build.__name__: (build, broker_kw) for build, broker_kw in (
    (two_consumers, {}),
    (priority_queue, {}),
    (consumer_with_priority, {}),
    (single_active_consumer, {}),
    (remote_consumer, {}),
    (active_trace, {}),
    (firehose_tap, {}),
    (tenant_latency_histogram, {}),
    (no_native_encoder, {"native_egress": False}),
)}


@pytest.mark.parametrize("case", sorted(NOT_TAKEN))
async def test_the_run_is_not_taken(case, monkeypatch):
    build, broker_kw = NOT_TAKEN[case]
    states = []
    for cls in (Consumer, PerMessageConsumer):
        w = World(cls, 7, **broker_kw)
        build(w, monkeypatch)
        if case == "remote_consumer":
            w.broker.drain_dispatch()
            assert w.remote._buf_count == 12
            w.remote._buf = []  # nothing for the scheduled flush to ship
        await w.settle()
        m = w.broker.metrics
        assert m.dispatch_run_msgs == 0
        assert w.queues[0].n_delivered == 12 and m.dispatch_passes >= 1
        states.append(w.state())
        if case == "firehose_tap":
            assert w.tap.taps == 12
        if case == "tenant_latency_histogram":
            assert w.tenant.latency_hist.count == 12
    assert states[0] == states[1]
