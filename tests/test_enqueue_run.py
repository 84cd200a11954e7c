"""The enqueue run of a deferred flush is held to the per-message path.

`Broker.flush_deferred_publishes` enqueues a flush as a run
(`Broker._enqueue_run`): one loop builds and pushes the transient messages
whose routed queues are all plain, and whatever it cannot prove goes, in
arrival order, through `_publish_local`. Here the same seeded world is built
twice: one flush goes through `flush_deferred_publishes`, the other through a
loop of `_publish_local` calls in the same order (the per-message path as
`publish_sync` calls it; the product has no switch). Both worlds must end in
the same state, and then deliver the same bytes; the cases the run must hand
over or not take say how many publishes it may count.
"""

import asyncio
import random

import pytest

from chanamq_tpu import events, trace
from chanamq_tpu.amqp.properties import BasicProperties
from chanamq_tpu.broker import entities
from chanamq_tpu.broker.broker import Broker
from chanamq_tpu.broker.channel import Consumer, ServerChannel
from chanamq_tpu.broker.connection import AMQPConnection
from chanamq_tpu.broker.entities import Queue
from chanamq_tpu.cluster.idgen import IdGenerator
from chanamq_tpu.replicate.log import ReplicationManager
from chanamq_tpu.store.sqlite import SqliteStore
from chanamq_tpu.streams import queue as streams_queue
from chanamq_tpu.streams.queue import StreamQueue
from chanamq_tpu.trace.runtime import TraceRuntime
from chanamq_tpu.wal import WalStore

NOW_MS = 1_790_000_000_000


@pytest.fixture(autouse=True)
def _one_clock(monkeypatch):
    """Expiries and stream timestamps read one frozen clock, so the two
    worlds compare exactly (ids and `published_ns` keep the real one)."""
    monkeypatch.setattr(entities, "now_ms", lambda: NOW_MS)
    monkeypatch.setattr(streams_queue, "now_ms", lambda: NOW_MS)


class _Writer:
    transport = None

    def write(self, data):  # pragma: no cover - the writer task never runs
        pass


class _Routes:
    """In the router's place: the flush's routes, made by the case."""

    def __init__(self, routes):
        self.routes = routes

    def route_pending(self, vhost_name, entries):
        assert len(entries) == len(self.routes)
        return self.routes, 0, 0


class _Ids(IdGenerator):
    """The broker's generator, keeping what it drew in order."""

    __slots__ = ("drawn",)

    def next_id(self):
        self.drawn.append(super().next_id())
        return self.drawn[-1]


class World:
    """One broker with queues, consumers and one flush, built by hand."""

    def __init__(self, seed, store=None, **broker_kw):
        self.rng = random.Random(seed)
        broker_kw.setdefault("memory_high_watermark", 512 << 20)
        self.broker = Broker(store=store, router_enabled=False, **broker_kw)
        if store is not None:
            # as server.main() wires them: the log counts into the broker's
            store.metrics = self.broker.metrics
        self.conn = AMQPConnection(self.broker, None, _Writer(),
                                   frame_max=131072)
        self.queues = []
        self.entries = []
        self.routes = []
        self.marks = []
        self.ids = []
        self.stages = []
        self.broker.idgen = _Ids(0)
        self.broker.idgen.drawn = self.ids
        if self.broker.flow is not None:
            # a stage change is recorded with what had been published, made
            # resident and drawn when it happened: "at the same message"
            self.broker.flow.listeners.append(lambda old, new: self.stages.append(
                (old, new, self.broker.metrics.published_msgs,
                 self.broker.resident_bytes, self.broker.queue_depth,
                 len(self.ids))))

    def queue(self, name, cls=Queue, consumer=True, **kw):
        queue = cls(self.broker, "/", name, **kw)
        self.queues.append(queue)
        if consumer:
            channel_id = len(self.queues)
            channel = self.conn.channels[channel_id] = ServerChannel(
                self.conn, channel_id)
            consumer = Consumer(f"ctag-{name}", channel, queue, True, False,
                                None)
            channel.consumers[consumer.tag] = consumer
            queue.add_consumer(consumer)
        return queue

    def entry(self, queues, body=None, confirmed=None, **props):
        """One row of a flush, as the connection defers it; body, header
        and exchange/key slices vary by the seed."""
        rng = self.rng
        if body is None:
            body = bytes(rng.getrandbits(8) for _ in range(
                rng.choice((0, 1, 12, 12, 12, 200, 5000))))
        if not props and rng.random() < 0.5:
            props = {"content_type": "text/plain",
                     "delivery_mode": rng.choice((None, 1)),
                     "headers": {"k": rng.randrange(1000)}}
        properties = BasicProperties(**props)
        exchange = rng.choice(("ex", "amq.topic"))
        key = "rk.%d" % rng.randrange(100)
        header = (properties.encode_header(len(body))
                  if rng.random() < 0.7 else None)
        exrk = None
        if rng.random() < 0.7:
            ex, rk = exchange.encode(), key.encode()
            exrk = bytes((len(ex),)) + ex + bytes((len(rk),)) + rk
        if confirmed is None:
            confirmed = rng.random() < 0.6
        self.entries.append(
            (exchange, key, properties, body, header, exrk, confirmed))
        self.routes.append(queues)

    def flush(self, per_message):
        broker = self.broker
        if not per_message:
            broker.router = _Routes(self.routes)
            broker.flush_deferred_publishes("/", self.entries, self.marks)
            return
        for entry, queues in zip(self.entries, self.routes):
            exchange, key, props, body, header, exrk, confirmed = entry
            broker.metrics.published(len(body))
            broker._publish_local(
                queues, exchange, key, props, body, False, header,
                self.marks if confirmed else None, exrk)

    def state(self):
        broker = self.broker
        m = broker.metrics
        flow = broker.flow

        def message(msg):
            return (msg.properties, msg.body, msg.exchange, msg.routing_key,
                    msg.ttl_ms, msg.refer_count, msg.persisted,
                    msg.header_raw, msg.accounted, msg.paged, msg.exrk_raw,
                    msg.trace)

        def entries(queue):
            if queue.is_stream:
                return [(rec.offset, rec.ts_ms, rec.exchange, rec.routing_key,
                         rec.header_raw, rec.body) for rec in queue._active]
            return [(qm.offset, qm.expire_at_ms, qm.body_size, qm.redelivered,
                     qm.priority, qm.dead, message(qm.message))
                    for qm in queue.messages]

        return {
            "queues": [(q.name, q.next_offset, q.ready_bytes, q.n_published,
                        q._dispatch_scheduled,
                        [qm.offset for qm in q._passivated], entries(q))
                       for q in self.queues],
            "queue_depth": broker.queue_depth,
            "published": (m.published_msgs, m.published_bytes),
            "resident": broker.resident_bytes,
            "flow": (None if flow is None else
                     (flow.total, flow.peak_total, flow.stage,
                      dict(flow.components))),
            "paging": (broker.flow_paging, broker.flow_page_resident_active,
                       broker.blocked),
            "stages": list(self.stages),
            "dispatch_ready": [q.name for q in broker.dispatch_ready],
            "marks": list(self.marks),
            "ids_drawn": len(self.ids),
            "counters": (m.semantics_priority_msgs, m.flow_paged_bodies,
                         m.flow_paged_bytes, m.flow_escalations,
                         m.flow_deescalations, m.stream_appends,
                         m.stream_append_bytes, m.dead_lettered_msgs,
                         m.expired_msgs, m.wal_appends,
                         m.wal_queue_msg_records),
        }

    async def delivered(self):
        """Run the passes the flush scheduled (and their hydrations); what
        they wrote and left behind."""
        for _ in range(400):
            await asyncio.sleep(0)
            if not any(q._dispatch_scheduled or q._hydrating
                       for q in self.queues):
                break
        self.conn.flush_egress()
        m = self.broker.metrics
        return {
            "wire": b"".join(bytes(part) for part in self.conn._out),
            "delivered": (m.delivered_msgs, m.delivered_bytes),
            "queues": [(q.name, q.n_delivered, q.ready_bytes, len(q.messages))
                       for q in self.queues],
            "queue_depth": self.broker.queue_depth,
            "resident": self.broker.resident_bytes,
        }


async def both(build, seed, stores=(None, None), **broker_kw):
    """`build`'s world made twice and flushed both ways: the run's world and
    the per-message world, each with its state after the flush and after the
    deliveries, and what `build` said the run must count."""
    out = []
    for per_message, store in zip((False, True), stores):
        w = World(seed, store=store, **broker_kw)
        w.expected = build(w)
        w.flush(per_message)
        w.after_flush = w.state()
        w.after_delivery = await w.delivered()
        out.append(w)
    return out


def held_equal(run, ref):
    for key, want in ref.after_flush.items():
        assert run.after_flush[key] == want, key
    for key, want in ref.after_delivery.items():
        assert run.after_delivery[key] == want, key
    for w in (run, ref):
        assert all(a < b for a, b in zip(w.ids, w.ids[1:])), "ids must rise"
    assert ref.broker.metrics.enqueue_run_msgs == 0
    assert ref.broker.metrics.enqueue_run_pushes == 0


def assert_run_counts(run, msgs, pushes):
    m = run.broker.metrics
    assert (m.enqueue_run_msgs, m.enqueue_run_pushes) == (msgs, pushes)


# -- all of the flush is the run's: seeds x fan-outs --------------------------


def fan_out(kind):
    def build(w):
        queues = [w.queue(f"q{i}", consumer=i % 5 != 4) for i in range(12)]
        # route lists are shared between entries, as the router's memo
        # hands them out
        nowhere = []
        pool = {
            "none": [nowhere],
            "one": [[q] for q in queues[:6]],
            "many": [w.rng.sample(queues, w.rng.randrange(2, 12))
                     for _ in range(8)],
        }
        pool["mixed"] = pool["none"] + pool["one"][:3] + pool["many"][:4]
        n = w.rng.randrange(150, 400)
        for _ in range(n):
            w.entry(w.rng.choice(pool[kind]))
        return n, sum(len(r) for r in w.routes)
    return build


@pytest.mark.parametrize("seed", [0, 1, 2147483659])
@pytest.mark.parametrize("kind", ["none", "one", "many", "mixed"])
async def test_a_flush_of_plain_transient_messages_is_one_run(kind, seed):
    run, ref = await both(fan_out(kind), seed)
    held_equal(run, ref)
    assert_run_counts(run, *run.expected)
    assert run.marks == []  # a transient message writes nothing to the store
    if kind != "none":
        assert run.after_delivery["delivered"][0] > 0
        assert run.after_flush["dispatch_ready"]


@pytest.mark.parametrize("seed", [3, 4])
async def test_a_broker_without_an_accountant_runs_the_same(seed):
    run, ref = await both(fan_out("mixed"), seed, memory_high_watermark=0)
    assert run.broker.flow is None
    held_equal(run, ref)
    assert_run_counts(run, *run.expected)


# -- what the run hands over, message by message, or does not take ------------


def beside_a_plain_queue(prepare=None, **special_kw):
    """30 publishes in turn to a plain queue, to the special one, to both:
    the run may take the first of every three and nothing else."""
    def build(w):
        plain = w.queue("plain")
        special = w.queue("special", **special_kw)
        if prepare is not None:
            prepare(w, special)
        assert plain.plain and not special.plain
        for route in ([plain], [special], [plain, special]) * 10:
            w.entry(route, body=b"b" * w.rng.randrange(1, 40))
        return 10, 10
    return build


def attach_a_replication_log(w, queue):
    class _Rpc:
        def register(self, name, handler):
            pass

    class _Ring:
        def preference_entity(self, *args):
            return ["n1"]

    class _Node:
        name = "n1"
        broker = w.broker
        rpc = _Rpc()
        ring = _Ring()
        membership = None

    assert queue.plain  # durability alone keeps a queue plain
    ReplicationManager(_Node()).attach(queue)
    assert queue.repl is not None
    queue.repl._ship_task.cancel()  # the meta event has nobody to go to


def by_message(**props):
    """30 publishes to two plain queues, every third with `props`: those
    leave the run, one by one, and the run goes on after each."""
    def build(w):
        a, b = w.queue("a"), w.queue("b", consumer=False)
        for i in range(30):
            route = ([a], [b], [a, b])[i % 3]
            if i % 3 == 1:
                w.entry(route, **props)
            else:
                w.entry(route)
        return 20, 30
    return build


def persistent_routed_nowhere(w):
    # a persistent publish is never the run's, wherever it goes: the run
    # takes none of a persistent flush
    queue = w.queue("q")
    for i in range(30):
        if i % 3 == 1:
            w.entry([], delivery_mode=2)
        else:
            w.entry([queue] if i % 3 else [])
    return 20, 10


def at_the_resident_cap(w):
    # queue_max_resident 8: the run fills `capped` to its cap with entries
    # 0 to 11; every later push there pages its body out, which is the
    # per-message path's, while the publishes to `free` alone stay the run's
    capped, free = w.queue("capped", consumer=False), w.queue("free")
    for i in range(24):
        w.entry(([capped], [free], [capped])[i % 3], body=b"p" * 50)
    return 12 + 4, 12 + 4


def crossing_the_next_threshold(w):
    # high watermark 3,000: the page stage enters above 1,800, at the 19th
    # body of 100. With flow_page_resident 4 both queues are then over the
    # pressure cap and page out whatever they are pushed; a third queue,
    # reached later, is the run's again until it holds 4
    queues = [w.queue("x"), w.queue("y", consumer=False)]
    for i in range(45):
        w.entry([queues[i % 2]], body=b"m" * 100)
    late = w.queue("late")
    for _ in range(6):
        w.entry([late], body=b"m" * 100)
    return 18 + 4, 18 + 4


def a_consumer_beside_none(w):
    with_consumer, without = w.queue("c"), w.queue("n", consumer=False)
    for i in range(40):
        w.entry(([with_consumer], [without], [without, with_consumer])[i % 3])
    return 40, 53


HANDED_OVER = {
    "x-max-priority": (beside_a_plain_queue(
        arguments={"x-max-priority": 5}), {}),
    "x-message-ttl": (beside_a_plain_queue(ttl_ms=60_000), {}),
    "x-max-length": (beside_a_plain_queue(
        arguments={"x-max-length": 7}), {}),
    "x-max-length-bytes": (beside_a_plain_queue(
        arguments={"x-max-length-bytes": 120}), {}),
    "x-queue-mode=lazy": (beside_a_plain_queue(
        arguments={"x-queue-mode": "lazy"}), {}),
    "stream": (beside_a_plain_queue(cls=StreamQueue), {}),
    "replication log": (beside_a_plain_queue(
        attach_a_replication_log, durable=True), {}),
    "persistent on transient queues": (by_message(delivery_mode=2), {}),
    "persistent routed nowhere": (persistent_routed_nowhere, {}),
    "expiration": (by_message(expiration="60000"), {}),
    "resident cap": (at_the_resident_cap, {"queue_max_resident": 8}),
    "threshold": (crossing_the_next_threshold, {
        "memory_high_watermark": 3000, "memory_low_watermark": 1500,
        "flow_page_resident": 4}),
    "no consumer": (a_consumer_beside_none, {}),
}


@pytest.mark.parametrize("case", sorted(HANDED_OVER))
async def test_what_the_run_cannot_prove_is_publish_locals(case):
    build, broker_kw = HANDED_OVER[case]
    run, ref = await both(build, 11, **broker_kw)
    held_equal(run, ref)
    if run.expected is not None:
        assert_run_counts(run, *run.expected)
    assert run.broker.metrics.published_msgs == len(run.entries)


async def test_the_cases_hand_over_where_they_say():
    """What each hand-over case is there to show, read from the run's world."""
    run, _ = await both(HANDED_OVER["x-max-priority"][0], 5)
    assert run.broker.metrics.semantics_priority_msgs == 20
    run, _ = await both(HANDED_OVER["x-message-ttl"][0], 5)
    assert [e[1] for e in run.after_flush["queues"][1][6]] == [
        NOW_MS + 60_000] * 20
    run, _ = await both(HANDED_OVER["x-max-length"][0], 5)
    assert len(run.after_flush["queues"][1][6]) == 7
    assert run.after_flush["queues"][1][1] == 21
    run, _ = await both(HANDED_OVER["x-queue-mode=lazy"][0], 5)
    assert len(run.after_flush["queues"][1][6]) == 20
    run, _ = await both(HANDED_OVER["stream"][0], 5)
    assert run.broker.metrics.stream_appends == 20
    run, _ = await both(HANDED_OVER["expiration"][0], 5)
    assert [e[1] for e in run.after_flush["queues"][1][6]] == [
        NOW_MS + 60_000, None] * 10  # the run's pushes between them

    build, kw = HANDED_OVER["resident cap"]
    run, ref = await both(build, 5, **kw)
    capped = run.after_flush["queues"][0]
    # 16 pushes: the first eight resident, every later one paged out as it
    # was pushed, once, by the per-message path
    assert capped[5] == list(range(9, 17))
    assert [e[6][1] is None for e in capped[6]] == [False] * 8 + [True] * 8
    assert run.broker.metrics.flow_paged_bodies == 0  # no pressure: the cap

    build, kw = HANDED_OVER["threshold"]
    run, ref = await both(build, 5, **kw)
    # the page stage at the 19th message (1,900 > 1,800), both ways, with
    # 18 published, resident and drawn before it: the crossing message was
    # accounted alone. No second stage: paging holds the gauge under 3,000
    assert run.after_flush["stages"] == [(0, 1, 19, 1900, 18, 19)]
    assert ref.after_flush["stages"] == [(0, 1, 19, 1900, 18, 19)]
    assert run.broker.metrics.flow_paged_bodies == 45 - 18 + 2
    assert run.after_flush["paging"] == (True, 4, False)


async def test_a_persistent_message_on_a_durable_queue_keeps_its_marks(
        tmp_path):
    def build(w):
        durable = w.queue("durable", durable=True)
        transient = w.queue("transient")
        assert durable.plain  # durability alone keeps a queue plain
        for i in range(30):
            route = ([durable], [transient], [durable, transient])[i % 3]
            if i % 2:
                w.entry(route, confirmed=True, delivery_mode=2)
            else:
                w.entry(route, confirmed=True)
        return 15, 20

    stores = [WalStore(SqliteStore(str(tmp_path / f"{name}.db")),
                       flush_ms=1.0) for name in ("run", "ref")]
    for store in stores:
        await store.open()
    try:
        run, ref = await both(build, 13, stores=stores)
        held_equal(run, ref)
        assert_run_counts(run, *run.expected)
        # a mark a persistent publish that reached the durable queue: 10
        assert len(run.marks) == 10 and run.marks == ref.marks
        assert all(lo < hi for lo, hi in run.marks)
        assert run.after_flush["counters"][-2:] == (10, 10)
    finally:
        for store in stores:
            await store.close()


class _Tap:
    tap_bindings = True

    def __init__(self):
        self.published = 0

    def tap_publish(self, *args):
        self.published += 1

    def tap_deliver(self, *args):
        pass


@pytest.mark.parametrize("door", ["trace sampler", "firehose tap"])
async def test_the_run_is_not_taken(door, monkeypatch):
    """A flush under a trace sampler or a bound firehose tap runs the
    per-message loop, all of it."""
    tap = _Tap()
    if door == "trace sampler":
        monkeypatch.setattr(trace, "ACTIVE", TraceRuntime(sample_rate=0.0))
    else:
        monkeypatch.setattr(events, "FIREHOSE", tap)
    run, ref = await both(fan_out("mixed"), 17)
    held_equal(run, ref)
    assert_run_counts(run, 0, 0)
    assert run.broker.metrics.published_msgs == len(run.entries)
    if door == "firehose tap":
        routed = sum(1 for r in run.routes if r)
        assert tap.published == 2 * routed
