"""The durable path's counters held to the plain reference on the CPU at a
small size (PR 35): on seeded random tables and keys, after the confirms
`wal_queue_msgs_committed` is the sum over the publishes of the size of the
queue set `benchmarks/reference.py` `expected_sets_plain` gives them
(durable queues, persistent messages), and after every delivery is
acknowledged `acked_msgs` and `wal_settle_rows` equal it too, a
`settle_ns` stamped for the ack frames."""

import asyncio
import importlib.util
import os
import random

import pytest

from chanamq_tpu.amqp.properties import BasicProperties
from chanamq_tpu.broker.server import BrokerServer
from chanamq_tpu.client import AMQPClient
from chanamq_tpu.store.sqlite import SqliteStore
from chanamq_tpu.wal import WalStore

pytestmark = pytest.mark.asyncio

_spec = importlib.util.spec_from_file_location(
    "benchmarks_reference", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "reference.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

WORDS = ("a", "b", "c", "d")
N_QUEUES, N_BINDINGS, N_PUBLISHES = 6, 24, 150


def random_table(kind: str, rng: random.Random) -> dict:
    def topic_pattern() -> str:
        return ".".join(rng.choice(WORDS + ("*", "#"))
                        for _ in range(rng.randint(1, 3)))

    def binding():
        if kind == "topic":
            return topic_pattern(), None
        if kind == "headers":
            names = rng.sample(WORDS, rng.randint(1, 2))
            args = {name: rng.randint(0, 2) for name in names}
            args["x-match"] = rng.choice(("all", "any"))
            return "", args
        return ".".join(rng.choice(WORDS) for _ in range(2)), None

    queues = [f"dq{i}" for i in range(N_QUEUES)]
    bindings = []
    for _ in range(N_BINDINGS):
        key, args = binding()
        bindings.append((key, rng.choice(queues), args))
    return {"exchange": f"durable.{kind}", "type": kind, "queues": queues,
            "bindings": bindings}


def random_publishes(kind: str, rng: random.Random) -> list:
    out = []
    for _ in range(N_PUBLISHES):
        key = ".".join(rng.choice(WORDS) for _ in range(rng.randint(1, 3)))
        headers = None
        if kind == "headers":
            headers = {name: rng.randint(0, 2)
                       for name in rng.sample(WORDS, rng.randint(0, 3))}
        out.append((key, headers))
    return out


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
@pytest.mark.parametrize("kind", ["topic", "direct", "fanout", "headers"])
async def test_the_logs_counters_equal_the_plain_references_sets(
        tmp_path, kind, seed):
    rng = random.Random(f"{kind}-{seed}")
    table = random_table(kind, rng)
    publishes = random_publishes(kind, rng)
    want = sum(len(s) for s in
               reference.expected_sets_plain(table, publishes))
    assert want > N_PUBLISHES // 3  # the keys do aim at the table

    store = WalStore(SqliteStore(str(tmp_path / "store.db")), flush_ms=1.0)
    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                          store=store)
    # as server.main() wires them: the log counts into the broker's registry
    metrics = store.metrics = server.broker.metrics
    await server.start()
    conn = await AMQPClient.connect("127.0.0.1", server.bound_port)
    try:
        ch = await conn.channel()
        await ch.exchange_declare(table["exchange"], kind, durable=True)
        for queue in table["queues"]:
            await ch.queue_declare(queue, durable=True)
        for key, queue, args in table["bindings"]:
            await ch.queue_bind(queue, table["exchange"], key, arguments=args)
        assert metrics.wal_queue_msg_records == 0  # declares are no rows
        await ch.confirm_select()
        for key, headers in publishes:
            ch.basic_publish(b"telemetry-12", exchange=table["exchange"],
                             routing_key=key, properties=BasicProperties(
                                 delivery_mode=2, headers=headers))
        await ch.wait_unconfirmed_below(1, timeout=30)
        # a confirm waits for the commit of every row of its publish
        assert metrics.wal_queue_msg_records == want
        assert metrics.wal_queue_msgs_committed == want
        assert metrics.wal_commit_errors == 0
        assert metrics.acked_msgs == 0 and metrics.wal_settle_rows == 0

        got = []
        consumer = await conn.channel()
        await consumer.basic_qos(prefetch_count=50)

        def on_msg(msg) -> None:
            got.append(msg)
            consumer.basic_ack(msg.delivery_tag)

        for queue in table["queues"]:
            await consumer.basic_consume(queue, on_msg, no_ack=False)
        for _ in range(500):
            if (metrics.acked_msgs >= want
                    and metrics.wal_settle_rows >= want):
                break
            await asyncio.sleep(0.01)
        assert len(got) == want
        assert metrics.acked_msgs == want
        assert metrics.wal_settle_rows == want
        assert metrics.settle_ns > 0
        assert metrics.dispatch_run_msgs == 0  # acked consumers take no run
        assert server.broker.queue_unacked == 0
        # acks and deliveries log records of their own, none of them a
        # message-and-queue row
        assert metrics.wal_queue_msg_records == want
        assert metrics.wal_appends > want
    finally:
        await conn.close()
        await server.stop()
