"""Acknowledging consumers take the head run, held to the plain reference
over a real socket on the CPU at a small size: on seeded random tables of
the four exchange types, transient queues and a backlog consumed by three
connections that acknowledge under a prefetch of 7 (which binds), every
delivery is the one `benchmarks/reference.py` `expected_sets_plain` says,
each once, with the delivery tags of each channel contiguous; after the last
ack nothing is ready or unacknowledged, `acked_msgs` equals the deliveries
and `dispatch_run_unacked` shows the run made them. The cell
`topic_acked_fleet_keys` then runs correct on the CPU."""

import asyncio
import importlib.util
import json
import os
import random
import subprocess
import sys

import pytest

from chanamq_tpu.amqp.properties import BasicProperties
from chanamq_tpu.broker.server import BrokerServer
from chanamq_tpu.client import AMQPClient

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "benchmarks_reference", os.path.join(ROOT, "benchmarks", "reference.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

WORDS = ("a", "b", "c", "d")
N_QUEUES, N_BINDINGS, N_PUBLISHES = 6, 24, 150
CONSUMERS, PREFETCH = 3, 7


def random_table(kind: str, rng: random.Random) -> dict:
    def binding():
        if kind == "topic":
            return ".".join(rng.choice(WORDS + ("*", "#"))
                            for _ in range(rng.randint(1, 3))), None
        if kind == "headers":
            names = rng.sample(WORDS, rng.randint(1, 2))
            args = {name: rng.randint(0, 2) for name in names}
            args["x-match"] = rng.choice(("all", "any"))
            return "", args
        return ".".join(rng.choice(WORDS) for _ in range(2)), None

    queues = [f"aq{i}" for i in range(N_QUEUES)]
    bindings = []
    for _ in range(N_BINDINGS):
        key, args = binding()
        bindings.append((key, rng.choice(queues), args))
    return {"exchange": f"acked.{kind}", "type": kind, "queues": queues,
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


@pytest.mark.parametrize("every", [1, 3])
@pytest.mark.parametrize("seed", [7, 2**31 + 44])
@pytest.mark.parametrize("kind", ["topic", "direct", "fanout", "headers"])
async def test_acked_deliveries_equal_the_plain_references_sets(
        kind, seed, every):
    rng = random.Random(f"{kind}-{seed}")
    table = random_table(kind, rng)
    publishes = random_publishes(kind, rng)
    expected = reference.expected_sets_plain(table, publishes)
    want = {(queue, i) for i, queues in enumerate(expected)
            for queue in queues}
    assert len(want) > N_PUBLISHES // 3  # the keys do aim at the table

    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    metrics = server.broker.metrics
    await server.start()
    conns = []
    try:
        conn = await AMQPClient.connect("127.0.0.1", server.bound_port)
        conns.append(conn)
        ch = await conn.channel()
        await ch.exchange_declare(table["exchange"], kind)
        for queue in table["queues"]:
            await ch.queue_declare(queue)
        for key, queue, args in table["bindings"]:
            await ch.queue_bind(queue, table["exchange"], key, arguments=args)
        await ch.confirm_select()
        # the backlog first, so that the prefetch window binds
        for i, (key, headers) in enumerate(publishes):
            ch.basic_publish(b"%d" % i, exchange=table["exchange"],
                             routing_key=key,
                             properties=BasicProperties(headers=headers))
        await ch.wait_unconfirmed_below(1, timeout=30)

        got = []
        tags = {}
        owing = []
        for index in range(CONSUMERS):
            consumer_conn = await AMQPClient.connect(
                "127.0.0.1", server.bound_port)
            conns.append(consumer_conn)
            consumer = await consumer_conn.channel()
            await consumer.basic_qos(prefetch_count=PREFETCH)
            owed = [0]
            owing.append((consumer, owed))

            def on_msg(msg, consumer=consumer, owed=owed, index=index):
                got.append((msg.consumer_tag, int(msg.body)))
                tags.setdefault(index, []).append(msg.delivery_tag)
                owed[0] += 1
                if owed[0] >= every:
                    consumer.basic_ack(msg.delivery_tag, multiple=every > 1)
                    owed[0] = 0

            for queue in table["queues"][index::CONSUMERS]:
                await consumer.basic_consume(
                    queue, on_msg, consumer_tag=queue, no_ack=False)
        for _ in range(500):
            if len(got) >= len(want):
                break
            await asyncio.sleep(0.01)
        # the last ack of each channel settles what fewer than `every`
        # deliveries left owed
        for index, (consumer, owed) in enumerate(owing):
            if owed[0]:
                consumer.basic_ack(tags[index][-1], multiple=True)
        for _ in range(500):
            if server.broker.queue_unacked == 0 \
                    and metrics.acked_msgs >= len(want):
                break
            await asyncio.sleep(0.01)

        assert len(got) == len(want)  # each once
        assert set(got) == want
        for index, issued in tags.items():
            assert issued == list(range(1, len(issued) + 1)), index
        assert server.broker.queue_unacked == 0
        assert server.broker.queue_depth == 0
        assert metrics.acked_msgs == metrics.delivered_msgs == len(want)
        assert metrics.dispatch_run_unacked > 0
        assert metrics.dispatch_run_credit_stops > 0  # the window bound
    finally:
        for conn in conns:
            await conn.close()
        await server.stop()


LIMIT_S = 300  # the test's own: the run takes ~25 s


def test_the_acked_cell_runs_correct_on_the_cpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "topic_acked_fleet_keys", "--seed", "1", "--seconds", "3",
         "--scale", "small", "--out", str(tmp_path / "out")],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=LIMIT_S)
    output = proc.stdout + proc.stderr[-3000:]
    assert proc.returncode == 0, output
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0, output
    assert list(last["compared"]) == [
        "unconfirmed", "missing", "unexpected", "duplicates", "unsettled"]
    assert all(pair == {"value": 0, "limit": 0}
               for pair in last["compared"].values()), output
    assert last["metrics"]["delivered_msgs_per_s"]["value"] > 0
