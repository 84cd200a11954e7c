"""Federation tests: segment shipping, cursor mirroring, DLX/Tx forwarding.

Covers the chanamq_tpu/federation/ contract: sealed segments ship to the
remote mirror CRC-checked and resume from the receiver's position (the
mirror is the source of truth — duplicates ack idempotently, gaps answer
with a resync hint), named-cursor commits mirror so a consumer group can
fail over, dead-letter publishes to federated exchanges forward a copy,
committed transactions arrive as one idempotent batch, and the whole
surface is observable (admin endpoint, Prometheus gauges, SLI samples).
"""

import asyncio
import json
import zlib

import pytest

from chanamq_tpu.amqp.properties import BasicProperties
from chanamq_tpu.broker.server import BrokerServer
from chanamq_tpu.client import AMQPClient
from chanamq_tpu.cluster.dataplane import _put_ss
from chanamq_tpu.cluster.rpc import RpcError
from chanamq_tpu.federation import FederationService, links_from_json
from chanamq_tpu.federation.link import _parse_gap
from chanamq_tpu.rest.admin import AdminServer
from chanamq_tpu.store.memory import MemoryStore
from chanamq_tpu.streams.segment import StreamRecord, pack_records

pytestmark = pytest.mark.asyncio

PERSISTENT = BasicProperties(delivery_mode=2)
# small segments so a handful of publishes seals (and ships) several
STREAM_SMALL = {"x-queue-type": "stream",
                "x-stream-max-segment-size-bytes": 256}


async def eventually(predicate, timeout=10.0, what="condition"):
    deadline = asyncio.get_event_loop().time() + timeout
    while not predicate():
        assert asyncio.get_event_loop().time() < deadline, \
            f"timed out waiting for {what}"
        await asyncio.sleep(0.02)


async def start_pair(queues=("fq",), exchanges=()):
    """Two independent brokers joined by one A->B link ("to-b")."""
    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await b_srv.start()
    fed_b = FederationService(b_srv.broker, node_name="cluster-b", port=0)
    await fed_b.start()
    a_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await a_srv.start()
    fed_a = FederationService(
        a_srv.broker, node_name="cluster-a", port=0,
        retry_s=0.05, idle_s=0.02,
        links=[{"name": "to-b", "host": "127.0.0.1", "port": fed_b.port,
                "queues": list(queues), "exchanges": list(exchanges)}])
    await fed_a.start()
    return a_srv, fed_a, b_srv, fed_b


async def stop_pair(a_srv, fed_a, b_srv, fed_b):
    await fed_a.stop()
    await a_srv.stop()
    await fed_b.stop()
    await b_srv.stop()


async def collect(ch, queue, n, *, offset="first", tag="", ack=True,
                  timeout=10.0):
    got: list = []
    done = asyncio.get_event_loop().create_future()

    def on_msg(msg):
        if len(got) >= n:
            return
        got.append(msg)
        if ack:
            ch.basic_ack(msg.delivery_tag)
        if len(got) >= n and not done.done():
            done.set_result(None)

    used_tag = await ch.basic_consume(
        queue, on_msg, consumer_tag=tag,
        arguments={"x-stream-offset": offset})
    await asyncio.wait_for(done, timeout)
    await ch.basic_cancel(used_tag)
    return got


def _ship_payload(vhost, qname, base, last, blob, crc=None, token=""):
    head = bytearray()
    _put_ss(head, token)
    _put_ss(head, vhost)
    _put_ss(head, qname)
    head += base.to_bytes(8, "big")
    head += last.to_bytes(8, "big")
    head += (0).to_bytes(8, "big")   # first_ts_ms
    head += (0).to_bytes(8, "big")   # last_ts_ms
    crc = zlib.crc32(blob) & 0xFFFFFFFF if crc is None else crc
    head += crc.to_bytes(4, "big")
    head += len(blob).to_bytes(4, "big")
    return memoryview(bytes(head) + blob)


def _tx_payload(link, epoch, seq, publishes, token=""):
    """FED_TX wire: publishes is [(exchange, rkey, header, body), ...]."""
    buf = bytearray()
    _put_ss(buf, token)
    _put_ss(buf, link)
    _put_ss(buf, epoch)
    buf += seq.to_bytes(8, "big")
    _put_ss(buf, "/")
    buf += len(publishes).to_bytes(4, "big")
    for exchange, rkey, header, body in publishes:
        _put_ss(buf, exchange)
        _put_ss(buf, rkey)
        buf += len(header).to_bytes(4, "big")
        buf += header
        buf += len(body).to_bytes(4, "big")
        buf += body
    return memoryview(bytes(buf))


def _pub_payload(link, epoch, seq, exchange, rkey, header, body, token=""):
    buf = bytearray()
    _put_ss(buf, token)
    _put_ss(buf, link)
    _put_ss(buf, epoch)
    buf += seq.to_bytes(8, "big")
    _put_ss(buf, "/")
    _put_ss(buf, exchange)
    _put_ss(buf, rkey)
    buf += len(header).to_bytes(4, "big")
    buf += header
    buf += len(body).to_bytes(4, "big")
    buf += body
    return memoryview(bytes(buf))


def _records(base, last, prefix="r"):
    header = BasicProperties(delivery_mode=2).encode_header(8)
    return [StreamRecord(i, 1000 + i, "", "q", header,
                         f"{prefix}{i:06d}".encode())
            for i in range(base, last + 1)]


# ---------------------------------------------------------------------------
# config parsing
# ---------------------------------------------------------------------------


async def test_links_from_json_validation():
    assert links_from_json("") == []
    assert links_from_json("   ") == []
    specs = links_from_json(
        '[{"name": "west", "host": "h", "port": 1, "queues": ["q"]}]')
    assert specs[0]["name"] == "west" and specs[0]["queues"] == ["q"]
    with pytest.raises(ValueError):
        links_from_json('{"name": "not-a-list"}')
    with pytest.raises(ValueError):
        links_from_json('[{"name": "x", "host": "h"}]')  # missing port
    with pytest.raises(ValueError):
        links_from_json('["just-a-string"]')


# ---------------------------------------------------------------------------
# segment shipping + cursor mirroring
# ---------------------------------------------------------------------------


async def test_sealed_segments_ship_to_mirror():
    a_srv, fed_a, b_srv, fed_b = await start_pair()
    try:
        conn = await AMQPClient.connect("127.0.0.1", a_srv.bound_port)
        ch = await conn.channel()
        await ch.confirm_select()
        await ch.queue_declare("fq", durable=True, arguments=STREAM_SMALL)
        for i in range(30):
            ch.basic_publish(f"f{i:06d}".encode(), routing_key="fq",
                             properties=PERSISTENT)
        await ch.wait_unconfirmed_below(1, timeout=15)
        a_queue = a_srv.broker.get_queue("/", "fq")
        sealed_tail = a_queue._active_base  # unsealed records don't ship
        assert sealed_tail > 1, "expected at least one sealed segment"
        await eventually(
            lambda: ("fq" in b_srv.broker.vhosts["/"].queues
                     and b_srv.broker.vhosts["/"].queues["fq"].next_offset
                     >= sealed_tail),
            what="mirror catch-up")
        # the mirror's content is byte-for-byte the shipped prefix
        b_conn = await AMQPClient.connect("127.0.0.1", b_srv.bound_port)
        b_ch = await b_conn.channel()
        await b_ch.basic_qos(prefetch_count=64)
        got = await collect(b_ch, "fq", sealed_tail - 1)
        assert [bytes(m.body).decode() for m in got] == \
            [f"f{i:06d}" for i in range(sealed_tail - 1)]
        metrics = a_srv.broker.metrics
        assert metrics.federation_segments_shipped >= 1
        assert metrics.federation_segment_bytes > 0
        assert b_srv.broker.metrics.federation_segments_applied >= 1
        assert any(ev == "link.up" for ev, _ in fed_a.events)
        await b_conn.close()
        await conn.close()
    finally:
        await stop_pair(a_srv, fed_a, b_srv, fed_b)


async def test_cursor_commits_mirror_to_remote():
    a_srv, fed_a, b_srv, fed_b = await start_pair()
    try:
        conn = await AMQPClient.connect("127.0.0.1", a_srv.bound_port)
        ch = await conn.channel()
        await ch.confirm_select()
        await ch.queue_declare("fq", durable=True, arguments=STREAM_SMALL)
        for i in range(20):
            ch.basic_publish(f"f{i:06d}".encode(), routing_key="fq",
                             properties=PERSISTENT)
        await ch.wait_unconfirmed_below(1, timeout=15)
        ch2 = await conn.channel()
        await ch2.basic_qos(prefetch_count=64)
        await collect(ch2, "fq", 10, tag="group-1")
        # stream offsets are 1-based: the 10th record lives at offset 10,
        # and the coalesced mirror write carries the max committed offset
        await eventually(
            lambda: ("fq" in b_srv.broker.vhosts["/"].queues
                     and b_srv.broker.vhosts["/"].queues["fq"]
                     .committed.get("group-1") == 10),
            what="cursor mirror")
        assert b_srv.broker.metrics.federation_cursors_mirrored >= 1
        # the shipper counts a cursor only once fed.cursor's reply is back,
        # which is after the mirror (checked above) has applied it
        await eventually(
            lambda: a_srv.broker.metrics.federation_cursors_shipped >= 1,
            what="cursor ship acknowledged")
        assert any(ev == "cursor.mirrored" for ev, _ in fed_b.events)
        await conn.close()
    finally:
        await stop_pair(a_srv, fed_a, b_srv, fed_b)


# ---------------------------------------------------------------------------
# receiver-side ship protocol: duplicate / gap / CRC
# ---------------------------------------------------------------------------


async def test_ship_duplicate_acks_idempotently_and_gap_resyncs():
    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await b_srv.start()
    fed_b = FederationService(b_srv.broker, node_name="b", port=0)
    await fed_b.start()
    try:
        blob = pack_records(_records(1, 3))
        reply = await fed_b._h_ship(_ship_payload("/", "mq", 1, 3, blob))
        assert int.from_bytes(reply[0], "big") == 4
        # duplicate: same segment again acks with the mirror's position
        # instead of failing, so a shipper that lost our ack fast-forwards
        reply = await fed_b._h_ship(_ship_payload("/", "mq", 1, 3, blob))
        assert int.from_bytes(reply[0], "big") == 4
        assert b_srv.broker.metrics.federation_duplicate_segments == 1
        assert b_srv.broker.vhosts["/"].queues["mq"].next_offset == 4
        # gap: a segment past the mirror's next offset answers the resync
        # hint (the shipper parses "gap: <next>" off the error reply)
        far = pack_records(_records(10, 12))
        with pytest.raises(RpcError) as exc:
            await fed_b._h_ship(_ship_payload("/", "mq", 10, 12, far))
        assert exc.value.code == "gap" and exc.value.message == "4"
        assert _parse_gap(RpcError("remote", "gap: 4")) == 4
        assert _parse_gap(RpcError("remote", "boom")) is None
    finally:
        await fed_b.stop()
        await b_srv.stop()


async def test_ship_crc_mismatch_rejected():
    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await b_srv.start()
    fed_b = FederationService(b_srv.broker, node_name="b", port=0)
    await fed_b.start()
    try:
        blob = pack_records(_records(1, 2))
        with pytest.raises(RpcError) as exc:
            await fed_b._h_ship(
                _ship_payload("/", "mq", 1, 2, blob, crc=0xDEADBEEF))
        assert exc.value.code == "crc"
        assert b_srv.broker.metrics.federation_crc_failures == 1
        # nothing applied: the mirror still expects offset 1
        assert b_srv.broker.vhosts["/"].queues["mq"].next_offset == 1
    finally:
        await fed_b.stop()
        await b_srv.stop()


async def test_ship_rejects_bad_range_claims():
    """CRC only guards transport corruption: a shipper claiming a range
    its blob doesn't cover must be refused before the splice, or the
    mirror's offset space corrupts permanently."""
    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await b_srv.start()
    fed_b = FederationService(b_srv.broker, node_name="b", port=0)
    await fed_b.start()
    try:
        # inverted range: last < base
        blob = pack_records(_records(1, 2))
        with pytest.raises(RpcError) as exc:
            await fed_b._h_ship(_ship_payload("/", "mq", 5, 1, blob))
        assert exc.value.code == "bad-range"
        # records outside the claimed range: blob holds offsets 1..5 but
        # the header claims only 1..2 (would advance next_offset past
        # records the mirror never stored)
        wide = pack_records(_records(1, 5))
        with pytest.raises(RpcError) as exc:
            await fed_b._h_ship(_ship_payload("/", "mq", 1, 2, wide))
        assert exc.value.code == "bad-range"
        assert b_srv.broker.metrics.federation_invalid_segments == 2
        # nothing spliced: the mirror still expects offset 1, and a
        # well-formed ship (sparse is fine — compaction holes are legal)
        # goes through afterwards
        sparse = pack_records([r for r in _records(1, 4) if r.offset != 2])
        reply = await fed_b._h_ship(_ship_payload("/", "mq", 1, 4, sparse))
        assert int.from_bytes(reply[0], "big") == 5
    finally:
        await fed_b.stop()
        await b_srv.stop()


async def test_auth_token_gates_every_handler():
    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await b_srv.start()
    fed_b = FederationService(b_srv.broker, node_name="b", port=0,
                              auth_token="sesame")
    await fed_b.start()
    try:
        with pytest.raises(RpcError) as exc:
            await fed_b._h_hello({"link": "x", "node": "a"})
        assert exc.value.code == "auth"
        with pytest.raises(RpcError):
            await fed_b._h_resume({"vhost": "/", "queue": "mq",
                                   "token": "wrong"})
        blob = pack_records(_records(1, 2))
        with pytest.raises(RpcError) as exc:
            await fed_b._h_ship(_ship_payload("/", "mq", 1, 2, blob))
        assert exc.value.code == "auth"
        body = b"x"
        header = BasicProperties().encode_header(len(body))
        with pytest.raises(RpcError):
            await fed_b._h_tx(_tx_payload(
                "l", "e", 1, [("", "q", header, body)], token="wrong"))
        with pytest.raises(RpcError):
            await fed_b._h_publish(_pub_payload(
                "l", "e", 1, "", "q", header, body))
        assert b_srv.broker.metrics.federation_auth_failures == 5
        # nothing auto-declared on refused calls
        assert "mq" not in b_srv.broker.vhosts["/"].queues
        # the right token passes
        reply = await fed_b._h_ship(
            _ship_payload("/", "mq", 1, 2, blob, token="sesame"))
        assert int.from_bytes(reply[0], "big") == 3
    finally:
        await fed_b.stop()
        await b_srv.stop()


async def test_authed_link_ships_end_to_end():
    """A link configured with the remote's token comes up and ships;
    the token rides fed.hello, the cursor mirror and the data plane."""
    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await b_srv.start()
    fed_b = FederationService(b_srv.broker, node_name="cluster-b", port=0,
                              auth_token="sesame")
    await fed_b.start()
    a_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await a_srv.start()
    fed_a = FederationService(
        a_srv.broker, node_name="cluster-a", port=0,
        retry_s=0.05, idle_s=0.02,
        links=[{"name": "to-b", "host": "127.0.0.1", "port": fed_b.port,
                "queues": ["fq"], "token": "sesame"}])
    await fed_a.start()
    try:
        await eventually(lambda: fed_a.links[0].state == "up",
                         what="authed link up")
        # and a wrong token never comes up (refused at fed.hello)
        fed_bad = FederationService(
            a_srv.broker, node_name="cluster-bad", port=0,
            retry_s=0.05, idle_s=0.02,
            links=[{"name": "to-b", "host": "127.0.0.1",
                    "port": fed_b.port, "queues": ["fq"],
                    "token": "wrong"}])
        await fed_bad.start()
        bad = fed_bad.links[0]
        await eventually(
            lambda: bad.last_error is not None and "auth" in bad.last_error,
            what="bad-token link refused")
        assert bad.state == "down"
        await fed_bad.stop()
    finally:
        await fed_a.stop()
        await a_srv.stop()
        await fed_b.stop()
        await b_srv.stop()


async def test_outbox_sheds_publishes_before_tx_batches(monkeypatch):
    """At the outbox bound, single DLX forwards are shed before whole
    committed Tx batches, and drops are counted per kind."""
    from chanamq_tpu.federation import link as link_module

    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await b_srv.start()
    fed = FederationService(
        b_srv.broker, node_name="b", port=0,
        links=[{"name": "l", "host": "127.0.0.1", "port": 1,
                "queues": []}])
    link = fed.links[0]  # never started: staging is pure local state
    try:
        monkeypatch.setattr(link_module, "_OUTBOX_MAX", 4)
        header, body = b"h", b"b"
        link.queue_tx([("ex", "rk", header, body)])
        link.queue_publish("ex", "rk", header, body)
        link.queue_tx([("ex", "rk", header, body)])
        link.queue_publish("ex", "rk", header, body)
        # outbox full at 4: the next stage sheds the OLDEST PUBLISH,
        # not the older tx batch at the head
        link.queue_tx([("ex", "rk", header, body)])
        kinds = [item[0] for item in link.outbox]
        assert kinds == ["tx", "tx", "publish", "tx"]
        metrics = b_srv.broker.metrics
        assert metrics.federation_outbox_dropped_publish == 1
        assert metrics.federation_outbox_dropped_tx == 0
        assert metrics.federation_outbox_dropped == 1
        # further pressure sheds the remaining publish first; once the
        # outbox is all tx, the oldest batch goes — counted as such
        link.queue_tx([("ex", "rk", header, body)])
        link.queue_tx([("ex", "rk", header, body)])
        assert [item[0] for item in link.outbox] == ["tx"] * 4
        assert metrics.federation_outbox_dropped_publish == 2
        assert metrics.federation_outbox_dropped_tx == 1
        assert metrics.federation_outbox_dropped == 3
    finally:
        await b_srv.stop()


async def test_resume_rejects_non_stream_queue():
    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    await b_srv.start()
    fed_b = FederationService(b_srv.broker, node_name="b", port=0)
    await fed_b.start()
    try:
        await b_srv.broker.declare_queue("/", "classic", durable=False)
        with pytest.raises(RpcError) as exc:
            await fed_b._h_resume({"vhost": "/", "queue": "classic"})
        assert exc.value.code == "bad-type"
    finally:
        await fed_b.stop()
        await b_srv.stop()


# ---------------------------------------------------------------------------
# DLX forwarding + federated Tx
# ---------------------------------------------------------------------------


async def test_dead_letter_forwards_to_federated_exchange():
    a_srv, fed_a, b_srv, fed_b = await start_pair(
        queues=(), exchanges=("fed_dlx",))
    try:
        # remote cluster owns the DLX target
        b_conn = await AMQPClient.connect("127.0.0.1", b_srv.bound_port)
        b_ch = await b_conn.channel()
        await b_ch.exchange_declare("fed_dlx", "fanout")
        await b_ch.queue_declare("dead")
        await b_ch.queue_bind("dead", "fed_dlx", "")
        # local cluster dead-letters into it via maxlen overflow; the
        # exchange exists only remotely, so the local copy drops NOT_FOUND
        conn = await AMQPClient.connect("127.0.0.1", a_srv.bound_port)
        ch = await conn.channel()
        await ch.queue_declare("src", arguments={
            "x-max-length": 1, "x-dead-letter-exchange": "fed_dlx"})
        ch.basic_publish(b"first", routing_key="src")
        ch.basic_publish(b"second", routing_key="src")
        await eventually(
            lambda: a_srv.broker.metrics.federation_dlx_forwarded >= 1,
            what="dlx staged")
        msg = None

        async def fetch():
            nonlocal msg
            msg = await b_ch.basic_get("dead", no_ack=True)
            return msg is not None

        deadline = asyncio.get_event_loop().time() + 10
        while msg is None:
            assert asyncio.get_event_loop().time() < deadline, \
                "forwarded dead-letter never arrived"
            await fetch()
            if msg is None:
                await asyncio.sleep(0.05)
        assert bytes(msg.body) == b"first"
        # x-death history survives the wire (raw header forwarded)
        death = msg.properties.headers["x-death"][0]
        assert death["queue"] == "src" and death["reason"] == "maxlen"
        await conn.close()
        await b_conn.close()
    finally:
        await stop_pair(a_srv, fed_a, b_srv, fed_b)


async def test_tx_commit_ships_one_batch():
    a_srv, fed_a, b_srv, fed_b = await start_pair(
        queues=(), exchanges=("fed_ex",))
    try:
        b_conn = await AMQPClient.connect("127.0.0.1", b_srv.bound_port)
        b_ch = await b_conn.channel()
        await b_ch.exchange_declare("fed_ex", "fanout")
        await b_ch.queue_declare("txq")
        await b_ch.queue_bind("txq", "fed_ex", "")
        conn = await AMQPClient.connect("127.0.0.1", a_srv.bound_port)
        ch = await conn.channel()
        await ch.exchange_declare("fed_ex", "fanout")
        await ch.tx_select()
        for i in range(3):
            ch.basic_publish(f"tx{i}".encode(), exchange="fed_ex",
                             routing_key="")
        await asyncio.sleep(0.1)
        # uncommitted publishes must not cross the link
        assert a_srv.broker.metrics.federation_tx_batches == 0
        await ch.tx_commit()
        assert a_srv.broker.metrics.federation_tx_batches == 1
        assert a_srv.broker.metrics.federation_tx_publishes == 3
        await eventually(
            lambda: b_srv.broker.metrics.federation_tx_applied == 1,
            what="tx batch applied")
        got = []
        while len(got) < 3:
            msg = await b_ch.basic_get("txq", no_ack=True)
            if msg is None:
                await asyncio.sleep(0.02)
                continue
            got.append(bytes(msg.body).decode())
        assert got == ["tx0", "tx1", "tx2"]
        await conn.close()
        await b_conn.close()
    finally:
        await stop_pair(a_srv, fed_a, b_srv, fed_b)


async def test_tx_batch_replay_is_idempotent():
    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await b_srv.start()
    fed_b = FederationService(b_srv.broker, node_name="b", port=0)
    await fed_b.start()
    try:
        conn = await AMQPClient.connect("127.0.0.1", b_srv.bound_port)
        ch = await conn.channel()
        await ch.queue_declare("txq")
        body = b"payload"
        header = BasicProperties(delivery_mode=2).encode_header(len(body))
        publishes = [("", "txq", header, body)] * 2
        payload = _tx_payload("from-a", "boot-1", 1, publishes)
        reply = await fed_b._h_tx(payload)
        assert int.from_bytes(reply[0], "big") == 1
        # a retried batch (lost reply) acks without re-publishing
        reply = await fed_b._h_tx(payload)
        assert int.from_bytes(reply[0], "big") == 1
        assert b_srv.broker.metrics.federation_tx_applied == 1
        assert b_srv.broker.metrics.federation_duplicate_forwards == 1
        queue = b_srv.broker.get_queue("/", "txq")
        assert queue.message_count == 2
        await conn.close()
    finally:
        await fed_b.stop()
        await b_srv.stop()


async def test_tx_dedup_scoped_by_shipper_epoch():
    """A restarted shipper's sequences restart at 1 under a fresh epoch;
    the receiver must apply them instead of swallowing everything below
    the previous incarnation's high-water mark."""
    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await b_srv.start()
    fed_b = FederationService(b_srv.broker, node_name="b", port=0)
    await fed_b.start()
    try:
        conn = await AMQPClient.connect("127.0.0.1", b_srv.bound_port)
        ch = await conn.channel()
        await ch.queue_declare("txq")
        body = b"payload"
        header = BasicProperties(delivery_mode=2).encode_header(len(body))
        publishes = [("", "txq", header, body)]
        # first incarnation ships seqs 1..3
        for seq in (1, 2, 3):
            await fed_b._h_tx(
                _tx_payload("from-a", "boot-1", seq, publishes))
        # shipper restarts: new epoch, seq restarts at 1 — must APPLY,
        # not ack as a duplicate of boot-1's seq 1
        reply = await fed_b._h_tx(
            _tx_payload("from-a", "boot-2", 1, publishes))
        assert int.from_bytes(reply[0], "big") == 1
        assert b_srv.broker.metrics.federation_tx_applied == 4
        queue = b_srv.broker.get_queue("/", "txq")
        assert queue.message_count == 4
        # within the new epoch, retries still dedup
        await fed_b._h_tx(_tx_payload("from-a", "boot-2", 1, publishes))
        assert queue.message_count == 4
        await conn.close()
    finally:
        await fed_b.stop()
        await b_srv.stop()


async def test_forwarded_publish_replay_is_idempotent():
    """FED_PUBLISH carries the same per-link (epoch, seq) identity as
    Tx batches: a retry after a lost ack must not duplicate the DLX
    message, and a fresh epoch opens a new dedup scope."""
    b_srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                         store=MemoryStore())
    await b_srv.start()
    fed_b = FederationService(b_srv.broker, node_name="b", port=0)
    await fed_b.start()
    try:
        conn = await AMQPClient.connect("127.0.0.1", b_srv.bound_port)
        ch = await conn.channel()
        await ch.queue_declare("dead")
        body = b"corpse"
        header = BasicProperties(delivery_mode=2).encode_header(len(body))
        payload = _pub_payload("from-a", "boot-1", 1, "", "dead",
                               header, body)
        await fed_b._h_publish(payload)
        await fed_b._h_publish(payload)  # retry after a lost ack
        queue = b_srv.broker.get_queue("/", "dead")
        assert queue.message_count == 1
        assert b_srv.broker.metrics.federation_duplicate_forwards == 1
        # new shipper incarnation: seq 1 again, but a different message
        await fed_b._h_publish(_pub_payload(
            "from-a", "boot-2", 1, "", "dead", header, body))
        assert queue.message_count == 2
        await conn.close()
    finally:
        await fed_b.stop()
        await b_srv.stop()


# ---------------------------------------------------------------------------
# observability: admin endpoint, Prometheus gauges, SLI samples
# ---------------------------------------------------------------------------


async def http_req(port, path, method="GET", body=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode() if body is not None else b""
    writer.write(
        f"{method} {path} HTTP/1.1\r\nHost: localhost\r\n"
        f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload)
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(262144), 5)
    writer.close()
    head, _, resp = raw.partition(b"\r\n\r\n")
    status = int(head.split()[1])
    return status, resp


async def test_admin_federation_endpoint_and_prometheus():
    a_srv, fed_a, b_srv, fed_b = await start_pair()
    try:
        admin = AdminServer(a_srv.broker, port=0)
        await admin.start()
        await eventually(lambda: fed_a.links[0].state == "up",
                         what="link up")
        status, resp = await http_req(admin.bound_port, "/admin/federation")
        assert status == 200
        stats = json.loads(resp)
        assert stats["node"] == "cluster-a"
        assert stats["links"][0]["name"] == "to-b"
        assert stats["links"][0]["state"] == "up"
        assert any(e["event"] == "link.up" for e in stats["events"])
        status, resp = await http_req(
            admin.bound_port, "/admin/federation", "POST",
            body={"action": "wake", "link": "to-b"})
        assert status == 200 and json.loads(resp)["woke"] == ["to-b"]
        status, _ = await http_req(
            admin.bound_port, "/admin/federation", "POST",
            body={"action": "wake", "link": "nope"})
        assert status == 404
        status, _ = await http_req(
            admin.bound_port, "/admin/federation", "POST",
            body={"action": "explode"})
        assert status == 400
        status, resp = await http_req(admin.bound_port, "/metrics")
        text = resp.decode()
        assert 'chanamq_federation_link_lag{link="to-b"}' in text
        assert 'chanamq_federation_link_up{link="to-b"} 1' in text
        await admin.stop()
    finally:
        await stop_pair(a_srv, fed_a, b_srv, fed_b)


async def test_admin_federation_409_when_disabled():
    srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    await srv.start()
    admin = AdminServer(srv.broker, port=0)
    await admin.start()
    try:
        status, _ = await http_req(admin.bound_port, "/admin/federation")
        assert status == 409
    finally:
        await admin.stop()
        await srv.stop()


async def test_sli_sampler_reports_federation_lag():
    from chanamq_tpu.slo import SLISampler

    a_srv, fed_a, b_srv, fed_b = await start_pair()
    try:
        await eventually(lambda: fed_a.links[0].state == "up",
                         what="link up")
        sampler = SLISampler(a_srv.broker, federation_lag_records=1000)
        samples = sampler.sample(True)
        assert samples["federation-lag@to-b"] == (1.0, 0.0)
        assert samples["federation-lag"] == (1.0, 0.0)
        # a down link burns the budget even with zero record lag
        fed_a.links[0].state = "down"
        samples = sampler.sample(True)
        assert samples["federation-lag@to-b"] == (0.0, 1.0)
        assert samples["federation-lag"] == (0.0, 1.0)
    finally:
        await stop_pair(a_srv, fed_a, b_srv, fed_b)
