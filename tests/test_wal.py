"""WAL storage engine tests: crash recovery (torn tail, CRC corruption,
replay-over-checkpoint idempotence), the confirm-at-commit-boundary
ordering contract, frame codec semantics, key compaction, and tiered
sealed-segment offload/rehydration (chanamq_tpu/wal/)."""

import asyncio
import os
import struct
import threading

import pytest

from chanamq_tpu.store.api import StoredMessage, StoredQueue
from chanamq_tpu.store.sqlite import SqliteStore
from chanamq_tpu.wal import CHECKPOINT_KEY, WalStore
from chanamq_tpu.wal.codec import (
    OP_INDEX, decode_payload, encode_record, scan_frames,
)
from chanamq_tpu.wal.segment import list_segments
from chanamq_tpu.wal.tier import StreamTier, compact_records

pytestmark = pytest.mark.asyncio

_HDR = struct.Struct("<II")


@pytest.fixture
def db_path(tmp_path):
    return str(tmp_path / "store.db")


def make_store(db_path, **kwargs):
    kwargs.setdefault("flush_ms", 1.0)
    # far future by default: tests that want a checkpoint trigger one by
    # hand so the segment lifecycle is deterministic
    kwargs.setdefault("checkpoint_ms", 3_600_000.0)
    return WalStore(SqliteStore(db_path), **kwargs)


def msg(i: int) -> StoredMessage:
    return StoredMessage(id=i, properties_raw=b"\x01", body=b"body%d" % i,
                         exchange="ex", routing_key="rk", refer_count=1)


async def crash(store: WalStore) -> None:
    """Simulated SIGKILL: abandon loops and buffers, no close(), no final
    commit — whatever reached the segment files is all recovery gets."""
    store._commit_task.cancel()
    store._checkpoint_task.cancel()
    store._inner._closed = True
    store._executor.shutdown(wait=True)
    store._inner._executor.shutdown(wait=False)


def wipe_index(db_path: str) -> None:
    """Erase the inner index the way a lost SQLite batch would: recovery
    must rebuild these rows from the WAL alone."""
    import sqlite3
    db = sqlite3.connect(db_path)
    db.execute("DELETE FROM msgs")
    db.commit()
    db.close()


def frame_offsets(path: str) -> list[int]:
    """Byte offset of every frame in a segment file."""
    with open(path, "rb") as f:
        data = f.read()
    offsets, pos = [], 0
    while pos + _HDR.size <= len(data):
        length, _crc = _HDR.unpack_from(data, pos)
        offsets.append(pos)
        pos += _HDR.size + length
    return offsets


def fail_first_append(store: WalStore) -> None:
    """The next commit's write raises; the ones after it go through."""
    orig_append = store._writer.append
    fail_once = [True]

    def flaky_append(data, last_lsn):
        if fail_once[0]:
            fail_once[0] = False
            raise OSError("disk on fire")
        orig_append(data, last_lsn)

    store._writer.append = flaky_append


# ---------------------------------------------------------------------------
# frame codec
# ---------------------------------------------------------------------------


async def test_codec_roundtrip_and_scan_states():
    rec = encode_record(7, OP_INDEX["insert_queue_msg"],
                        ("/", "q", 1, 100, 10, None))
    payloads, good, status = scan_frames(rec + rec)
    assert status == "ok" and good == len(rec) * 2
    assert [decode_payload(p)[0] for p in payloads] == [7, 7]
    lsn, op, args = decode_payload(payloads[0])
    assert op == OP_INDEX["insert_queue_msg"]
    assert args == ("/", "q", 1, 100, 10, None)

    # torn: the final frame is cut short -> droppable tail
    payloads, good, status = scan_frames(rec + rec[:-3])
    assert status == "torn" and good == len(rec) and len(payloads) == 1

    # corrupt: a damaged frame with intact data behind it -> stop point
    bad = bytearray(rec + rec)
    bad[_HDR.size + 2] ^= 0xFF
    payloads, good, status = scan_frames(bytes(bad))
    assert status == "corrupt" and payloads == []


async def test_codec_stored_dataclass_values():
    m = msg(3)
    rec = encode_record(1, OP_INDEX["insert_message"], (m,))
    _lsn, _op, (back,) = decode_payload(scan_frames(rec)[0][0])
    assert back == m
    q = StoredQueue(vhost="/", name="q", durable=True,
                    arguments={"x-stream-compact": True})
    rec = encode_record(2, OP_INDEX["insert_queue_meta"], (q,))
    _lsn, _op, (back,) = decode_payload(scan_frames(rec)[0][0])
    assert back == q


# ---------------------------------------------------------------------------
# crash recovery
# ---------------------------------------------------------------------------


async def test_torn_tail_truncated_on_recovery(db_path):
    s = make_store(db_path)
    await s.open()
    lo = s.mark()
    for i in range(10):
        s.insert_message_nowait(msg(i))
    await s.flush([(lo, s.mark())])
    await crash(s)

    # the crash tore the last frame mid-write; the index lost its batch
    segs = list_segments(s.dir)
    assert len(segs) == 1
    with open(segs[0][1], "r+b") as f:
        f.truncate(f.seek(0, os.SEEK_END) - 3)
    wipe_index(db_path)

    s2 = make_store(db_path)
    await s2.open()
    assert s2.recovered_records == 9
    assert s2.metrics.wal_recover_torn == 1
    got = await s2.select_messages(list(range(10)))
    assert sorted(got) == list(range(9))  # the torn record is gone
    await s2.close()


async def test_crc_corruption_stops_replay_and_quarantines(db_path):
    s = make_store(db_path)
    await s.open()
    lo = s.mark()
    for i in range(20):
        s.insert_message_nowait(msg(i))
    await s.flush([(lo, s.mark())])
    await crash(s)

    segs = list_segments(s.dir)
    path = segs[0][1]
    offsets = frame_offsets(path)
    assert len(offsets) == 20
    # flip one payload byte of frame 10: replay must stop THERE — records
    # behind a damaged one are ordered after it, so applying them would
    # reorder history
    with open(path, "r+b") as f:
        f.seek(offsets[10] + _HDR.size + 1)
        byte = f.read(1)
        f.seek(offsets[10] + _HDR.size + 1)
        f.write(bytes([byte[0] ^ 0xFF]))
    wipe_index(db_path)

    s2 = make_store(db_path)
    await s2.open()
    assert s2.recovered_records == 10
    assert s2.metrics.wal_recover_corrupt >= 1
    got = await s2.select_messages(list(range(20)))
    assert sorted(got) == list(range(10))
    # the unreplayable segment is kept aside as evidence, not deleted
    assert any(name.endswith(".corrupt") for name in os.listdir(s2.dir))
    await s2.close()


async def test_replay_over_checkpoint_is_idempotent(db_path):
    s = make_store(db_path, checkpoint_ms=50.0)
    await s.open()
    lo = s.mark()
    for i in range(100):
        s.insert_message_nowait(msg(i))
        s.insert_queue_msg_nowait("/", "q", i + 1, i, 5, None)
    await s.flush([(lo, s.mark())])
    await s.insert_queue_meta(StoredQueue(vhost="/", name="q"))
    wid = await s.allocate_worker_id()
    for _ in range(100):
        await asyncio.sleep(0.05)
        ck = await s._inner.get_kv(CHECKPOINT_KEY)
        if ck is not None and int(ck) >= s.mark():
            break
    assert int(ck) >= s.mark()
    # tail written after the checkpoint: the only part recovery may replay
    lo = s.mark()
    for i in range(100, 150):
        s.insert_message_nowait(msg(i))
    await s.flush([(lo, s.mark())])
    await crash(s)

    s2 = make_store(db_path)
    await s2.open()
    # replay covered exactly the post-checkpoint tail; the checkpointed
    # prefix was NOT re-applied (it no longer exists in any segment)...
    assert s2.recovered_records == 50
    # ...yet replaying over rows the write-through already landed is safe:
    # every journaled op is INSERT OR REPLACE / DELETE shaped
    got = await s2.select_messages(list(range(150)))
    assert len(got) == 150
    for i in range(150):
        assert got[i].body == b"body%d" % i
    q = await s2.select_queue("/", "q")
    assert q is not None and len(q.msgs) == 100
    # the journaled worker-id floor survives the crash: no id reuse
    assert await s2.allocate_worker_id() > wid

    # recovery itself re-checkpointed: a second boot replays only what
    # s2 appended after it (the one journaled worker-id floor), never
    # the 252-record history it already folded into the index
    await crash(s2)
    s3 = make_store(db_path)
    await s3.open()
    assert s3.recovered_records == 1
    await s3.close()
    # clean shutdown checkpoints everything: the WAL dir holds no segments
    assert list_segments(s3.dir) == []


async def test_clean_restart_replays_nothing(db_path):
    s = make_store(db_path)
    await s.open()
    await s.insert_queue_meta(StoredQueue(vhost="/", name="q"))
    await s.close()
    s2 = make_store(db_path)
    await s2.open()
    assert s2.recovered_records == 0
    assert (await s2.select_queue("/", "q")) is not None
    await s2.close()


# ---------------------------------------------------------------------------
# confirm-at-commit-boundary ordering
# ---------------------------------------------------------------------------


async def test_confirm_barrier_waits_for_fsync(db_path):
    """A durability barrier (what releases a publisher confirm) must not
    resolve before the group commit's write+fsync completes — stall the
    writer's sync and the barrier must stall with it."""
    s = make_store(db_path)
    await s.open()
    gate = threading.Event()
    synced = threading.Event()
    orig_sync = s._writer.sync

    def gated_sync(fsync):
        assert gate.wait(10), "test gate never released"
        orig_sync(fsync)
        synced.set()

    s._writer.sync = gated_sync
    lo = s.mark()
    s.insert_message_nowait(msg(1))
    fut = asyncio.ensure_future(s.flush([(lo, s.mark())]))
    await asyncio.sleep(0.2)
    assert not fut.done(), "confirm released before the fsync happened"
    gate.set()
    await asyncio.wait_for(fut, 10)
    assert synced.is_set()
    s._writer.sync = orig_sync
    await s.close()


async def test_failed_commit_raises_only_overlapping_barriers(db_path):
    """Commit-failure attribution: the barrier whose LSN window rode the
    failed batch raises; a later barrier over a healthy batch succeeds."""
    s = make_store(db_path)
    await s.open()
    fail_first_append(s)
    lo = s.mark()
    s.insert_message_nowait(msg(1))
    with pytest.raises(RuntimeError):
        await s.flush([(lo, s.mark())])
    assert s.metrics.wal_commit_errors == 1
    assert s.error_count >= 1
    lo = s.mark()
    s.insert_message_nowait(msg(2))
    await s.flush([(lo, s.mark())])  # healthy batch: must not raise
    await s.close()


async def test_group_commit_batches_many_appends(db_path):
    """The whole point: hundreds of appends from interleaved 'channels'
    amortize into a handful of fsyncs, not one per op."""
    s = make_store(db_path, flush_ms=5.0)
    await s.open()
    lo = s.mark()
    for i in range(500):
        s.insert_message_nowait(msg(i))
        s.insert_queue_msg_nowait("/", "q", i + 1, i, 5, None)
    await s.flush([(lo, s.mark())])
    # each blob+row pair fuses into one insert_published record
    assert s.metrics.wal_appends == 500
    assert s.metrics.wal_fsyncs <= 3
    await s.close()


async def test_fused_publish_record_recovers_blob_and_row(db_path):
    """insert_message_nowait + insert_queue_msg_nowait for the same id
    journal as ONE insert_published record, and recovery expands it back
    into both index writes."""
    s = make_store(db_path)
    await s.open()
    await s.insert_queue_meta(StoredQueue(vhost="/", name="q"))
    lo = s.mark()
    for i in range(20):
        s.insert_message_nowait(msg(i))
        s.insert_queue_msg_nowait("/", "q", i + 1, i, 5, None)
    await s.flush([(lo, s.mark())])
    assert s.metrics.wal_appends == 21  # queue meta + 20 fused publishes
    await crash(s)
    wipe_index(db_path)

    s2 = make_store(db_path)
    await s2.open()
    got = await s2.select_messages(list(range(20)))
    assert sorted(got) == list(range(20))
    q = await s2.select_queue("/", "q")
    assert q is not None and len(q.msgs) == 20
    await s2.close()


def test_coalesce_splits_half_dead_fused_record():
    """A fused publish whose blob OR row (not both) dies inside the batch
    forwards only the living half to the index."""
    from chanamq_tpu.wal.engine import _coalesce_ops

    pub = ("insert_published", (msg(1), "/", "q", 7, 5, None))
    # blob deleted -> only the queue-log row survives
    net, elided = _coalesce_ops([pub, ("delete_messages", ([1],))])
    assert net == [("insert_queue_msg", ("/", "q", 7, 1, 5, None))]
    # row consumed past the watermark -> only the blob survives
    net, elided = _coalesce_ops(
        [pub, ("update_queue_last_consumed", ("/", "q", 7))])
    assert [n for n, _ in net] == ["insert_message",
                                   "update_queue_last_consumed"]
    # both halves dead -> the record never reaches SQLite
    net, elided = _coalesce_ops(
        [pub, ("update_queue_last_consumed", ("/", "q", 7)),
         ("delete_messages", ([1],))])
    assert [n for n, _ in net] == ["update_queue_last_consumed"]


async def test_error_count_aggregates_inner(db_path):
    s = make_store(db_path)
    await s.open()
    assert s.error_count == 0
    s._inner.error_count += 1  # a lost background index write
    assert s.error_count == 1  # readiness sees one number
    await s.close()


# ---------------------------------------------------------------------------
# key compaction + tiered offload
# ---------------------------------------------------------------------------


def _stream_blob(base: int, keys: list) -> tuple:
    import chanamq_tpu.broker  # noqa: F401  (streams import needs broker first)
    from chanamq_tpu.streams.segment import StreamRecord, pack_records
    records = [
        StreamRecord(base + i, 1000 + i, "ex", key, b"\x01", b"v%d" % i)
        for i, key in enumerate(keys)
    ]
    return records, pack_records(records)


async def test_compact_records_keeps_newest_per_key():
    records, _blob = _stream_blob(1, ["a", "b", "a", "c", "b"])
    seen: set = set()
    kept, dropped = compact_records(records, seen)
    assert dropped == 2
    assert [(r.offset, r.routing_key) for r in kept] == [
        (3, "a"), (4, "c"), (5, "b")]
    # an older segment compacts against the keys this one established
    older, _ = _stream_blob(0, ["c"])
    kept2, dropped2 = compact_records(older, seen)
    assert kept2 == [] and dropped2 == 1


async def test_wal_compacts_declared_stream_queues(db_path):
    from chanamq_tpu.streams.segment import unpack_records
    s = make_store(db_path, compact_streams=True)
    await s.open()
    await s.insert_queue_meta(StoredQueue(
        vhost="/", name="sq", arguments={
            "x-queue-type": "stream", "x-stream-compact": True}))
    # two sealed segments with overlapping keys: k0 repeats in the newer
    _, blob1 = _stream_blob(1, ["k0", "k1", "k2"])
    _, blob2 = _stream_blob(4, ["k0", "k3"])
    await s.insert_stream_segment("/", "sq", 1, 3, 0, 0, len(blob1), blob1)
    await s.insert_stream_segment("/", "sq", 4, 5, 0, 0, len(blob2), blob2)
    await s._maintain_streams()
    assert s.metrics.wal_compactions == 1
    assert s.metrics.wal_compacted_records == 1
    old = await s.select_stream_segment("/", "sq", 1)
    offsets = [r.offset for r in unpack_records(old)]
    assert offsets == [2, 3]  # k0@1 compacted away; newer seg untouched
    new = await s.select_stream_segment("/", "sq", 4)
    assert [r.offset for r in unpack_records(new)] == [4, 5]
    # sparse-safe decode: holes stay addressable by offset
    from chanamq_tpu.streams.segment import unpack_records_indexed
    slots = unpack_records_indexed(old, 1, 3)
    assert slots[0] is None and slots[1].offset == 2
    await s.close()


async def test_tier_offload_and_rehydrate(db_path):
    s = make_store(db_path, tier_keep_segments=1)
    await s.open()
    await s.insert_queue_meta(StoredQueue(
        vhost="/", name="sq", arguments={"x-queue-type": "stream"}))
    _, blob1 = _stream_blob(1, ["a", "b"])
    _, blob2 = _stream_blob(3, ["c", "d"])
    await s.insert_stream_segment("/", "sq", 1, 2, 0, 0, len(blob1), blob1)
    await s.insert_stream_segment("/", "sq", 3, 4, 0, 0, len(blob2), blob2)
    await s._maintain_streams()
    assert s.metrics.wal_tier_offloads == 1
    # the cold blob left SQLite but the index row remains; reads rehydrate
    assert await s._inner.select_stream_segment("/", "sq", 1) is None
    metas = await s.stream_segment_metas("/", "sq")
    assert [m[0] for m in metas] == [1, 3]
    back = await s.select_stream_segment("/", "sq", 1)
    assert back == blob1
    assert s.metrics.wal_tier_rehydrations == 1
    # retention drop cleans the tier file too
    await s.delete_stream_segments("/", "sq", [1])
    assert not s.tier.has("/", "sq", 1)
    assert await s.select_stream_segment("/", "sq", 1) is None
    await s.close()


async def test_tier_file_crc_damage_reads_as_absent(tmp_path):
    tier = StreamTier(str(tmp_path / "tier"))
    tier.write("/", "q", 5, b"payload-bytes")
    assert tier.read("/", "q", 5) == b"payload-bytes"
    path = tier._path("/", "q", 5)
    with open(path, "r+b") as f:
        f.write(b"\xff")
    assert tier.read("/", "q", 5) is None  # damaged, never silent garbage


async def test_broker_restart_hydrates_tiered_segments_on_cursor_read(db_path):
    """Full recovery path for tiered offload: a broker seals stream
    segments, the maintenance pass tiers the cold ones out of SQLite
    (tier_keep_segments=1), the broker restarts on the same data dir, and
    a cursor read from offset "first" must deliver every record — the
    cold blobs hydrate transparently through select_stream_segment."""
    from chanamq_tpu.amqp.properties import BasicProperties
    from chanamq_tpu.broker.server import BrokerServer
    from chanamq_tpu.client import AMQPClient

    persistent = BasicProperties(delivery_mode=2)
    store = make_store(db_path, tier_keep_segments=1)
    srv = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0, store=store)
    await srv.start()
    conn = await AMQPClient.connect("127.0.0.1", srv.bound_port)
    ch = await conn.channel()
    await ch.confirm_select()
    await ch.queue_declare("tsq", durable=True, arguments={
        "x-queue-type": "stream", "x-stream-max-segment-size-bytes": 256})
    for i in range(30):
        ch.basic_publish(b"t%03d" % i, routing_key="tsq",
                         properties=persistent)
    await ch.wait_unconfirmed_below(1, timeout=15)
    queue = srv.broker.get_queue("/", "tsq")
    if queue._active:
        queue._seal_active()
    sealed = len(queue._seg_bases)
    assert sealed >= 3, "segment cap too large to exercise tiering"
    for _ in range(250):  # spills ride store_bg: wait for all to land
        if len(await store.stream_segment_metas("/", "tsq")) == sealed:
            break
        await asyncio.sleep(0.02)
    await store._maintain_streams()
    assert store.metrics.wal_tier_offloads >= sealed - 1
    await conn.close()
    await srv.stop()

    store2 = make_store(db_path, tier_keep_segments=1)
    srv2 = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                        store=store2)
    await srv2.start()
    queue2 = srv2.broker.get_queue("/", "tsq")
    assert queue2.next_offset == 31
    # recovery rebuilds the index cold: metadata only, no resident records
    assert all(seg.records is None for seg in queue2._segments)
    conn2 = await AMQPClient.connect("127.0.0.1", srv2.bound_port)
    ch2 = await conn2.channel()
    await ch2.basic_qos(prefetch_count=64)
    got: list = []
    done = asyncio.get_event_loop().create_future()

    def on_msg(msg):
        got.append(bytes(msg.body))
        ch2.basic_ack(msg.delivery_tag)
        if len(got) >= 30 and not done.done():
            done.set_result(None)

    tag = await ch2.basic_consume("tsq", on_msg,
                                  arguments={"x-stream-offset": "first"})
    await asyncio.wait_for(done, 15)
    await ch2.basic_cancel(tag)
    assert got == [b"t%03d" % i for i in range(30)]
    assert store2.metrics.wal_tier_rehydrations >= 1
    await conn2.close()
    await srv2.stop()


# ---------------------------------------------------------------------------
# the counters per message and queue (PR 35): wal_queue_msg_records,
# wal_queue_msgs_committed, wal_settle_rows against what the segment files hold
# ---------------------------------------------------------------------------

_ROW_OPS = (OP_INDEX["insert_queue_msg"], OP_INDEX["insert_published"])


def rows_on_disk(store: WalStore) -> int:
    """Message-and-queue rows a scan of the segment files finds, whichever
    record carries them: a fused publish, a plain row, an op of a tx_batch."""
    found = 0
    for _first, path in list_segments(store.dir):
        with open(path, "rb") as f:
            payloads, _good, status = scan_frames(f.read())
        assert status == "ok"
        for payload in payloads:
            _lsn, op, args = decode_payload(payload)
            if op == OP_INDEX["tx_batch"]:
                found += sum(1 for sub, _ in args[0] if sub in _ROW_OPS)
            elif op in _ROW_OPS:
                found += 1
    return found


def hand_rows(s: WalStore, path: str, first: int, awaited: bool) -> int:
    """Hand message-and-queue rows to the log down one write path; returns
    how many. `awaited`: the plain path may use insert_queue_msg's awaited
    form too (never inside a scope that will be aborted: its barrier would
    wait for a record that is never framed)."""
    rows = 0
    if path in ("fused", "tx_batch"):
        for i in range(first, first + 6):  # blob + first queue: one record
            s.insert_message_nowait(msg(i))
            s.insert_queue_msg_nowait("/", "q", i + 1, i, 5, None)
            rows += 1
    if path in ("plain", "tx_batch"):
        for i in range(first, first + 4):  # a further queue: a row alone
            s.insert_queue_msg_nowait("/", "q2", i + 1, i, 5, None)
            rows += 1
        if awaited:
            s._fire(s.insert_queue_msg("/", "q3", first + 1, first, 5, None))
            rows += 1
    return rows


@pytest.mark.parametrize("outcome", ["commit_ok", "commit_fails", "tx_aborted"])
@pytest.mark.parametrize("path", ["fused", "plain", "tx_batch"])
async def test_queue_msg_counters_match_the_segment_files(db_path, path,
                                                          outcome):
    s = make_store(db_path)
    await s.open()
    m = s.metrics
    if outcome == "commit_fails":
        fail_first_append(s)
    lo = s.mark()
    scoped = path == "tx_batch" or outcome == "tx_aborted"
    if scoped:
        s.tx_begin()
    handed = hand_rows(s, path, 0, awaited=outcome != "tx_aborted")
    if outcome == "tx_aborted":
        s.tx_abort()
        handed = 0
    elif scoped:
        s.tx_seal()
    assert m.wal_queue_msg_records == handed
    assert m.wal_queue_msgs_committed == 0  # nothing is durable yet
    hi = s.mark()
    if outcome == "commit_fails":
        with pytest.raises(RuntimeError):
            await s.flush([(lo, hi)])
        assert m.wal_commit_errors == 1
        assert m.wal_queue_msgs_committed == 0 and rows_on_disk(s) == 0
        # a later healthy batch commits its own rows and no others
        lo = s.mark()
        later = hand_rows(s, "fused", 100, awaited=False)
        await s.flush([(lo, s.mark())])
        assert m.wal_queue_msg_records == handed + later
        assert m.wal_queue_msgs_committed == later == rows_on_disk(s)
    else:
        await s.flush([(lo, hi)])
        assert m.wal_queue_msg_records == handed
        assert m.wal_queue_msgs_committed == handed == rows_on_disk(s)
    if path == "tx_batch" and outcome == "commit_ok":
        assert m.wal_tx_batches == 1 and m.wal_appends == 1  # one record
    # settle rows: ids a call, not calls; and no message-and-queue row
    records = m.wal_queue_msg_records
    lo = s.mark()
    s._fire(s.delete_queue_unacks("/", "q", [1, 2, 3]))
    s._fire(s.delete_queue_unacks("/", "q2", iter([4, 5, 6, 7])))
    await s.flush([(lo, s.mark())])
    assert m.wal_settle_rows == 7
    assert m.wal_queue_msg_records == records
    assert m.wal_commit_ns > 0 and m.wal_commits >= 1
    await s.close()
