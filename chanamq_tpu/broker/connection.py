"""Per-connection AMQP protocol engine.

Capability parity with the reference's FrameStage GraphStage
(chana-mq-server .../engine/FrameStage.scala:53-1297): protocol-header
handshake, SASL (PLAIN/EXTERNAL), tune negotiation, vhost open, channel
lifecycle, the full method dispatch (exchange/queue/basic/confirm/tx/access),
publish routing with mandatory/immediate returns, confirm-mode acks with
multiple-coalescing, QoS, ack/nack/reject/recover, heartbeats, and teardown
of exclusive queues on connection death.

Engine shape, by design (SURVEY.md §7.3 "pipelined command batching"): one
reader task processes commands strictly in order per connection; one writer
task drains an explicit output buffer (the reference's subtle isLastCommand
batching becomes trivially correct — everything appended between drains
coalesces into one TCP write). Delivery pushes come from queue dispatch
(event-driven), never from a poll tick.

Hot loop (_consume_scan): the native scanner hands back frame-index arrays
for a whole read chunk; contained Basic.Publish triples and Basic.Ack
frames are handled straight off the arrays with no Frame/Method/AMQCommand
objects (_fused_publish; _ack_run, the single acks of one channel in a
batch settled as one run, else _fused_ack), and everything else falls back
to the per-frame assembler path. Batch boundaries double as barriers:
publisher confirms, the store group-commit flush, and pipelined remote
queue.push RPCs all settle once per read batch (_confirm_barrier).
"""

from __future__ import annotations

import asyncio
import logging
import os
import socket
import struct
import time
import uuid
from typing import Optional

from ..amqp.command import AMQCommand, CommandAssembler
from ..amqp.constants import (
    ClassId,
    ErrorCode,
    FRAME_MIN_SIZE,
    FrameType,
    PROTOCOL_HEADER,
)
from ..amqp.frame import (
    Frame,
    FrameError,
    FrameParser,
    HEARTBEAT_BYTES,
    deliveries_wire_size,
    encode_deliveries,
)
from ..amqp import methods as am
from ..amqp.properties import BasicProperties
from ..amqp.frame import ENC_META as _ENC_META
from .. import device, events, profile, trace
from .broker import Broker, BrokerError
from .channel import ChannelMode, Consumer, ServerChannel
from ..flow import STAGE_THROTTLE

log = logging.getLogger("chanamq.connection")

from .. import __version__

SERVER_PROPERTIES = {
    "product": "chanamq-tpu",
    "version": __version__,
    "platform": "Python/asyncio",
    "capabilities": {
        "publisher_confirms": True,
        "basic.nack": True,
        "consumer_cancel_notify": True,
        "exchange_exchange_bindings": True,
    },
}

MECHANISMS = b"PLAIN EXTERNAL"
LOCALES = b"en_US"

# output buffer watermarks: above high, queue dispatch skips this connection's
# consumers; below low, dispatch resumes (SURVEY.md §7.3 "backpressure")
WRITE_HIGH_WATERMARK = 4 * 1024 * 1024
WRITE_LOW_WATERMARK = 1 * 1024 * 1024

# native batch egress: deliveries pending in a flush batch below this count
# render through the Python fallback — under ~4 records the ctypes argument
# marshalling costs more than the per-record b"".join it replaces
_EGRESS_MIN_BATCH = 4

# packed egress record meta (see native_ext._ENC_META): egress_deliver packs
# each record's header at buffer time so the flush is a single join + one
# native call with no per-record marshalling
_ENC_META_PACK = _ENC_META.pack
_ENC_META_UNPACK = _ENC_META.unpack

# scatter-gather egress: buffers per sendmsg call (Linux UIO_MAXIOV is 1024;
# stay under it and let the partial-write loop take further rounds)
_IOV_MAX = 512
_WRITEV_ENABLED = hasattr(os, "writev") and os.environ.get(
    "CHANAMQ_NATIVE_WRITEV", "1") not in ("0", "false", "no")

# method-frame payload prefixes the scan hot loop recognizes before any
# decode: Basic.Publish (class 60, method 40) and Basic.Ack (60, 80)
_PUBLISH_SIG = b"\x00\x3c\x00\x28"
_ACK_SIG = b"\x00\x3c\x00\x50"
# a whole Basic.Ack method payload: class+method, delivery tag, bits
_ACK_FRAME = struct.Struct(">IQB")
_ACK_METHOD = 0x003C0050

# fused-path publish-args cache: a flow's exchange+routing-key repeat on
# every message, so their utf-8 decodes cache keyed by the raw args slice
# (everything past the 6 fixed bytes, bits included — plain publishes only
# reach the fused path, so bits are always 0). Churn-driven clears disable
# the cache for the process: per-message-unique routing keys must not pay
# cache overhead (same adaptive pattern as the client's deliver parse).
_PUBLISH_ARGS_CACHE: dict[bytes, tuple[str, str, bytes]] = {}
_PUBLISH_CACHE_STRIKES = 4
_publish_cache_strikes = 0

# fused-path content-header cache: a flow's publishes repeat the exact
# header payload (same properties, same body size), so the decoded
# BasicProperties caches keyed by the raw header bytes. The shared instance
# is safe: nothing mutates a decoded properties object (per-message state
# like published_ns lives on Message). Same adaptive churn-disable as the
# args cache — varying body sizes change the key, so mixed-size traffic
# self-disables instead of thrashing.
_HEADER_CACHE: dict[bytes, BasicProperties] = {}
_header_cache_strikes = 0


class ConnectionClosed(Exception):
    pass


class ChannelError(Exception):
    def __init__(self, code: ErrorCode, text: str, class_id: int = 0, method_id: int = 0):
        super().__init__(text)
        self.code = code
        self.text = text
        self.class_id = class_id
        self.method_id = method_id


class HardError(ChannelError):
    """Connection-level error: close the whole connection."""


class AMQPConnection:
    """One client connection being served."""

    _next_id = 1

    def __init__(
        self,
        broker: Broker,
        reader: asyncio.StreamReader,
        writer: asyncio.StreamWriter,
        *,
        heartbeat_s: int = 30,
        frame_max: int = 131072,
        channel_max: int = 2047,
        max_message_size: int = 128 * 1024 * 1024,
        users: Optional[dict[str, str]] = None,
        permissions: Optional[dict[str, list[str]]] = None,
    ) -> None:
        self.broker = broker
        self.reader = reader
        self.writer = writer
        self.id = AMQPConnection._next_id
        AMQPConnection._next_id += 1

        self.cfg_heartbeat = heartbeat_s
        self.cfg_frame_max = frame_max
        self.cfg_channel_max = channel_max
        self.heartbeat_s = 0
        self.frame_max = frame_max
        self.channel_max = channel_max

        self.users = users  # None: accept anything (reference parity)
        self.permissions = permissions  # per-user vhost allowlists
        self.username: Optional[str] = None
        self.vhost_name: str = ""
        self.channels: dict[int, ServerChannel] = {}
        # channels we soft-closed: frames on them are discarded until the
        # client's Channel.CloseOk arrives (0-9-1 close protocol)
        self._closing_channels: set[int] = set()
        self.exclusive_queues: set[str] = set()
        # monotonic per-connection counters: the telemetry sampler derives
        # per-connection publish/deliver/ack rates from their deltas
        self.published_msgs = 0
        self.delivered_msgs = 0
        self.acked_msgs = 0
        self.closing = False
        self.closed = asyncio.get_event_loop().create_future()

        from .. import native_ext

        if native_ext.available():
            self._parser: FrameParser = native_ext.NativeFrameParser()
        else:
            self._parser = FrameParser()
        # cap declared content size: body chunks buffer in the assembler
        # before a command exists, so resident-memory backpressure can't
        # see them (chana.mq.message.max-size; RabbitMQ's analogue caps
        # at 512 MiB, default 128 MiB)
        self._assembler = CommandAssembler(max_body_size=max_message_size)
        # output path: a list of pending wire buffers (bytes appended via
        # send_bytes coalesce into a bytearray tail; batch-encoded egress
        # appends pooled memoryviews) drained by the writer task as ONE
        # scatter-gather sendmsg per wakeup. _out_bytes tracks the list's
        # total so the watermarks stay O(1); _out_pooled holds the arena
        # slot ids riding in _out, released once the kernel write lands.
        self._out: list = []
        self._out_bytes = 0
        self._out_pooled: list[int] = []
        self._out_event = asyncio.Event()
        # raw socket for the scatter-gather writer (resolved in serve();
        # None = TLS or non-socket transport, writer falls back to
        # join + StreamWriter.write)
        self._sock = None
        # native batch egress: deliveries buffered as flat packed parts
        # (_ENC_META header + prefix/exrk/header/body slices, 5 parts per
        # record) and rendered in one chana_encode_deliveries_packed call
        # when the dispatch drain ends (or the call_soon guard for
        # off-drain paths: streams, cluster stubs)
        self._egress = broker.egress_encoder
        # one pooled buffer of the encoder: the pending batch is rendered
        # before a record that would make it outgrow one, so that a batch
        # goes to the heap only when a single record is larger than that
        self._egress_cap = (self._egress.buf_bytes
                            if self._egress is not None else 0)
        self._egress_pending: list = []
        self._egress_records = 0
        self._egress_bytes = 0
        self._egress_guard_scheduled = False
        # the head run a dispatch drain has open on one of this
        # connection's channels (channel.HeadRun), which holds the
        # connection's egress counts until it hands them over
        self._head_run = None
        self._writer_task: Optional[asyncio.Task] = None
        self._heartbeat_task: Optional[asyncio.Task] = None
        self._last_recv = time.monotonic()
        self._last_send = time.monotonic()
        # publish-timestamp ring for latency measurement (confirm-less)
        self._authenticated = False
        self._tuned = False
        self._opened = False
        # confirm coalescing: channel id -> highest publish seq completed in
        # the current read batch; flushed as one Basic.Ack(multiple) per batch
        self._pending_confirms: dict[int, int] = {}
        # store-op enqueue windows (store.mark() pairs) covering THIS
        # connection's confirmed persistent publishes; passed to
        # flush(intervals=...) so the barrier fails only for our own writes
        self._confirm_marks: list[tuple[int, int]] = []
        # backpressure marker: only connections that have published can have
        # work held at the broker gate (consumer-only connections are never
        # touched by it)
        self._has_published = False
        # publish-hold backpressure (VERDICT r4 weak #2, reworked after
        # review): while the broker gate is closed, Basic.Publish commands
        # are HELD per channel instead of executed — and once a channel
        # holds a publish, everything behind it on that channel holds too
        # (per-channel FIFO). Every other frame keeps processing, so acks
        # still drain the gate (no deadlock), heartbeats/EOF stay
        # observable (the reaper keeps working), and a flooder gains
        # nothing from a token consumer. Held bodies are capped at
        # PARK_BUF_MAX bytes and accounted against the memory gauge; at
        # the cap the connection stops being read (real TCP backpressure)
        # with a bounded liveness grace (_park_full_since).
        self._held: dict[int, list] = {}
        self._held_bytes = 0
        self._park_full_since: Optional[float] = None
        # flow-ladder per-connection state: publish credit remaining while
        # the broker throttles (lazily granted from broker.flow_publish_credit
        # at the first gated publish; None = no grant outstanding), the
        # channels we sent Channel.Flow(active=false) to, and the
        # perf-counter stamp of the first hold in the current park episode
        # (feeds the flow-throttle trace span)
        self._flow_credit: Optional[int] = None
        self._flow_stopped: set[int] = set()
        self._park_t0: Optional[int] = None
        # client announced capabilities.connection.blocked in start-ok:
        # it wants Connection.Blocked/Unblocked notifications
        self._supports_blocked = False
        # capabilities.consumer_cancel_notify: the client wants a server-
        # sent Basic.Cancel when a queue dies under its consumer
        self._supports_cancel_notify = False
        # frames the current _fused_publish covered (so _consume_scan's
        # soft-error handlers resume past the failed publish's frames)
        self._fused_skip = 0
        # buffered remote push records from this read batch (clustered
        # pipelined publishes) — sent as one queue.push_many per owner and
        # awaited at the batch barrier. _remote_strict marks that at least
        # one buffered record came from a confirm-armed publish: only then
        # does a drain failure escalate to a connection error (best-effort
        # publishes just log, like the pre-pipelining inline path)
        self._remote_pending: list = []
        # single-node twin of _remote_pending: fused publishes deferred for
        # the tensor router (chana.mq.router.*) — flushed synchronously
        # before ANY other command, publish, confirm release, or close, so
        # per-channel/per-queue FIFO and confirm durability are preserved
        # exactly as if each message had published inline
        self._route_pending: list = []
        # the read chunk's open `conn.ingress` profiler span, if any
        self._ingress = None
        self._remote_strict = False
        self._remote_failures: list = []
        # tail of the ordered background chain pipelining remote-push
        # round trips past the read loop (see _batch_barrier)
        self._remote_chain: Optional[asyncio.Task] = None
        # multi-tenancy (chanamq_tpu/tenancy/): resolved once at
        # Connection.Open from broker.tenancy. _throttled is the tenant's
        # publish gate (token bucket drained / memory-share floor) and
        # rides the same hold machinery as broker.blocked; _tenant_rated
        # is the Tenant object ONLY when its quota declares a
        # publish-rate, so the ungated publish hot path pays one
        # attribute load + None check. ACL booleans are per-connection
        # constants (user x vhost is fixed after Open); _can_write also
        # gates the fused fast path so denials surface as proper 403s.
        self.tenant = None
        self._tenant_rated = None
        self._throttled = False
        self._can_configure = True
        self._can_write = True
        self._can_read = True

    # ------------------------------------------------------------------
    # output path
    # ------------------------------------------------------------------

    @property
    def write_saturated(self) -> bool:
        return self._out_bytes + self._egress_bytes >= WRITE_HIGH_WATERMARK

    def send_bytes(self, data: bytes) -> None:
        if self.closing:
            return
        if self._egress_pending:
            # wire-order invariant: buffered deliveries precede any frame
            # rendered after them (confirms, method replies, heartbeats)
            self.flush_egress()
        out = self._out
        if out and type(out[-1]) is bytearray:
            out[-1] += data
        else:
            out.append(bytearray(data))
        self._out_bytes += len(data)
        self._out_event.set()

    def send_command(self, command: AMQCommand) -> None:
        self.send_bytes(command.render(self.frame_max))

    def send_method(self, channel: int, method: am.Method) -> None:
        self.send_bytes(Frame.method(channel, method.encode()).to_bytes())

    # -- native batch egress -------------------------------------------

    def egress_deliver(self, channel_id: int, prefix: bytes, tag: int,
                       redelivered: bool, exrk: bytes, header: bytes,
                       body: bytes) -> None:
        """Buffer one basic.deliver as packed parts instead of rendering
        it: the whole batch renders in one native
        chana_encode_deliveries_packed call at the flush point: the end of
        the dispatch drain for classic queues (Broker.drain_dispatch, once
        a connection a loop tick, inside the dispatch/deliver ledger
        window), the call_soon guard for what is buffered outside a drain
        (stream and cluster delivery paths), or here, before a record that
        would make the pending batch outgrow one pooled buffer."""
        plen = len(prefix)
        elen = len(exrk)
        hlen = len(header)
        blen = len(body)
        # exact wire size, tracked so write_saturated (dispatch
        # backpressure) sees buffered records the moment they queue
        size = 25 + plen + elen + hlen
        if blen:
            frame_max = self.frame_max
            if frame_max:
                size += blen + 8 * -(-blen // (frame_max - 8))
            else:
                size += blen + 8
        pend = self._egress_pending
        if pend and self._egress_bytes + size > self._egress_cap:
            self.flush_egress()
            pend = self._egress_pending
        if not pend:
            self.egress_opened()
        pend += (_ENC_META_PACK(channel_id, tag, 1 if redelivered else 0,
                                plen, elen, hlen, blen),
                 prefix, exrk, header, body)
        self._egress_records += 1
        self._egress_bytes += size

    def egress_opened(self) -> None:
        """The first record of a batch was (or is about to be) buffered:
        name this connection to the dispatch drain's closing flush and arm
        the call_soon guard."""
        self.broker.egress_dirty.add(self)
        if not self._egress_guard_scheduled:
            self._egress_guard_scheduled = True
            asyncio.get_event_loop().call_soon(self._egress_guard)

    def egress_room(self) -> int:
        """Wire bytes this connection may still buffer before it is
        write_saturated (<= 0: it is). ServerChannel.deliver_run counts
        its run's own bytes down from it."""
        return WRITE_HIGH_WATERMARK - self._out_bytes - self._egress_bytes

    def _egress_guard(self) -> None:
        # safety net for deliveries buffered outside a dispatch drain
        # (stream cursors, cluster stub renders, a pass called directly):
        # runs on the next loop iteration, when the drain's closing flush
        # has usually already rendered the batch
        self._egress_guard_scheduled = False
        if self._egress_pending:
            self.flush_egress()

    def flush_egress(self) -> None:
        """Render the buffered delivery records into the output list: one
        native batch encode into a pooled arena buffer when the batch is
        worth it, the pure-Python encode_deliveries fallback otherwise.
        Synchronous — callable from any point of dispatch or batch
        processing without yielding the loop."""
        pend = self._egress_pending
        if not pend:
            return
        self._egress_pending = []
        nrec = self._egress_records
        self._egress_records = 0
        nbytes = self._egress_bytes
        self._egress_bytes = 0
        self.broker.egress_dirty.discard(self)
        if self.closing:
            return
        metrics = self.broker.metrics
        enc = self._egress
        buf = None
        slot = -1
        # egress_render_ns: the encode up to the writer's wake-up. A
        # counter and no span: this runs inside broker.dispatch
        t0 = time.perf_counter_ns()
        if enc is not None and nrec >= _EGRESS_MIN_BATCH:
            res = enc.encode_packed(pend, nrec, self.frame_max, nbytes)
            if res is not None:
                buf, slot = res
                if slot < 0 and nbytes > enc.buf_bytes:
                    # oversized batch went to the heap by design, not
                    # because the arena ran dry
                    pass
                elif slot < 0:
                    metrics.native_pool_exhausted += 1
            else:  # pragma: no cover - size-mismatch defense
                metrics.native_egress_fallbacks += 1
        if buf is None:
            # small batch / no encoder: rebuild records off the packed
            # parts (5 per record) for the pure-Python renderer
            records = []
            for j in range(0, len(pend), 5):
                cid, tag, red, _pl, _el, _hl, _bl = _ENC_META_UNPACK(pend[j])
                records.append((cid, pend[j + 1], tag, red, pend[j + 2],
                                pend[j + 3], pend[j + 4]))
            buf = encode_deliveries(records, self.frame_max)
        else:
            metrics.native_egress_batches += 1
            metrics.native_egress_msgs += nrec
            metrics.native_egress_bytes += nbytes
        out = self._out
        if slot >= 0:
            self._out_pooled.append(slot)
            out.append(buf)
        elif type(buf) is bytearray:
            out.append(buf)  # native heap encode: already its own buffer
        elif out and type(out[-1]) is bytearray:
            out[-1] += buf
        else:
            out.append(bytearray(buf))
        self._out_bytes += nbytes
        self._out_event.set()
        metrics.egress_render_ns += time.perf_counter_ns() - t0

    # -- writer task ----------------------------------------------------

    async def _writer_loop(self) -> None:
        try:
            while True:
                await self._out_event.wait()
                self._out_event.clear()
                if self._out:
                    bufs = self._out
                    pooled = self._out_pooled
                    nbytes = self._out_bytes
                    self._out = []
                    self._out_pooled = []
                    self._out_bytes = 0
                    was_saturated = nbytes >= WRITE_HIGH_WATERMARK
                    try:
                        await self._write_bufs(bufs)
                    finally:
                        # arena slots return to the pool even when the
                        # write dies mid-flight (connection teardown
                        # awaits/cancels this task before closing)
                        if pooled:
                            enc = self._egress
                            for slot in pooled:
                                enc.release(slot)
                    self._last_send = time.monotonic()
                    if not self._out and self.broker.flow_consumer_buffer:
                        # fully drained to the kernel: whatever this
                        # connection's consumers had buffered is on the
                        # wire — reset their delivery-buffer accounting
                        self._reset_consumer_buffers()
                    if was_saturated and (self._out_bytes
                                          < WRITE_LOW_WATERMARK):
                        self._resume_dispatch()
                if self.closing and not self._out:
                    break
        except (ConnectionResetError, BrokenPipeError, OSError, ValueError,
                asyncio.CancelledError):
            # dead peer (or the socket closed under us mid-write): mark
            # closing so a main loop parked at the memory gate (not
            # reading, hence blind to the hangup) still exits
            self.closing = True

    async def _write_bufs(self, bufs: list) -> None:
        """One writer wakeup's kernel hand-off: scatter-gather writev of
        the pending buffer list on plain TCP/UDS sockets (no concatenation
        copy), StreamWriter.write + drain otherwise (TLS, test doubles).

        asyncio forbids a second add_writer on a transport-owned fd, so a
        full kernel buffer (EAGAIN) spills the remainder into the transport
        — which owns the fd's writability callback — and writev resumes
        once the transport reports its buffer drained.

        The synchronous writev loop, and never the drain, is the span
        ``conn.egress_write`` and ``egress_write_ns`` /
        ``egress_writev_calls``; a spill counts in ``egress_write_spills``.
        A write that goes through the transport (TLS, or while a spill is
        still buffered there) is counted by none of them."""
        sock = self._sock
        if sock is None or self.writer.transport.get_write_buffer_size():
            self.writer.write(b"".join(bufs))
            await self.writer.drain()
            return
        metrics = self.broker.metrics
        t0 = time.perf_counter_ns()
        with device.span("conn.egress_write"):
            spilled = self._writev(sock.fileno(), bufs)
        metrics.egress_write_ns += time.perf_counter_ns() - t0
        if spilled:
            metrics.egress_write_spills += 1
            await self.writer.drain()

    def _writev(self, fd: int, bufs: list) -> bool:
        """writev ``bufs`` until all are with the kernel (False) or its
        buffer is full: the rest is then with the transport, for the
        caller to drain (True)."""
        metrics = self.broker.metrics
        idx = 0
        total = len(bufs)
        while idx < total:
            batch = bufs[idx:idx + _IOV_MAX]
            try:
                metrics.egress_writev_calls += 1
                sent = os.writev(fd, batch)
            except InterruptedError:
                continue
            except BlockingIOError:
                self.writer.write(b"".join(bufs[idx:]))
                return True
            while sent > 0:
                blen = len(bufs[idx])
                if sent >= blen:
                    sent -= blen
                    idx += 1
                else:
                    # partial buffer: keep the unsent tail (memoryview
                    # slicing is zero-copy for both bytearray and pooled
                    # arena buffers)
                    mv = bufs[idx]
                    if type(mv) is not memoryview:
                        mv = memoryview(mv)
                    bufs[idx] = mv[sent:]
                    sent = 0
        return False

    def _resume_dispatch(self) -> None:
        for channel in self.channels.values():
            for consumer in channel.consumers.values():
                consumer.queue.schedule_dispatch()

    def _reset_consumer_buffers(self) -> None:
        """Output buffer hit the kernel: clear per-consumer delivery-buffer
        bytes and wake dispatch for any consumer that was marked slow."""
        for channel in self.channels.values():
            for consumer in channel.consumers.values():
                if consumer.buffered_bytes:
                    consumer.buffered_bytes = 0
                    if consumer.slow:
                        consumer.slow = False
                        consumer.queue.schedule_dispatch()

    # ------------------------------------------------------------------
    # main loop
    # ------------------------------------------------------------------

    async def serve(self) -> None:
        """Run the connection to completion."""
        self.broker.metrics.connections_opened += 1
        sock = self.writer.get_extra_info("socket")
        if sock is not None and hasattr(sock, "setsockopt"):
            try:
                # disable Nagle: deliver/confirm frames are small writes
                # and must not wait on the peer's delayed ACK (the batch
                # egress already coalesces a dispatch pass into one
                # writev, so there is nothing left for Nagle to batch)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass  # unix socket / exotic family: no Nagle to disable
        if _WRITEV_ENABLED and self.writer.get_extra_info("ssl_object") is None:
            # plain TCP/UDS stream: the writer drains via scatter-gather
            # sendmsg on the raw socket (every steady-state byte goes
            # through _out, so the transport's own buffer stays empty and
            # direct socket writes can't interleave with it)
            if sock is not None and hasattr(sock, "fileno"):
                self._sock = sock
        self._writer_task = asyncio.create_task(self._writer_loop())
        self.broker.blocked_listeners.add(self._on_memory_blocked)
        self.broker.flow_stage_listeners.add(self._on_flow_stage)
        self.broker.connections.add(self)
        try:
            await self._handshake()
            bus = events.ACTIVE
            if bus is not None:
                bus.emit("connection.created", {
                    "connection": self.id, "vhost": self.vhost_name,
                    "user": self.username,
                })
            await self._main_loop()
        except ConnectionClosed:
            pass
        except (ConnectionResetError, BrokenPipeError, asyncio.IncompleteReadError):
            pass
        except Exception:
            log.exception("connection %d crashed", self.id)
        finally:
            self.broker.blocked_listeners.discard(self._on_memory_blocked)
            self.broker.flow_stage_listeners.discard(self._on_flow_stage)
            self.broker.connections.discard(self)
            await self._teardown()

    def _on_memory_blocked(self, blocked: bool) -> None:
        """Broker memory gate transition: notify clients that announced the
        connection.blocked capability (exceeds the reference, which never
        implemented Blocked/Unblocked — README.md:10-22)."""
        if self._supports_blocked and self._opened and not self.closing:
            if blocked:
                self.send_method(0, am.Connection.Blocked(
                    reason=self.broker.blocked_reason))
            else:
                self.send_method(0, am.Connection.Unblocked())

    def _on_flow_stage(self, old: int, new: int) -> None:
        """Flow-ladder transition (stage 2 = throttle): surface publisher
        throttling on the wire as server-initiated Channel.Flow. Publishers
        that honor it stop sending voluntarily; ones that don't hit the
        park/credit path anyway (Flow is advisory, the hold is the law).
        Consumer-only connections are left alone — pausing them would slow
        the very drain that reopens the gate."""
        if self.closing or not self._opened:
            return
        if new >= STAGE_THROTTLE and old < STAGE_THROTTLE:
            if not self._has_published:
                return
            for channel_id, channel in self.channels.items():
                if channel_id not in self._closing_channels:
                    self.send_method(channel_id, am.Channel.Flow(active=False))
                    self._flow_stopped.add(channel_id)
            if self._flow_stopped:
                self.broker.metrics.flow_throttles += 1
        elif new < STAGE_THROTTLE and old >= STAGE_THROTTLE:
            if self._throttled:
                return  # tenant gate still closed: keep publishers stopped
            resumed = False
            for channel_id in self._flow_stopped:
                if (channel_id in self.channels
                        and channel_id not in self._closing_channels):
                    self.send_method(channel_id, am.Channel.Flow(active=True))
                    resumed = True
            self._flow_stopped.clear()
            if resumed:
                self.broker.metrics.flow_resumes += 1

    def set_tenant_gate(self, on: bool) -> None:
        """Tenant publish-gate transition (token bucket drained or
        memory-share floor hit, tenancy/registry.py). Mirrors
        _on_flow_stage: the gate itself is the hold interception in
        _run_command / the fused-path check; Channel.Flow is the advisory
        wire signal for publishers that honor it."""
        if on == self._throttled:
            return
        self._throttled = on
        if self.closing or not self._opened:
            return
        if on:
            if not self._has_published:
                return
            for channel_id in self.channels:
                if channel_id not in self._closing_channels:
                    self.send_method(channel_id, am.Channel.Flow(active=False))
                    self._flow_stopped.add(channel_id)
        else:
            if self.broker.blocked:
                return  # broker ladder still throttling: keep them stopped
            for channel_id in self._flow_stopped:
                if (channel_id in self.channels
                        and channel_id not in self._closing_channels):
                    self.send_method(channel_id, am.Channel.Flow(active=True))
            self._flow_stopped.clear()

    def detach_tenant(self) -> None:
        """Tenant removed at runtime: the connection stays open but loses
        quota/ACL scoping (its vhost is no longer tenant-owned)."""
        if self._throttled:
            self.set_tenant_gate(False)
        self.tenant = None
        self._tenant_rated = None
        self._can_configure = self._can_write = self._can_read = True

    def notify_consumer_cancel(self, channel: ServerChannel, tag: str) -> None:
        """Server-sent Basic.Cancel: the queue died under this consumer
        (delete / auto-delete / exclusive death / idle expiry). Sent only
        to clients that announced the consumer_cancel_notify capability
        (RabbitMQ extension; EXCEEDS the reference, which never cancels)."""
        if (self._supports_cancel_notify and not self.closing
                and not channel.closed):
            self.send_method(channel.id, am.Basic.Cancel(
                consumer_tag=tag, nowait=True))

    # held-publish byte cap per connection: one read chunk. Checked between
    # chunks, so the effective bound is cap + one chunk; past it the peer
    # is genuinely backpressured (TCP window closes) and unobservable.
    PARK_BUF_MAX = 262144
    # flat per-held-command cost added to the body bytes (AMQCommand +
    # method + properties object overhead): bounds the held-command COUNT
    # for empty/tiny-body floods, not just the byte volume
    HELD_COMMAND_OVERHEAD = 512
    # multiple of the heartbeat interval a full-buffer (unobservable) peer
    # keeps its liveness clock refreshed; past it the heartbeat reaper's
    # normal 2x-interval deadline applies even while the broker is gated
    PARK_FULL_GRACE_INTERVALS = 4

    def _park_grace_tick(self) -> None:
        """Liveness bookkeeping while reads are stopped at the held-buffer
        cap. Pending bytes prove the peer was alive recently, so the clock
        is refreshed — but only for a bounded grace: an unobservable peer
        must not dodge the reaper forever (a dead flooder would otherwise
        linger until kernel retransmit timeout, VERDICT r4 weak #3)."""
        now = time.monotonic()
        if self._park_full_since is None:
            self._park_full_since = now
        grace = self.PARK_FULL_GRACE_INTERVALS * max(self.heartbeat_s, 1)
        if now - self._park_full_since < grace:
            self._last_recv = now

    def _hold_command(self, command: AMQCommand) -> None:
        """Park one command behind the publisher gate (publishes, and
        anything pipelined behind a held publish on the same channel)."""
        if type(command.method) is am.Basic.Publish:
            self._has_published = True  # set at hold time too: a fully-held
            # publisher must still read as a publisher everywhere the flag
            # is consulted
        if self._park_t0 is None:
            # first hold of this park episode: start the flow-throttle span
            self._park_t0 = time.perf_counter_ns()
        self._held.setdefault(command.channel, []).append(command)
        # cost = body + flat per-command overhead, so a flood of empty-body
        # publishes (legal AMQP) still trips the cap instead of accumulating
        # unbounded AMQCommand objects past a body-only count
        cost = self._held_cost(command)
        self._held_bytes += cost
        # tracked on a SEPARATE gauge, not resident_bytes: held bodies
        # gating their own release would deadlock the gate (they only
        # leave RAM by being released below the low watermark). They
        # are structurally bounded instead: PARK_BUF_MAX per
        # connection x the listener's max-connections cap. The flow
        # accountant counts them in the reported total but excludes them
        # from ladder decisions for the same deadlock reason.
        self.broker.account_held(cost)

    @classmethod
    def _held_cost(cls, command: AMQCommand) -> int:
        return len(command.body or b"") + cls.HELD_COMMAND_OVERHEAD

    def _held_cap(self) -> int:
        """Hold budget before reads stop. A connection with outstanding
        deliveries gets 4x: its acks — the very thing that drains the gate
        — may be pipelined behind a burst of publishes, and stopping reads
        at the base cap would wedge them unread (a worker publishing and
        consuming on one connection would deadlock its own gate). Still a
        hard bound: a flooder parking one unacked delivery as a hostage
        buys 4x PARK_BUF_MAX, not an unbounded hold, and the ack-timeout
        sweep eventually closes channels that never ack."""
        base = self.broker.park_buf_max or self.PARK_BUF_MAX
        for channel in self.channels.values():
            if channel.unacked:
                return 4 * base
        return base

    def _should_hold(self, command: AMQCommand) -> bool:
        method_type = type(command.method)
        if method_type in (am.Basic.Ack, am.Basic.Nack, am.Basic.Reject):
            # settles of PRIOR deliveries commute with held publishes
            # (delivery tags are independent of the publish stream) and are
            # exactly what must keep draining the gate: holding a
            # same-channel ack behind a held publish would deadlock a
            # single-channel publish+consume client against its own gate
            return False
        if command.channel in self._held:
            return True  # per-channel FIFO behind an already-held publish
        if method_type is am.Basic.Publish and command.channel != 0:
            if self.broker.blocked:
                # per-connection publish credit
                # (chana.mq.flow.publish-credit): the first gated publishes
                # spend a bounded byte allowance before the hard hold
                # engages, so a well-behaved publisher that reacts to
                # Channel.Flow(active=false) in time never parks at all.
                # Credit 0 (the Broker default) holds immediately — the
                # legacy gate contract.
                return not self._spend_flow_credit(command)
            if self._throttled:
                return not self._spend_tenant_credit(command)
        return False

    def _spend_flow_credit(self, command: AMQCommand) -> bool:
        """Spend publish credit for one gated publish; True while credit
        remains (the publish executes instead of holding). The grant is
        lazy — taken from the broker knob at the first gated publish of a
        throttle episode — and reset when the gate reopens."""
        grant = self.broker.flow_publish_credit
        if not grant:
            return False
        if self._flow_credit is None:
            self._flow_credit = grant
        if self._flow_credit <= 0:
            return False
        self._flow_credit -= self._held_cost(command)
        return True

    def _spend_tenant_credit(self, command: AMQCommand) -> bool:
        """Tenant-gated twin of _spend_flow_credit: while the tenant's
        publish gate is closed, the per-connection credit grant is drawn
        from whatever tokens the tenant's bucket has re-accrued (capped at
        the broker's flow grant), so the held stream drains at exactly the
        quota rate instead of stalling until a full resume. Executed
        publishes that pass here are pre-paid — the publish-site spend is
        skipped while _throttled (see _tenant_spend)."""
        tenant = self.tenant
        if tenant is None or tenant.memory_gated:
            # no tenant (gate mid-lift) executes; a memory-share floor
            # never grants — only draining lifts it
            return tenant is None
        if not self._flow_credit:  # None or spent: draw a fresh grant
            grant = tenant.take_credit(
                self.broker.flow_publish_credit or self.PARK_BUF_MAX)
            if grant <= 0:
                return False
            self._flow_credit = grant
        self._flow_credit -= self._held_cost(command)
        return True

    def _tenant_spend(self, nbytes: int) -> None:
        """Publish-site token spend (generic + fast paths; the fused path
        inlines the same two lines). Accounted cost matches the held-cost
        formula (body + flat per-command overhead) so empty-body floods
        still drain the bucket. Skipped while gated: gated publishes that
        execute pre-paid via _spend_tenant_credit."""
        rated = self._tenant_rated
        if rated is not None and not self._throttled:
            rated.spend(nbytes + self.HELD_COMMAND_OVERHEAD)

    async def _release_held(self) -> bool:
        """Gate reopened: execute held commands, per-channel FIFO (channel
        interleaving is free under AMQP). If the gate closes again
        mid-release, the remainder re-holds via the normal interception.
        Returns False when the connection must stop serving."""
        held, self._held = self._held, {}
        self._held_bytes = 0
        self._park_full_since = None
        self._flow_credit = None  # fresh grant next throttle episode
        if self._park_t0 is not None:
            t0, self._park_t0 = self._park_t0, None
            t1 = time.perf_counter_ns()
            self.broker.metrics.flow_hold_releases += 1
            self.broker.metrics.flow_hold_wait_ns += t1 - t0
            prof = profile.ACTIVE
            if prof is not None:
                # wall, not CPU: how long the gate parked this stream —
                # one accumulate per throttle episode, already-stamped
                prof.stage_ns[profile.FLOW_THROTTLE] += t1 - t0
                prof.stage_calls[profile.FLOW_THROTTLE] += 1
            if trace.ACTIVE is not None:
                # the first released publish carries the flow-throttle span
                # (how long the gate parked this connection's stream)
                trace.ACTIVE.flow_ns = (t0, t1)
        queues = list(held.values())
        for qi, commands in enumerate(queues):
            for ci, command in enumerate(commands):
                self.broker.account_held(-self._held_cost(command))
                if not await self._run_command(command):
                    # connection is stopping: release the gauge for every
                    # command not yet un-accounted (none were confirmed —
                    # seqs are assigned at execution time)
                    for rest in commands[ci + 1:]:
                        self.broker.account_held(-self._held_cost(rest))
                    for later in queues[qi + 1:]:
                        for rest in later:
                            self.broker.account_held(-self._held_cost(rest))
                    return False
        # same barrier as the main loop: confirms for persistent publishes
        # must not ack until their store writes are flushed (a barrier
        # failure propagates and tears the connection down, like there)
        await self._confirm_barrier()
        self._flush_confirms()
        return True

    async def _read_chunk(self) -> bytes:
        # large reads amortize event-loop wakeups and process context
        # switches (one core may run broker + many clients); at ~170 wire
        # bytes per small publish this is ~1500 messages per syscall
        data = await self.reader.read(262144)
        if not data:
            raise ConnectionClosed()
        self._last_recv = time.monotonic()
        if trace.ACTIVE is not None:
            # ingress-parse spans start at the chunk read; one stamp per
            # ~256 KiB read, not per message (begin_publish drops it when
            # stale, e.g. an idle connection)
            trace.ACTIVE.ingress_ns = time.perf_counter_ns()
        return data

    async def _handshake(self) -> None:
        """Protocol header exchange (reference: FrameStage.scala:181-234)."""
        header = await self.reader.readexactly(8)
        self._last_recv = time.monotonic()
        if header != PROTOCOL_HEADER:
            # wrong protocol: reply with ours and hang up
            self.writer.write(PROTOCOL_HEADER)
            await self.writer.drain()
            raise ConnectionClosed()
        self.send_method(0, am.Connection.Start(
            version_major=0, version_minor=9,
            server_properties=SERVER_PROPERTIES,
            mechanisms=MECHANISMS, locales=LOCALES,
        ))

    async def _main_loop(self) -> None:
        # the native parser exposes the raw scan arrays: the hot loop walks
        # them directly (fused publish path); the pure-Python parser keeps
        # the Frame-object path
        scan = getattr(self._parser, "scan_batches", None)
        while not self.closing:
            if self._held and not self.broker.blocked and not self._throttled:
                # gate reopened: run the held publishes (per-channel FIFO)
                if not await self._release_held():
                    return
                continue
            # held-buffer cap reached while the gate is closed: stop
            # reading (bytes back up into TCP). Liveness is unobservable
            # in this state, so the clock gets a BOUNDED grace — a peer
            # that stays unobservable past it is reaped by the heartbeat
            # loop (VERDICT r4 weak #3: the grace must be capped). The
            # tenant gate has no event to wait on (it lifts on the next
            # registry tick), so its park leg is a bounded sleep.
            while ((self.broker.blocked or self._throttled)
                   and not self.closing
                   and self._held_bytes >= self._held_cap()):
                self._park_grace_tick()
                if self.broker.blocked:
                    await self.broker.wait_memory_gate()
                else:
                    await asyncio.sleep(0.25)
            if self.closing:
                return
            if self._held and not self.broker.blocked and not self._throttled:
                continue  # gate just reopened: release before reading more
            if self._held:
                # bounded read while holding: the loop must wake to release
                # held commands once the gate reopens even if the peer
                # sends nothing further (a blocking read would deadlock
                # the release against the peer's silence)
                try:
                    data = await asyncio.wait_for(self._read_chunk(), 0.25)
                except asyncio.TimeoutError:
                    continue
            else:
                data = await self._read_chunk()
            # one ingress-cycle ledger window per read chunk: parse walk,
            # fused publishes, command dispatch, and the batch barrier all
            # run inside it (two stamps per ~256 KiB chunk, not per
            # message) — this is the top-level "where did the loop's CPU
            # go" stage the finer route/enqueue stages nest within. The
            # window is loop-thread CPU, and any OTHER top-level window
            # that accumulated while this coroutine was suspended (a
            # dispatch pass, a sibling connection's cycle) is subtracted
            # back out so the top-level sum never double-counts.
            prof = profile.ACTIVE
            if prof is not None:
                sns = prof.stage_ns
                t_cycle = time.thread_time_ns()
                nested0 = int(sns[profile.DISPATCH]
                              + sns[profile.CLUSTER_PUSH]
                              + sns[profile.INGRESS_CYCLE])
            if scan is not None:
                # one profiler span a read chunk over the native scan and
                # the fused-publish loop; the doors out of that synchronous
                # stretch (a flush, a generic command, a close) end it
                # first, so that no other span opens inside it
                self._ingress = device.span("conn.ingress")
                self._ingress.__enter__()
                try:
                    ok = await self._consume_scan(scan(data))
                finally:
                    self._end_ingress()
            else:
                ok = await self._consume_feed(self._parser.feed(data))
            if ok:
                await self._batch_barrier()
            if prof is not None:
                dt = time.thread_time_ns() - t_cycle
                nested = int(sns[profile.DISPATCH]
                             + sns[profile.CLUSTER_PUSH]
                             + sns[profile.INGRESS_CYCLE]) - nested0
                if dt > nested:
                    sns[profile.INGRESS_CYCLE] += dt - nested
                prof.stage_calls[profile.INGRESS_CYCLE] += 1
            if not ok:
                return

    def _end_ingress(self) -> None:
        span = self._ingress
        if span is not None:
            self._ingress = None
            span.__exit__(None, None, None)

    async def _run_command(self, out: AMQCommand) -> bool:
        """Dispatch one assembled command with the connection's error
        semantics. Returns False when the connection must stop serving."""
        self._end_ingress()
        if (self.broker.flow_refusing
                and type(out.method) is am.Basic.Publish
                and out.channel != 0
                and out.channel not in self._held):
            # ladder stage 4 (refuse): past the refuse watermark, fresh
            # publishes are rejected outright with a channel-level
            # precondition error instead of parked — holding more bodies
            # would push accounted memory toward the hard limit while
            # consumers drain. Publishes already FIFO-queued behind a held
            # one still park (closing their channel would orphan them).
            self.broker.metrics.flow_publishes_refused += 1
            await self._soft_close_channel(out.channel, ChannelError(
                ErrorCode.PRECONDITION_FAILED,
                "memory overload: broker refusing publishes"))
            return not self.closing
        if ((self._held or self.broker.blocked or self._throttled)
                and self._should_hold(out)):
            self._hold_command(out)
            return True
        try:
            if not self._try_fast_publish(out):
                await self._dispatch(out)
        except HardError as exc:
            await self._hard_close(
                exc.code, exc.text, exc.class_id, exc.method_id)
            return False
        except ChannelError as exc:
            await self._soft_close_channel(out.channel, exc)
        except BrokerError as exc:
            if exc.code.is_hard_error:
                await self._hard_close(
                    exc.code, exc.text,
                    out.method.CLASS_ID, out.method.METHOD_ID)
                return False
            await self._soft_close_channel(
                out.channel,
                ChannelError(exc.code, exc.text,
                             out.method.CLASS_ID, out.method.METHOD_ID))
        return not self.closing

    async def _consume_feed(self, items) -> bool:
        for item in items:
            if isinstance(item, FrameError):
                await self._hard_close(item.code, item.message)
                return False
            if item.type == FrameType.HEARTBEAT:
                continue  # _last_recv already updated
            out = self._assembler.feed_one(item)
            if out is None:
                continue  # content still assembling
            if isinstance(out, FrameError):
                await self._hard_close(out.code, out.message)
                return False
            if not await self._run_command(out):
                return False
        return True

    async def _consume_scan(self, batches) -> bool:
        """The native-parser read loop: walk the scan arrays directly. A
        contained Basic.Publish (method+header+body in one batch, plain
        flags) short-circuits through _fused_publish without constructing
        Frame / Method / AMQCommand objects; everything else falls back to
        the Frame path one frame at a time."""
        partials = self._assembler._partial
        for batch in batches:
            if isinstance(batch, FrameError):
                await self._hard_close(batch.code, batch.message)
                return False
            raw, n, types, channels, offsets, lengths, pub_mark, body_off, \
                body_len = batch
            i = 0
            while i < n:
                ftype = types[i]
                if ftype == 8:  # heartbeat: _last_recv already updated
                    i += 1
                    continue
                channel_id = channels[i]
                off = offsets[i]
                if (ftype == 1 and self._fast_path
                        and channel_id not in partials
                        and not self._held and not self.broker.blocked
                        and not self._throttled):
                    consumed = 0
                    try:
                        mark = pub_mark[i]
                        if mark:
                            # the native scanner already validated the
                            # complete METHOD/HEADER/BODY publish triple:
                            # no sig compare, no shape walk, one body slice
                            consumed = self._fused_publish_marked(
                                raw, i, mark, channel_id, off, offsets,
                                lengths, body_off, body_len)
                        else:
                            sig = raw[off:off + 4]
                            if (sig == _PUBLISH_SIG and i + 1 < n
                                    and types[i + 1] == 2
                                    and channels[i + 1] == channel_id):
                                consumed = self._fused_publish(
                                    raw, i, n, types, channels, offsets,
                                    lengths)
                            elif sig == _ACK_SIG and lengths[i] == 13:
                                consumed = self._ack_run(
                                    raw, i, n, types, channels, offsets,
                                    lengths)
                    except HardError as exc:
                        await self._hard_close(
                            exc.code, exc.text, exc.class_id, exc.method_id)
                        return False
                    except ChannelError as exc:
                        await self._soft_close_channel(channel_id, exc)
                        if self.closing:  # flipped during the await
                            return False
                        i += self._fused_skip
                        continue
                    except BrokerError as exc:
                        if exc.code.is_hard_error:
                            await self._hard_close(exc.code, exc.text, 60, 40)
                            return False
                        await self._soft_close_channel(
                            channel_id,
                            ChannelError(exc.code, exc.text, 60, 40))
                        if self.closing:  # flipped during the await
                            return False
                        i += self._fused_skip
                        continue
                    if consumed:
                        i += consumed
                        continue
                frame = Frame(ftype, channel_id, raw[off:off + lengths[i]])
                i += 1
                out = self._assembler.feed_one(frame)
                if out is None:
                    continue
                if isinstance(out, FrameError):
                    await self._hard_close(out.code, out.message)
                    return False
                # a generic command may publish, mutate topology, or read
                # queue state: deferred publishes must land first
                if self._route_pending:
                    self._flush_route_pending()
                if not await self._run_command(out):
                    return False
        return True

    @property
    def _fast_path(self) -> bool:
        # clustered connections take it too: _fused_publish falls back on
        # a cluster-route-cache miss, and _fused_ack settles through the
        # same channel.ack the generic arm uses (remote settles buffer).
        # ACL write denial routes publishes to the generic path so each
        # raises a proper access-refused channel error.
        return self._opened and not self._closing_channels and self._can_write

    @staticmethod
    def _publish_args(payload: bytes):
        """Decode (exchange, routing_key, exrk_raw) off a Basic.Publish
        method payload through the adaptive args cache. None -> generic
        path (truncated payload, or mandatory/immediate bits that need a
        Return render)."""
        global _publish_cache_strikes
        caching = _publish_cache_strikes < _PUBLISH_CACHE_STRIKES
        if caching:
            args_key = payload[6:]
            cached = _PUBLISH_ARGS_CACHE.get(args_key)
            if cached is not None:
                return cached
        try:
            exchange, routing_key, bits, pos = am.parse_publish_wire(payload)
        except (IndexError, UnicodeDecodeError, am.MethodDecodeError):
            return None  # truncated/bad payload: generic path raises properly
        if bits:
            return None  # mandatory / immediate: generic path renders Returns
        exrk_raw = payload[6:pos]
        if caching:
            if len(_PUBLISH_ARGS_CACHE) >= 1024:
                _PUBLISH_ARGS_CACHE.clear()
                _publish_cache_strikes += 1
            if _publish_cache_strikes < _PUBLISH_CACHE_STRIKES:
                _PUBLISH_ARGS_CACHE[args_key] = (
                    exchange, routing_key, exrk_raw)
        return exchange, routing_key, exrk_raw

    @staticmethod
    def _publish_props(header: bytes) -> Optional[BasicProperties]:
        """Decode BasicProperties off a raw content-header payload through
        the adaptive header cache. None -> generic path (the assembler
        raises the proper SYNTAX_ERROR)."""
        global _header_cache_strikes
        caching = _header_cache_strikes < _PUBLISH_CACHE_STRIKES
        if caching:
            props = _HEADER_CACHE.get(header)
            if props is not None:
                return props
        try:
            _class_id, _size, props = BasicProperties.decode_header(header)
        except Exception:
            return None
        if caching:
            if len(_HEADER_CACHE) >= 1024:
                _HEADER_CACHE.clear()
                _header_cache_strikes += 1
            if _header_cache_strikes < _PUBLISH_CACHE_STRIKES:
                _HEADER_CACHE[header] = props
        return props

    def _fused_publish_marked(
        self, raw, i, mark, channel_id, moff, offsets, lengths, body_off,
        body_len
    ) -> int:
        """Marked fast lane: chana_scan_publish already proved frames
        i..i+mark-1 form a complete plain Basic.Publish triple on one
        channel, so this skips the signature compare, the shape checks,
        and the body-gather walk — one slice per wire field. Cache hits
        (the steady-state flow: same exchange+rk, same header shape) are
        checked inline to skip the decode-helper calls entirely. Returns
        the frames consumed or 0 to fall back (TX channel, unknown
        channel, over the size cap, clustered route-cache miss)."""
        payload = raw[moff:moff + lengths[i]]
        if _publish_cache_strikes < _PUBLISH_CACHE_STRIKES:
            args = _PUBLISH_ARGS_CACHE.get(payload[6:])
        else:
            args = None
        if args is None:
            args = self._publish_args(payload)
            if args is None:
                return 0
        exchange, routing_key, exrk_raw = args
        channel = self.channels.get(channel_id)
        if channel is None:
            return 0  # full path raises the proper channel error
        if channel.mode is ChannelMode.TX:
            return 0  # transactional publish: generic path buffers it
        hoff = offsets[i + 1]
        header = raw[hoff:hoff + lengths[i + 1]]
        blen = body_len[i]
        max_body = self._assembler.max_body_size
        if max_body and blen > max_body:
            return 0  # over the message-size cap: the assembler raises 501
        if blen:
            boff = body_off[i]
            body = raw[boff:boff + blen]
        else:
            body = b""
        if _header_cache_strikes < _PUBLISH_CACHE_STRIKES:
            props = _HEADER_CACHE.get(header)
        else:
            props = None
        if props is None:
            props = self._publish_props(header)
            if props is None:
                return 0
        return self._publish_fused_tail(
            channel, channel_id, exchange, routing_key, props, body,
            header, exrk_raw, mark)

    def _fused_publish(
        self, raw, i, n, types, channels, offsets, lengths
    ) -> int:
        """Publish straight off the scan arrays: returns the number of
        frames consumed (method + header + body frames), or 0 to fall back
        to the generic Frame/assembler path (rare shapes: mandatory or
        immediate bits, body spanning into the next read, interleaved
        channels, unknown channel). Semantics mirror _try_fast_publish —
        same publish_sync call, same confirm arming — minus the Return
        cases, which the bit check routes to the fallback. The common
        single-body-frame shape never lands here anymore — chana_scan_publish
        marks it and _fused_publish_marked takes it; this path keeps the
        multi-body-frame (within one read batch) publishes fused."""
        moff = offsets[i]
        args = self._publish_args(raw[moff:moff + lengths[i]])
        if args is None:
            return 0
        exchange, routing_key, exrk_raw = args
        channel = self.channels.get(channels[i])
        if channel is None:
            return 0  # full path raises the proper channel error
        if channel.mode is ChannelMode.TX:
            return 0  # transactional publish: generic path buffers it
        hoff = offsets[i + 1]
        header = raw[hoff:hoff + lengths[i + 1]]
        body_size = int.from_bytes(header[4:12], "big")
        max_body = self._assembler.max_body_size
        if max_body and body_size > max_body:
            return 0  # over the message-size cap: the assembler raises 501
        channel_id = channels[i]
        consumed = 2
        if body_size == 0:
            body = b""
        else:
            j = i + 2
            got = 0
            first = None
            chunks = None
            while got < body_size:
                if j >= n or types[j] != 3 or channels[j] != channel_id:
                    return 0  # spans the batch / interleaved: generic path
                boff = offsets[j]
                blen = lengths[j]
                got += blen
                if got > body_size:
                    return 0  # overflow: generic path raises FRAME_ERROR
                if first is None:
                    first = raw[boff:boff + blen]
                else:
                    if chunks is None:
                        chunks = [first]
                    chunks.append(raw[boff:boff + blen])
                j += 1
            body = first if chunks is None else b"".join(chunks)
            consumed = j - i
        props = self._publish_props(header)
        if props is None:
            return 0
        return self._publish_fused_tail(
            channel, channel_id, exchange, routing_key, props, body,
            header, exrk_raw, consumed)

    def _publish_fused_tail(
        self, channel, channel_id, exchange, routing_key, props, body,
        header, exrk_raw, consumed
    ) -> int:
        """Shared back half of the fused publish lanes: tenant spend,
        router deferral / publish_sync / clustered fast push, confirm
        arming — identical semantics to the pre-split _fused_publish."""
        # count the skip before publish: the except handlers in
        # _consume_scan resume past this publish's frames on soft errors
        self._fused_skip = consumed
        rated = self._tenant_rated
        if rated is not None:
            # tenant publish-rate token spend (same cost formula as
            # _held_cost); may close the tenant gate, which the scan-loop
            # gate check observes before the NEXT frame
            rated.spend(len(body) + self.HELD_COMMAND_OVERHEAD)
        broker = self.broker
        if broker.cluster is None:
            router = broker.router
            if router is not None and router.defer_ok(
                    self.vhost_name, exchange):
                # batch routing: buffer the decoded publish; the whole
                # read batch routes in one kernel call at the next flush
                # point. Confirm arming is identical to the inline path —
                # the confirm can only be RELEASED after a barrier, and
                # every barrier flushes this buffer first.
                seq = self._arm_confirm(channel)
                self._route_pending.append((
                    exchange, routing_key, props, body, header, exrk_raw,
                    seq is not None))
                if seq is not None:
                    self._pending_confirms[channel_id] = seq
                    broker.metrics.confirmed_msgs += 1
                return consumed
            if self._route_pending:
                # non-deferrable publish while deferred ones are buffered:
                # flush first (per-channel/per-queue FIFO)
                self._flush_route_pending()
            seq = self._arm_confirm(channel)
            broker.publish_sync(
                self.vhost_name, exchange, routing_key, props, body,
                header_raw=header,
                marks=self._confirm_marks if seq is not None else None,
                exrk_raw=exrk_raw,
            )
        else:
            # clustered: fused only on a route-cache hit (checked before
            # arming the confirm, so a miss has no side effects) — the
            # generic path resolves the route once and fills the cache
            if not broker.cluster_route_cached(
                    self.vhost_name, exchange, routing_key):
                return 0
            seq = self._arm_confirm(channel)
            pending = self._remote_pending
            buffered_before = len(pending)
            broker.publish_clustered_fast(
                self.vhost_name, exchange, routing_key, props, body,
                header,
                self._confirm_marks if seq is not None else None,
                pending)
            if seq is not None and len(pending) > buffered_before:
                self._remote_strict = True
        if seq is not None:
            # coalesce: one Basic.Ack(multiple=true) per read batch
            self._pending_confirms[channel_id] = seq
            self.broker.metrics.confirmed_msgs += 1
        return consumed

    def _flush_route_pending(self) -> None:
        """Route + publish the deferred fused publishes, in arrival order,
        through one batched router call. Synchronous: the single-node
        publish path never awaits, so a flush can run at any point of
        read-batch processing without yielding the event loop (which is
        exactly what makes deferral invisible to other connections)."""
        self._end_ingress()
        entries, self._route_pending = self._route_pending, []
        self.broker.flush_deferred_publishes(
            self.vhost_name, entries, self._confirm_marks)

    async def _batch_barrier(self) -> None:
        """Per-read-batch barrier. When ONLY pipelined remote pushes gate
        this batch's confirms (no local store marks, no sync replication),
        the round trip is offloaded to an ordered background chain and the
        read loop keeps parsing the next batch — read batches pipeline
        through the data plane's per-stream windows instead of stalling
        the whole connection one RTT each. Anything needing the store or
        replication barrier takes the synchronous path below."""
        cluster = self.broker.cluster
        if (self._remote_pending and not self._confirm_marks
                and not self._remote_failures
                and (cluster.replication is None
                     or not cluster.replication.sync)):
            records, self._remote_pending = self._remote_pending, []
            strict, self._remote_strict = self._remote_strict, False
            confirms, self._pending_confirms = self._pending_confirms, {}
            # submit NOW (sync): the RPCs hit the wire while this batch's
            # barrier rides the background chain — successive read batches
            # keep the per-stream in-flight windows full instead of
            # alternating parse / round-trip
            futures = cluster.submit_batch(records)
            prev = self._remote_chain
            self._remote_chain = asyncio.get_event_loop().create_task(
                self._remote_confirm_chain(prev, futures, strict, confirms))
            return
        await self._confirm_barrier()
        self._flush_confirms()

    async def _remote_confirm_chain(
        self, prev: Optional[asyncio.Task], futures: set, strict: bool,
        confirms: dict,
    ) -> None:
        """One offloaded batch: await the previous batch (confirm order —
        a later multiple=true ack would cover an earlier batch's seqs),
        barrier on the already-submitted pushes, then release this batch's
        confirms. A strict failure kills the connection like a failed
        store barrier would — never a false confirm."""
        if prev is not None:
            await prev
        try:
            failures = await self.broker.cluster.await_batch(futures)
        except Exception as exc:  # pragma: no cover - await_batch collects
            failures = [exc]
        if failures:
            if strict:
                log.warning(
                    "remote push failed under confirm barrier: %r; "
                    "dropping connection %d", failures[0], self.id)
                for failure in failures:
                    self._remote_failures.append((failure, False))
                try:
                    self.writer.transport.abort()
                except Exception:
                    pass
                return
            for failure in failures:
                log.warning("remote push failed (best-effort publish): %r",
                            failure)
        if self.closing:
            return
        for channel_id, max_seq in confirms.items():
            if channel_id in self.channels:
                self.send_method(channel_id, am.Basic.Ack(
                    delivery_tag=max_seq, multiple=True))

    async def _confirm_barrier(self) -> None:
        """Durability barrier before releasing publisher confirms: a confirm
        may only reach the client once (a) every pipelined remote queue.push
        of this batch has been accepted by its owner and (b) the store has
        committed every write the confirmed publishes enqueued (message
        blob + queue-log rows — all in one group-commit batch). Free for
        single-node transient traffic: with no remote pushes and no enqueue
        windows recorded, flush([]) resolves immediately."""
        self._end_ingress()
        if self._route_pending:
            # deferred publishes must enqueue their store writes (and
            # record their marks) before the marks are consumed below
            self._flush_route_pending()
        await self._settle_remote_failures()
        if self._pending_confirms:
            intervals, self._confirm_marks = self._confirm_marks, []
            await self.broker.store.flush(intervals)
            cluster = self.broker.cluster
            if (cluster is not None and cluster.replication is not None
                    and cluster.replication.sync):
                # chana.mq.replicate.sync: confirms additionally gate on
                # follower acks, so a confirmed persistent message survives
                # the loss of this whole node (bounded by ack-timeout)
                await cluster.replication.sync_barrier()

    async def _settle_remote_failures(self) -> None:
        """Drain pipelined remote pushes and account for their failures:
        a failure covering a confirm-armed (or tx-commit) publish escalates
        — never acknowledge over a lost remote push; best-effort failures
        just log (shared by the confirm barrier and tx.commit)."""
        if self._remote_pending or self._remote_chain is not None:
            await self._drain_remote()
        if self._remote_failures:
            failures, self._remote_failures = self._remote_failures, []
            strict = next((f for f, s in failures if s), None)
            if strict is not None:
                # never confirm over a lost confirm-armed remote push:
                # drop the connection like a failed store barrier would
                raise RuntimeError(
                    f"remote push failed under confirm barrier: "
                    f"{strict!r}") from strict
            for failure, _ in failures:
                log.warning("remote push failed (best-effort publish): %r",
                            failure)

    async def _drain_remote(self) -> None:
        """Flush buffered remote push records through the data plane,
        awaited to completion — including any offloaded batches still in
        the background chain (in-channel ordering: a basic.get right after
        a publish must see the publish applied on the owner). Failures
        collect for the barrier, tagged with whether a confirm-armed
        publish was in the drained batch (strictness is per-drain: a
        batched RPC can't attribute a failure to individual records)."""
        chain = self._remote_chain
        if chain is not None:
            try:
                await chain
            finally:
                if self._remote_chain is chain:
                    self._remote_chain = None
        records, self._remote_pending = self._remote_pending, []
        strict, self._remote_strict = self._remote_strict, False
        if not records:
            return
        for failure in await self.broker.cluster.push_batch(records):
            self._remote_failures.append((failure, strict))

    def _flush_confirms(self) -> None:
        if not self._pending_confirms:
            return
        with device.span("conn.confirms"):
            for channel_id, max_seq in self._pending_confirms.items():
                if channel_id in self.channels:
                    self.send_method(
                        channel_id,
                        am.Basic.Ack(delivery_tag=max_seq, multiple=True))
            self._pending_confirms.clear()

    # ------------------------------------------------------------------
    # teardown / close
    # ------------------------------------------------------------------

    async def _hard_close(
        self, code: ErrorCode, text: str, class_id: int = 0, method_id: int = 0
    ) -> None:
        await self._confirm_barrier()
        self._flush_confirms()
        if not self.closing:
            self.send_method(0, am.Connection.Close(
                reply_code=int(code), reply_text=text[:255],
                class_id=class_id, method_id=method_id,
            ))
        self.closing = True

    async def _soft_close_channel(self, channel_id: int, exc: ChannelError) -> None:
        """Channel exception: close just the channel (reference behavior for
        404/405/406 soft errors)."""
        await self._confirm_barrier()
        self._flush_confirms()
        self._pending_confirms.pop(channel_id, None)
        channel = self.channels.pop(channel_id, None)
        if channel is not None:
            channel.release_all()
        self._assembler.abort_channel(channel_id)
        self._closing_channels.add(channel_id)
        self.send_method(channel_id, am.Channel.Close(
            reply_code=int(exc.code), reply_text=exc.text[:255],
            class_id=exc.class_id, method_id=exc.method_id,
        ))

    async def close_channel_ack_timeout(self, channel: ServerChannel) -> None:
        """Sweep-detected delivery-ack timeout (chana.mq.consumer.timeout):
        close just the channel — release_all requeues its unacked — with
        the PRECONDITION_FAILED the RabbitMQ consumer_timeout uses."""
        if (self.closing or channel.closed
                or channel.id in self._closing_channels
                or self.channels.get(channel.id) is not channel):
            # already closing (a prior sweep tick's task may still be inside
            # the close barrier), or the id was reused by a NEW channel —
            # never double-close or close a stranger
            return
        await self._soft_close_channel(channel.id, ChannelError(
            ErrorCode.PRECONDITION_FAILED,
            "delivery acknowledgement timeout"))

    async def _teardown(self) -> None:
        self.closing = True
        # commands held at the publisher gate die with the connection: none
        # were executed or confirmed, but their bodies were counted against
        # the memory gauge at hold time and must be released
        if self._held:
            for commands in self._held.values():
                for command in commands:
                    self.broker.account_held(-self._held_cost(command))
            self._held.clear()
            self._held_bytes = 0
            self._park_t0 = None
        # buffered/chained pipelined remote pushes: send them (the broker
        # accepted these publishes pre-teardown; dropping them would lose
        # messages) and log any failures best-effort
        if self._remote_pending or self._remote_chain is not None:
            try:
                await self._drain_remote()
            except Exception as exc:  # pragma: no cover - teardown races
                log.warning("remote drain failed during teardown: %r", exc)
        for failure, _ in self._remote_failures:
            log.warning("remote push failed during teardown: %r", failure)
        self._remote_failures.clear()
        # requeue unacked, detach consumers
        for channel in list(self.channels.values()):
            channel.release_all()
        self.channels.clear()
        # exclusive queues die with the connection (reference:
        # FrameStage.scala:144-153)
        for queue_name in list(self.exclusive_queues):
            try:
                vhost = self.broker.vhosts.get(self.vhost_name)
                if vhost and queue_name in vhost.queues:
                    await self.broker._remove_queue(vhost, vhost.queues[queue_name])
            except Exception:
                log.exception("failed deleting exclusive queue %s", queue_name)
        self.exclusive_queues.clear()
        if self._heartbeat_task:
            self._heartbeat_task.cancel()
        # buffered deliveries die with the connection (same as bytes
        # already in _out): drop the records and their dirty registration
        self._egress_pending.clear()
        self._egress_records = 0
        self._egress_bytes = 0
        self.broker.egress_dirty.discard(self)
        if self._writer_task:
            self._out_event.set()
            try:
                await asyncio.wait_for(self._writer_task, timeout=2)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                self._writer_task.cancel()
        if self._out_pooled:
            # arena slots still riding an unwritten _out (writer died or
            # timed out): return them so the pool doesn't bleed capacity
            enc = self._egress
            for slot in self._out_pooled:
                enc.release(slot)
            self._out_pooled = []
        try:
            self.writer.close()
            await self.writer.wait_closed()
        except Exception:
            pass
        tenant = self.tenant
        if tenant is not None:
            # fold the per-connection counters into the tenant so its
            # published/delivered series stay monotonic across churn
            tenant.conns.discard(self)
            tenant.published_folded += self.published_msgs
            tenant.delivered_folded += self.delivered_msgs
            self.tenant = None
            self._tenant_rated = None
        self.broker.metrics.connections_closed += 1
        bus = events.ACTIVE
        if bus is not None and self._opened:
            bus.emit("connection.closed", {
                "connection": self.id, "vhost": self.vhost_name,
                "user": self.username,
            })
        if not self.closed.done():
            self.closed.set_result(None)

    # ------------------------------------------------------------------
    # heartbeats (reference: FrameStage.scala:100-107,845-851)
    # ------------------------------------------------------------------

    async def _heartbeat_loop(self) -> None:
        interval = self.heartbeat_s
        try:
            while not self.closing:
                await asyncio.sleep(interval / 2)
                now = time.monotonic()
                if now - self._last_send >= interval / 2:
                    self.send_bytes(HEARTBEAT_BYTES)
                if now - self._last_recv > 2 * interval:
                    # no gate exemption: a gated connection keeps being
                    # read (publishes are held, heartbeats refresh the
                    # clock via the bounded read), and a held-cap-full
                    # peer gets only the bounded _park_grace_tick refresh
                    # — so a stale clock here means a genuinely silent
                    # peer, gated or not
                    log.warning("connection %d heartbeat timeout", self.id)
                    self.closing = True
                    self.writer.close()
                    return
        except asyncio.CancelledError:
            pass

    # ------------------------------------------------------------------
    # dispatch
    # ------------------------------------------------------------------

    async def _dispatch(self, command: AMQCommand) -> None:
        method = command.method
        if (self._remote_pending or self._remote_chain is not None) \
                and type(method) is not am.Basic.Publish:
            # any non-publish command may issue an inline remote RPC
            # (basic.get, queue purge/delete/stats, consume) or observe
            # owner-side state: drain the pipelined publishes first —
            # buffered AND chained — so in-channel ordering holds (a get
            # right after a publish must see the publish). Publishes keep
            # buffering — _on_publish handles its own mandatory/immediate
            # drain.
            await self._drain_remote()
        if command.channel in self._closing_channels:
            # discard everything pipelined behind our Channel.Close until the
            # client acknowledges it
            if isinstance(method, (am.Channel.CloseOk, am.Channel.Close)):
                self._closing_channels.discard(command.channel)
                if isinstance(method, am.Channel.Close):
                    self.send_method(command.channel, am.Channel.CloseOk())
            return
        cid = method.CLASS_ID
        if not self._opened and cid != ClassId.CONNECTION:
            raise HardError(
                ErrorCode.COMMAND_INVALID, "connection not open",
                cid, method.METHOD_ID)
        if cid == ClassId.CONNECTION:
            await self._on_connection(command)
        elif cid == ClassId.CHANNEL:
            await self._on_channel(command)
        elif cid == ClassId.EXCHANGE:
            await self._on_exchange(command)
        elif cid == ClassId.QUEUE:
            await self._on_queue(command)
        elif cid == ClassId.BASIC:
            await self._on_basic(command)
        elif cid == ClassId.CONFIRM:
            self._on_confirm(command)
        elif cid == ClassId.TX:
            await self._on_tx(command)
        elif cid == ClassId.ACCESS:
            self.send_method(command.channel, am.Access.RequestOk(ticket=0))
        else:
            raise HardError(
                ErrorCode.COMMAND_INVALID, f"unsupported class {cid}",
                cid, method.METHOD_ID)

    def _channel(self, command: AMQCommand) -> ServerChannel:
        channel = self.channels.get(command.channel)
        if channel is None:
            raise HardError(
                ErrorCode.CHANNEL_ERROR, f"channel {command.channel} not open",
                command.method.CLASS_ID, command.method.METHOD_ID)
        return channel

    # -- connection class --------------------------------------------------

    async def _on_connection(self, command: AMQCommand) -> None:
        method = command.method
        if isinstance(method, am.Connection.StartOk):
            ok = self._authenticate(method.mechanism, bytes(method.response))
            if not ok:
                raise HardError(ErrorCode.ACCESS_REFUSED, "authentication failed")
            self._authenticated = True
            capabilities = (method.client_properties or {}).get("capabilities")
            if isinstance(capabilities, dict):
                self._supports_blocked = bool(
                    capabilities.get("connection.blocked"))
                self._supports_cancel_notify = bool(
                    capabilities.get("consumer_cancel_notify"))
            self.send_method(0, am.Connection.Tune(
                channel_max=self.cfg_channel_max,
                frame_max=self.cfg_frame_max,
                heartbeat=self.cfg_heartbeat,
            ))
        elif isinstance(method, am.Connection.SecureOk):
            raise HardError(ErrorCode.NOT_IMPLEMENTED, "secure-ok unexpected")
        elif isinstance(method, am.Connection.TuneOk):
            self.channel_max = min(method.channel_max or self.cfg_channel_max,
                                   self.cfg_channel_max)
            client_fm = method.frame_max or self.cfg_frame_max
            self.frame_max = max(FRAME_MIN_SIZE, min(client_fm, self.cfg_frame_max))
            self._parser.frame_max = self.frame_max
            # heartbeat 0 on either side disables heartbeats entirely (a
            # client sending tune-ok heartbeat=0 must not be timed out)
            if method.heartbeat == 0 or self.cfg_heartbeat == 0:
                self.heartbeat_s = 0
            else:
                self.heartbeat_s = min(method.heartbeat, self.cfg_heartbeat)
            self._tuned = True
            if self.heartbeat_s > 0:
                self._heartbeat_task = asyncio.create_task(self._heartbeat_loop())
        elif isinstance(method, am.Connection.Open):
            if not self._tuned:
                raise HardError(ErrorCode.COMMAND_INVALID, "tune-ok required first")
            vhost_name = method.virtual_host or "/"
            registry = self.broker.tenancy
            # tenant users are confined to their tenant's vhosts: the
            # effective allowlist view merges the registry over the
            # server-wide map (built per handshake, so POST /admin/tenants
            # takes effect without a listener restart)
            permissions = (self.permissions if registry is None
                           else registry.auth_permissions(self.permissions))
            # allowlist BEFORE existence: a restricted user must not be
            # able to use the error-code difference as a vhost-name oracle
            if (permissions is not None and self.username is not None):
                allowed = permissions.get(self.username)
                # a user absent from the map is unrestricted (allowlists
                # are opt-in per user)
                if allowed is not None and vhost_name not in allowed:
                    raise HardError(
                        ErrorCode.ACCESS_REFUSED,
                        f"user '{self.username}' may not access "
                        f"vhost '{vhost_name}'",
                        method.CLASS_ID, method.METHOD_ID)
            vhost = self.broker.vhosts.get(vhost_name)
            if vhost is None or not vhost.active:
                raise HardError(
                    ErrorCode.INVALID_PATH, f"no vhost '{vhost_name}'",
                    method.CLASS_ID, method.METHOD_ID)
            if registry is not None:
                refusal = registry.connection_refusal(vhost_name)
                if refusal is not None:
                    raise HardError(
                        ErrorCode.NOT_ALLOWED, refusal,
                        method.CLASS_ID, method.METHOD_ID)
                tenant = registry.by_vhost.get(vhost_name)
                if tenant is not None:
                    self.tenant = tenant
                    tenant.conns.add(self)
                    if tenant.rated:
                        self._tenant_rated = tenant
                    if tenant.gated:
                        self._throttled = True  # join an already-gated tenant
                    (self._can_configure, self._can_write,
                     self._can_read) = tenant.acl_for(
                        self.username, vhost_name)
            self.vhost_name = vhost_name
            self._opened = True
            self.send_method(0, am.Connection.OpenOk())
        elif isinstance(method, am.Connection.Close):
            # confirms for publishes pipelined ahead of the close must still
            # reach the client before close-ok
            await self._confirm_barrier()
            self._flush_confirms()
            self.send_method(0, am.Connection.CloseOk())
            self.closing = True
        elif isinstance(method, am.Connection.CloseOk):
            self.closing = True
        elif isinstance(method, (am.Connection.Blocked, am.Connection.Unblocked)):
            pass  # client-to-server blocked notifications: informational
        else:
            raise HardError(
                ErrorCode.COMMAND_INVALID, f"unexpected {method.NAME}",
                method.CLASS_ID, method.METHOD_ID)

    def _authenticate(self, mechanism: str, response: bytes) -> bool:
        """SASL. Without configured users this matches the reference
        (SaslMechanism.scala:6-98 — PLAIN parses user/password but verifies
        nothing; auth unimplemented there, README 'Status'). With
        chana.mq.auth.users configured, PLAIN verifies against the user
        table in constant time and EXTERNAL is refused (EXCEEDS the
        reference). The effective table merges tenant users
        (tenancy/registry.py) over the server-wide map, rebuilt per
        handshake so runtime tenant changes apply immediately."""
        registry = self.broker.tenancy
        users = (self.users if registry is None
                 else registry.auth_users(self.users))
        if mechanism == "PLAIN":
            parts = response.split(b"\x00")
            if len(parts) != 3:
                return False
            if users is None:
                return True
            import hmac

            try:
                user = parts[1].decode("utf-8")
                password = parts[2].decode("utf-8")
            except UnicodeDecodeError:
                return False
            expected = users.get(user)
            # compare even for unknown users so a timing probe can't
            # enumerate the user table
            ok = hmac.compare_digest(
                (expected if expected is not None else "\x00").encode(),
                password.encode())
            if ok and expected is not None:
                self.username = user
                return True
            return False
        if mechanism == "EXTERNAL":
            return users is None
        return False

    # -- channel class -----------------------------------------------------

    async def _on_channel(self, command: AMQCommand) -> None:
        method = command.method
        cid = command.channel
        if isinstance(method, am.Channel.Open):
            if cid == 0 or cid > self.channel_max:
                raise HardError(
                    ErrorCode.CHANNEL_ERROR, f"bad channel id {cid}",
                    method.CLASS_ID, method.METHOD_ID)
            if cid in self.channels:
                raise HardError(
                    ErrorCode.CHANNEL_ERROR, f"channel {cid} already open",
                    method.CLASS_ID, method.METHOD_ID)
            if self.tenant is not None:
                refusal = self.broker.tenancy.channel_refusal(self.tenant)
                if refusal is not None:
                    # connection exception, like RabbitMQ's channel-limit
                    # refusal (530 not-allowed)
                    raise HardError(
                        ErrorCode.NOT_ALLOWED, refusal,
                        method.CLASS_ID, method.METHOD_ID)
            self.channels[cid] = ServerChannel(self, cid)
            self.send_method(cid, am.Channel.OpenOk())
        elif isinstance(method, am.Channel.Flow):
            channel = self._channel(command)
            channel.flow_active = method.active
            self.send_method(cid, am.Channel.FlowOk(active=method.active))
            if method.active:
                for consumer in channel.consumers.values():
                    consumer.queue.schedule_dispatch()
        elif isinstance(method, am.Channel.FlowOk):
            pass
        elif isinstance(method, am.Channel.Close):
            await self._confirm_barrier()
            self._flush_confirms()
            self._pending_confirms.pop(cid, None)
            channel = self.channels.pop(cid, None)
            if channel is not None:
                channel.release_all()
            self._assembler.abort_channel(cid)
            self.send_method(cid, am.Channel.CloseOk())
        elif isinstance(method, am.Channel.CloseOk):
            pass
        else:
            raise HardError(
                ErrorCode.COMMAND_INVALID, f"unexpected {method.NAME}",
                method.CLASS_ID, method.METHOD_ID)

    # -- exchange class (reference: FrameStage.scala:967-1029) -------------

    async def _on_exchange(self, command: AMQCommand) -> None:
        method = command.method
        cid = command.channel
        self._channel(command)
        if (not self._can_configure
                and isinstance(method, (am.Exchange.Declare,
                                        am.Exchange.Delete))):
            self._deny_acl("configure", method)
        if isinstance(method, am.Exchange.Declare):
            self.broker_check_name(method.exchange, method)
            await self.broker.declare_exchange(
                self.vhost_name, method.exchange, method.type,
                passive=method.passive, durable=method.durable,
                auto_delete=method.auto_delete, internal=method.internal,
                arguments=method.arguments,
            )
            if not method.nowait:
                self.send_method(cid, am.Exchange.DeclareOk())
        elif isinstance(method, am.Exchange.Delete):
            await self.broker.delete_exchange(
                self.vhost_name, method.exchange, if_unused=method.if_unused)
            if not method.nowait:
                self.send_method(cid, am.Exchange.DeleteOk())
        elif isinstance(method, am.Exchange.Bind):
            # exchange-to-exchange bindings (EXCEEDS the reference, which
            # stubs these with a TODO log, FrameStage.scala:1023-1027)
            await self.broker.bind_exchange(
                self.vhost_name, method.destination, method.source,
                method.routing_key, method.arguments)
            if not method.nowait:
                self.send_method(cid, am.Exchange.BindOk())
        elif isinstance(method, am.Exchange.Unbind):
            await self.broker.unbind_exchange(
                self.vhost_name, method.destination, method.source,
                method.routing_key, method.arguments)
            if not method.nowait:
                self.send_method(cid, am.Exchange.UnbindOk())
        else:
            raise HardError(
                ErrorCode.COMMAND_INVALID, f"unexpected {method.NAME}",
                method.CLASS_ID, method.METHOD_ID)

    def broker_check_name(self, name: str, method: am.Method) -> None:
        if len(name) > 255:
            raise ChannelError(
                ErrorCode.PRECONDITION_FAILED, "name too long",
                method.CLASS_ID, method.METHOD_ID)

    # -- queue class (reference: FrameStage.scala:1031-1149) ---------------

    async def _on_queue(self, command: AMQCommand) -> None:
        method = command.method
        cid = command.channel
        self._channel(command)
        if (not self._can_configure
                and isinstance(method, (am.Queue.Declare, am.Queue.Delete))):
            self._deny_acl("configure", method)
        if isinstance(method, am.Queue.Declare):
            name = method.queue
            if not name:
                name = f"tmp.{uuid.uuid4()}"
            self.broker_check_name(name, method)
            cluster = self.broker.cluster
            vhost_obj = self.broker.vhost(self.vhost_name)
            if (cluster is not None and not method.exclusive
                    and name not in vhost_obj.queues  # local (e.g. exclusive) wins
                    and not cluster.owns_queue(self.vhost_name, name)):
                # clustered queue owned elsewhere: proxy to the owner
                if method.passive:
                    if (self.vhost_name, name) not in cluster.queue_metas:
                        raise ChannelError(
                            ErrorCode.NOT_FOUND, f"no queue '{name}'",
                            method.CLASS_ID, method.METHOD_ID)
                    counts = await cluster.remote_stats(self.vhost_name, name)
                else:
                    reply = await cluster.remote_declare(
                        self.vhost_name, name,
                        durable=method.durable, auto_delete=method.auto_delete,
                        arguments=method.arguments)
                    counts = (int(reply["message_count"]),
                              int(reply["consumer_count"]))
                if not method.nowait:
                    self.send_method(cid, am.Queue.DeclareOk(
                        queue=name, message_count=counts[0],
                        consumer_count=counts[1]))
                return
            queue = await self.broker.declare_queue(
                self.vhost_name, name,
                passive=method.passive, durable=method.durable,
                exclusive_owner=self.id if method.exclusive else None,
                auto_delete=method.auto_delete, arguments=method.arguments,
                connection_id=self.id,
            )
            if method.exclusive:
                self.exclusive_queues.add(name)
            if not method.nowait:
                self.send_method(cid, am.Queue.DeclareOk(
                    queue=name,
                    message_count=queue.message_count,
                    consumer_count=queue.consumer_count,
                ))
        elif isinstance(method, am.Queue.Bind):
            await self.broker.bind_queue(
                self.vhost_name, method.queue, method.exchange,
                method.routing_key, method.arguments, connection_id=self.id)
            if not method.nowait:
                self.send_method(cid, am.Queue.BindOk())
        elif isinstance(method, am.Queue.Unbind):
            await self.broker.unbind_queue(
                self.vhost_name, method.queue, method.exchange,
                method.routing_key, method.arguments, connection_id=self.id)
            self.send_method(cid, am.Queue.UnbindOk())
        elif isinstance(method, am.Queue.Purge):
            site, queue = self.broker.queue_site(
                self.vhost_name, method.queue, self.id)
            if site == "local":
                count = queue.purge()
            elif site == "activate":
                activated = await self.broker.activate_queue(
                    self.vhost_name, method.queue)
                count = activated.purge() if activated else 0
            elif site == "remote":
                count = await self.broker.cluster.remote_purge(
                    self.vhost_name, method.queue)
            else:
                raise ChannelError(
                    ErrorCode.NOT_FOUND, f"no queue '{method.queue}'",
                    method.CLASS_ID, method.METHOD_ID)
            if not method.nowait:
                self.send_method(cid, am.Queue.PurgeOk(message_count=count))
        elif isinstance(method, am.Queue.Delete):
            count = await self.broker.delete_queue(
                self.vhost_name, method.queue,
                if_unused=method.if_unused, if_empty=method.if_empty,
                connection_id=self.id)
            self.exclusive_queues.discard(method.queue)
            if not method.nowait:
                self.send_method(cid, am.Queue.DeleteOk(message_count=count))
        else:
            raise HardError(
                ErrorCode.COMMAND_INVALID, f"unexpected {method.NAME}",
                method.CLASS_ID, method.METHOD_ID)

    # -- basic class -------------------------------------------------------

    async def _on_basic(self, command: AMQCommand) -> None:
        method = command.method
        cid = command.channel
        channel = self._channel(command)
        if isinstance(method, am.Basic.Publish):
            await self._on_publish(channel, command)
        elif isinstance(method, am.Basic.Qos):
            channel.set_qos(method.prefetch_size, method.prefetch_count, method.global_)
            self.send_method(cid, am.Basic.QosOk())
        elif isinstance(method, am.Basic.Consume):
            await self._on_consume(channel, method)
        elif isinstance(method, am.Basic.Cancel):
            consumer = channel.consumers.pop(method.consumer_tag, None)
            if consumer is not None:
                from ..cluster.node import RemoteQueueRef

                if isinstance(consumer.queue, RemoteQueueRef):
                    await self.broker.cluster.remote_cancel(
                        consumer.queue.vhost, consumer.queue.name, consumer.tag)
                else:
                    auto_deleted = consumer.queue.remove_consumer(consumer)
                    if auto_deleted:
                        self.broker.schedule_queue_delete(
                            self.vhost_name, consumer.queue.name)
            if not method.nowait:
                self.send_method(cid, am.Basic.CancelOk(
                    consumer_tag=method.consumer_tag))
        elif isinstance(method, am.Basic.Get):
            await self._on_get(channel, method)
        elif isinstance(method, am.Basic.Ack):
            deliveries = channel.resolve_tags(method.delivery_tag, method.multiple)
            self._check_settled_tags(channel, method, deliveries)
            if channel.mode is ChannelMode.TX:
                self._tx_stash_settles(channel, "ack", deliveries)
            else:
                self._ack_all(channel, deliveries)
        elif isinstance(method, am.Basic.Nack):
            deliveries = channel.resolve_tags(method.delivery_tag, method.multiple)
            self._check_settled_tags(channel, method, deliveries)
            self._settle_negative(channel, deliveries, method.requeue)
        elif isinstance(method, am.Basic.Reject):
            deliveries = channel.resolve_tags(method.delivery_tag, False)
            self._check_settled_tags(channel, method, deliveries, multiple=False)
            self._settle_negative(channel, deliveries, method.requeue)
        elif isinstance(method, (am.Basic.Recover, am.Basic.RecoverAsync)):
            self._on_recover(channel, method.requeue)
            if isinstance(method, am.Basic.Recover):
                self.send_method(cid, am.Basic.RecoverOk())
        else:
            raise HardError(
                ErrorCode.COMMAND_INVALID, f"unexpected {method.NAME}",
                method.CLASS_ID, method.METHOD_ID)

    @staticmethod
    def _tx_stash_settles(
        channel: ServerChannel, kind: str, deliveries: list
    ) -> None:
        for delivery in deliveries:
            channel.tx_stash_settle(kind, delivery)

    def _settle_negative(
        self, channel: ServerChannel, deliveries: list, requeue: bool
    ) -> None:
        """Shared nack/reject settle: requeue or drop, buffered on a tx
        channel (the two methods differ only in how tags were resolved)."""
        if channel.mode is ChannelMode.TX:
            self._tx_stash_settles(
                channel, "requeue" if requeue else "drop", deliveries)
        else:
            for delivery in deliveries:
                if requeue:
                    channel.requeue(delivery)
                else:
                    channel.drop(delivery)

    @staticmethod
    def _check_settled_tags(
        channel: ServerChannel, method, deliveries: list,
        multiple: Optional[bool] = None,
    ) -> None:
        """Ack/Nack/Reject tag validation (RabbitMQ contract): an unknown
        tag is a channel PRECONDITION_FAILED, not a silent no-op. With
        multiple=true a tag never issued on this channel (above the
        delivery-tag counter) is equally unknown; a tag inside the issued
        range whose deliveries are already settled is a legal no-op.
        multiple overrides method.multiple for methods without the field
        (Reject)."""
        AMQPConnection._check_settled_raw(
            channel, deliveries, method.delivery_tag,
            method.multiple if multiple is None else multiple,
            method.CLASS_ID, method.METHOD_ID)

    @staticmethod
    def _check_settled_raw(
        channel: ServerChannel, deliveries: list, tag: int, multiple: bool,
        class_id: int, method_id: int,
    ) -> None:
        if deliveries:
            return
        if not multiple or (tag != 0 and not channel.tag_was_issued(tag)):
            raise ChannelError(
                ErrorCode.PRECONDITION_FAILED,
                f"unknown delivery tag {tag}", class_id, method_id)

    def _fused_ack(self, raw, off: int, channel_id: int) -> int:
        """basic.ack straight off the scan arrays (payload is exactly
        class+method+tag8+bits1 = 13 bytes, no content follows): same
        resolve/validate/settle steps as the generic Basic.Ack arm, minus
        the Frame/Method/AMQCommand/coroutine scaffolding. Returns 1 when
        handled, 0 to fall back (unknown channel: the generic path raises
        the proper channel error)."""
        channel = self.channels.get(channel_id)
        if channel is None:
            return 0
        if channel.mode is ChannelMode.TX:
            return 0  # transactional ack: generic path buffers it
        tag = int.from_bytes(raw[off + 4:off + 12], "big")
        multiple = raw[off + 12] & 1 == 1
        self._fused_skip = 1
        deliveries = channel.resolve_tags(tag, multiple)
        self._check_settled_raw(channel, deliveries, tag, multiple, 60, 80)
        self._ack_all(channel, deliveries)
        return 1

    def _ack_run(self, raw, i: int, n: int, types, channels, offsets,
                 lengths) -> int:
        """basic.ack frame i of a scan batch and the frames after it,
        settled as one run: each METHOD frame of the same channel that is
        a 13-byte basic.ack with `multiple` 0 and a tag in `unacked` joins
        it. Each delivery is settled as ServerChannel.ack -> Queue.ack
        settles it: popped from `unacked` and `queue.outstanding`, the
        queue's `n_acked`, the message's reference. What they write per
        ack the run writes once: the ack counters, `queue_unacked`, the
        consumer's prefetch budget (once a stretch of one consumer, clamped
        as _release_budget clamps), the released bytes in one
        account_memory while their sum stays under
        Broker._memory_room_down (DispatchDrain.close's rule), one
        schedule_dispatch a queue in first-ack order, one pair of clock
        reads into `settle_ns`. The run ends before a frame it does not
        take: another frame or channel, `multiple`, an unknown tag, a
        queue that is not plain (stream, replicated, remote), a persisted
        or paged message, the release that would reach that room. Such a
        frame at the run's start, and every frame under a TX channel, a
        trace sampler or the profiler, goes to _fused_ack. Returns the
        frames consumed."""
        channel_id = channels[i]
        channel = self.channels.get(channel_id)
        if (channel is None or channel.mode is ChannelMode.TX
                or trace.ACTIVE is not None or profile.ACTIVE is not None):
            return self._fused_ack(raw, offsets[i], channel_id)
        broker = self.broker
        unacked = channel.unacked
        consumers = channel.consumers
        unpack = _ACK_FRAME.unpack_from
        keep = broker._memory_room_down()
        t0 = time.perf_counter_ns()
        queues: dict = {}
        freed = settled = held = held_size = 0
        ctag = consumer = None
        j = i
        while j < n:
            if j != i and (types[j] != 1 or channels[j] != channel_id
                           or lengths[j] != 13):
                break
            sig, tag, bits = unpack(raw, offsets[j])
            if sig != _ACK_METHOD or bits & 1:
                break
            delivery = unacked.get(tag)
            if delivery is None:
                break
            queue = delivery.queue
            if not getattr(queue, "plain", False):
                break
            qm = delivery.queued
            msg = qm.message
            if msg.persisted or msg.paged:
                break
            left = msg.refer_count - 1
            if left <= 0 and msg.accounted:
                size = len(msg.body or b"")
                if size >= keep:
                    break
                keep -= size
                freed += size
                msg.accounted = False
            msg.refer_count = left
            del unacked[tag]
            if delivery.consumer_tag != ctag:
                if consumer is not None:
                    consumer.release(held, held_size)
                ctag = delivery.consumer_tag
                consumer = consumers.get(ctag)
                held = held_size = 0
            held += 1
            held_size += qm.body_size
            if queue.outstanding.pop(qm.offset, None) is not None:
                settled += 1
            queue.n_acked += 1
            queues[queue] = None
            j += 1
        if j == i:
            return self._fused_ack(raw, offsets[i], channel_id)
        count = j - i
        if consumer is not None:
            consumer.release(held, held_size)
        self.acked_msgs += count
        metrics = broker.metrics
        metrics.acked_msgs += count
        metrics.ack_runs += 1
        metrics.ack_run_msgs += count
        broker.queue_unacked -= settled
        if freed:
            broker.account_memory(-freed)
        for queue in queues:
            queue.schedule_dispatch()
        metrics.settle_ns += time.perf_counter_ns() - t0
        return count

    def _ack_all(self, channel: ServerChannel, deliveries: list) -> None:
        """Settle what one Basic.Ack frame covers (`multiple` included),
        timed as one: `settle_ns` over `acked_msgs` is the settle path's
        wall an acknowledged delivery. A counter and no profiler span: a
        fused ack is handled inside `conn.ingress`, and spans stay flat."""
        t0 = time.perf_counter_ns()
        for delivery in deliveries:
            channel.ack(delivery)
        self.broker.metrics.settle_ns += time.perf_counter_ns() - t0

    def _arm_confirm(self, channel: ServerChannel) -> Optional[int]:
        self._has_published = True
        self.published_msgs += 1
        if channel.mode == ChannelMode.CONFIRM:
            channel.publish_seq += 1
            return channel.publish_seq
        return None

    def _publish_aftermath(
        self, channel: ServerChannel, command: AMQCommand,
        props: BasicProperties, routed: bool, deliverable: bool,
        seq: Optional[int],
    ) -> None:
        method = command.method
        if not routed and method.mandatory:
            self.broker.metrics.returned_msgs += 1
            self.send_command(AMQCommand(
                channel.id,
                am.Basic.Return(
                    reply_code=int(ErrorCode.NO_ROUTE), reply_text="NO_ROUTE",
                    exchange=method.exchange, routing_key=method.routing_key),
                props, command.body, header_raw=command.header_raw))
        elif not deliverable and method.immediate:
            self.broker.metrics.returned_msgs += 1
            self.send_command(AMQCommand(
                channel.id,
                am.Basic.Return(
                    reply_code=int(ErrorCode.NO_CONSUMERS), reply_text="NO_CONSUMERS",
                    exchange=method.exchange, routing_key=method.routing_key),
                props, command.body, header_raw=command.header_raw))
        if seq is not None:
            # coalesce: publish seqs are contiguous per channel and commands
            # are processed in order, so one Basic.Ack(multiple=true) with the
            # batch's max seq confirms everything processed this read batch
            # (reference: the run-length logic at FrameStage.scala:571-596)
            self._pending_confirms[channel.id] = seq
            self.broker.metrics.confirmed_msgs += 1

    def _try_fast_publish(self, command: AMQCommand) -> bool:
        """Per-message hot loop: a single-node Basic.Publish involves no
        awaits anywhere (broker.publish's local branch is plain calls), so
        handling it as a plain call skips three coroutine constructions per
        message (_dispatch → _on_basic → _on_publish). Falls back to the
        full async path (returns False) for anything unusual so error
        semantics stay in one place."""
        method = command.method
        if (type(method) is not am.Basic.Publish
                or self.broker.cluster is not None
                or self._closing_channels
                or not self._opened
                or not self._can_write):
            return False
        channel = self.channels.get(command.channel)
        if channel is None:
            return False  # full path raises the proper channel error
        if channel.mode is ChannelMode.TX:
            return False  # transactional publish: _on_publish buffers it
        props = command.properties or BasicProperties()
        self._tenant_spend(len(command.body or b""))
        seq = self._arm_confirm(channel)
        routed, deliverable = self.broker.publish_sync(
            self.vhost_name, method.exchange, method.routing_key,
            props, command.body,
            mandatory=method.mandatory, immediate=method.immediate,
            header_raw=command.header_raw,
            marks=self._confirm_marks if seq is not None else None,
            exrk_raw=method._values.get("exrk_raw"),
        )
        self._publish_aftermath(channel, command, props, routed, deliverable, seq)
        return True

    async def _on_publish(self, channel: ServerChannel, command: AMQCommand) -> None:
        if not self._can_write:
            self._deny_acl("write", command.method)
        if channel.mode is ChannelMode.TX:
            # transactional publish: buffer until tx.commit. The body counts
            # against the broker memory gate while parked (a flood inside a
            # never-committed tx must not be invisible to backpressure).
            self._has_published = True
            channel.tx_ops.append(("publish", command))
            channel.tx_bytes += len(command.body)
            self.broker.account_memory(len(command.body))
            return
        method = command.method
        if (method.mandatory or method.immediate) and (
                self._remote_pending or self._remote_chain is not None):
            # a mandatory/immediate publish awaits its remote push inline:
            # drain the buffered pipeline first so per-queue FIFO holds
            await self._drain_remote()
        props = command.properties or BasicProperties()
        self._tenant_spend(len(command.body or b""))
        seq = self._arm_confirm(channel)
        buffered_before = len(self._remote_pending)
        routed, deliverable = await self.broker.publish(
            self.vhost_name, method.exchange, method.routing_key,
            props, command.body,
            mandatory=method.mandatory, immediate=method.immediate,
            header_raw=command.header_raw,
            marks=self._confirm_marks if seq is not None else None,
            exrk_raw=method._values.get("exrk_raw"),
            pending=self._remote_pending,
        )
        if seq is not None and len(self._remote_pending) > buffered_before:
            self._remote_strict = True
        self._publish_aftermath(channel, command, props, routed, deliverable, seq)

    def _deny_acl(self, perm: str, method: am.Method) -> None:
        """ACL denial -> AMQP access-refused (403, soft): the channel
        closes, the connection survives (RabbitMQ's mapping)."""
        self.broker.metrics.tenancy_acl_denials_total += 1
        raise ChannelError(
            ErrorCode.ACCESS_REFUSED,
            f"ACL: user '{self.username}' lacks {perm} permission on "
            f"vhost '{self.vhost_name}'",
            method.CLASS_ID, method.METHOD_ID)

    async def _on_consume(self, channel: ServerChannel, method: am.Basic.Consume) -> None:
        if not self._can_read:
            self._deny_acl("read", method)
        tag = method.consumer_tag or f"ctag-{self.id}-{channel.id}-{len(channel.consumers) + 1}"
        if tag in channel.consumers:
            raise ChannelError(
                ErrorCode.NOT_ALLOWED, f"consumer tag '{tag}' in use",
                method.CLASS_ID, method.METHOD_ID)
        # validated up front so local and remotely-owned queues agree
        x_priority = (method.arguments or {}).get("x-priority")
        if x_priority is not None and not isinstance(x_priority, int):
            raise ChannelError(
                ErrorCode.PRECONDITION_FAILED, "invalid x-priority",
                method.CLASS_ID, method.METHOD_ID)
        site, queue = self.broker.queue_site(self.vhost_name, method.queue, self.id)
        if site == "activate":
            queue = await self.broker.activate_queue(self.vhost_name, method.queue)
            site = "local" if queue is not None else "none"
        if site == "remote":
            if method.exclusive:
                raise ChannelError(
                    ErrorCode.NOT_IMPLEMENTED,
                    "exclusive consumers on remotely-owned queues",
                    method.CLASS_ID, method.METHOD_ID)
            # credit window: the client's prefetch if it set one, else the
            # cluster's pipelined consume window
            # (chana.mq.cluster.consume-credit)
            prefetch = (channel.prefetch_count_consumer
                        or channel.prefetch_count_global or 0)
            credit = min(prefetch, self.broker.cluster.consume_credit) \
                if prefetch else self.broker.cluster.consume_credit
            await self.broker.cluster.remote_consume(
                channel, self.vhost_name, method.queue, tag,
                method.no_ack, credit, priority=int(x_priority or 0))
            if not method.nowait:
                self.send_method(channel.id, am.Basic.ConsumeOk(consumer_tag=tag))
            return
        if site == "none":
            raise ChannelError(
                ErrorCode.NOT_FOUND, f"no queue '{method.queue}'",
                method.CLASS_ID, method.METHOD_ID)
        if queue.has_exclusive_consumer() or (method.exclusive and queue.consumers):
            raise ChannelError(
                ErrorCode.ACCESS_REFUSED,
                f"queue '{queue.name}' has an exclusive consumer",
                method.CLASS_ID, method.METHOD_ID)
        if queue.is_stream:
            # attach position must be parseable BEFORE ConsumeOk goes out —
            # a post-Ok failure would leave the client believing it is
            # subscribed
            from ..streams import parse_offset_spec, validate_group_args

            try:
                parse_offset_spec(
                    (method.arguments or {}).get("x-stream-offset"))
            except ValueError as exc:
                raise ChannelError(
                    ErrorCode.PRECONDITION_FAILED, str(exc),
                    method.CLASS_ID, method.METHOD_ID) from None
            group_err = validate_group_args(queue, method.arguments)
            if group_err is not None:
                raise ChannelError(
                    ErrorCode.PRECONDITION_FAILED, group_err,
                    method.CLASS_ID, method.METHOD_ID)
        elif (method.arguments or {}).get("x-group") is not None:
            raise ChannelError(
                ErrorCode.PRECONDITION_FAILED,
                "x-group requires a stream queue (x-queue-type: stream)",
                method.CLASS_ID, method.METHOD_ID)
        consumer = Consumer(
            tag, channel, queue, method.no_ack, method.exclusive, method.arguments)
        channel.consumers[tag] = consumer
        if not method.nowait:
            self.send_method(channel.id, am.Basic.ConsumeOk(consumer_tag=tag))
        queue.add_consumer(consumer)

    async def _on_get(self, channel: ServerChannel, method: am.Basic.Get) -> None:
        if not self._can_read:
            self._deny_acl("read", method)
        site, queue = self.broker.queue_site(self.vhost_name, method.queue, self.id)
        if site == "activate":
            queue = await self.broker.activate_queue(self.vhost_name, method.queue)
            site = "local" if queue is not None else "none"
        if site == "remote":
            await self._on_get_remote(channel, method)
            return
        if site == "none":
            raise ChannelError(
                ErrorCode.NOT_FOUND, f"no queue '{method.queue}'",
                method.CLASS_ID, method.METHOD_ID)
        qm = await queue.basic_get()
        if qm is None:
            self.send_method(channel.id, am.Basic.GetEmpty())
            return
        tag = channel.next_delivery_tag()
        msg = qm.message
        self.send_command(AMQCommand(
            channel.id,
            am.Basic.GetOk(
                delivery_tag=tag, redelivered=qm.redelivered,
                exchange=msg.exchange, routing_key=msg.routing_key,
                message_count=queue.message_count),
            msg.properties, msg.body))
        self.delivered_msgs += 1
        self.broker.metrics.delivered(len(msg.body))
        if method.no_ack:
            self.broker.unrefer(msg)
        else:
            from .entities import Delivery

            delivery = Delivery(qm, queue, channel, "", tag, no_ack=False)
            channel.unacked[tag] = delivery
            queue.note_outstanding(delivery)
            if queue.durable and msg.persisted:
                # mirror the consume dispatch path: the unacked message must
                # survive a restart
                self.broker.store.insert_queue_unacks_nowait(
                    queue.vhost, queue.name,
                    [(msg.id, qm.offset, qm.body_size, qm.expire_at_ms)])
                if queue.repl is not None:
                    queue.repl.append("unacks", {"rows": [
                        [msg.id, qm.offset, qm.body_size, qm.expire_at_ms]]})

    async def _on_get_remote(self, channel: ServerChannel, method: am.Basic.Get) -> None:
        """basic.get on a remotely-owned queue: fetch one message over RPC
        and account for it locally like any other unacked delivery."""
        from ..cluster.node import RemoteQueueRef
        from .entities import Delivery, Message, QueuedMessage

        reply = await self.broker.cluster.remote_get(
            self.vhost_name, method.queue, method.no_ack)
        if reply.get("empty"):
            self.send_method(channel.id, am.Basic.GetEmpty())
            return
        _, _, props = BasicProperties.decode_header(bytes(reply["props_raw"]))
        message = Message(
            int(reply["msg_id"]), props, bytes(reply["body"]),
            str(reply["exchange"]), str(reply["routing_key"]))
        qm = QueuedMessage(message, int(reply["offset"]), reply.get("expire_at_ms"))
        qm.redelivered = bool(reply.get("redelivered"))
        tag = channel.next_delivery_tag()
        self.send_command(AMQCommand(
            channel.id,
            am.Basic.GetOk(
                delivery_tag=tag, redelivered=qm.redelivered,
                exchange=message.exchange, routing_key=message.routing_key,
                message_count=int(reply.get("message_count", 0))),
            message.properties, message.body))
        self.delivered_msgs += 1
        self.broker.metrics.delivered(len(message.body))
        if not method.no_ack:
            ref = RemoteQueueRef(self.broker.cluster, self.vhost_name, method.queue)
            channel.unacked[tag] = Delivery(qm, ref, channel, "", tag, no_ack=False)  # type: ignore[arg-type]

    def _on_recover(self, channel: ServerChannel, requeue: bool) -> None:
        """reference: FrameStage.scala:711-776."""
        if requeue:
            # highest tag first -> requeue's appendleft fast path
            for tag in sorted(channel.unacked, reverse=True):
                channel.requeue(channel.unacked[tag])
        else:
            for tag in sorted(channel.unacked):
                channel.redeliver(channel.unacked[tag])

    # -- confirm / tx ------------------------------------------------------

    def _on_confirm(self, command: AMQCommand) -> None:
        method = command.method
        channel = self._channel(command)
        if isinstance(method, am.Confirm.Select):
            if channel.mode == ChannelMode.TX:
                raise ChannelError(
                    ErrorCode.PRECONDITION_FAILED, "channel is transactional",
                    method.CLASS_ID, method.METHOD_ID)
            channel.mode = ChannelMode.CONFIRM
            if not method.nowait:
                self.send_method(command.channel, am.Confirm.SelectOk())
        else:
            raise HardError(
                ErrorCode.COMMAND_INVALID, f"unexpected {method.NAME}",
                method.CLASS_ID, method.METHOD_ID)

    async def _on_tx(self, command: AMQCommand) -> None:
        """tx class with real transactional semantics (EXCEEDS the
        reference, which stubs tx.* with TODO logs,
        FrameStage.scala:1261-1272). tx.select flips the channel into
        transactional mode; publishes and ack/nack/reject buffer in order
        until tx.commit replays them behind the same durability barrier
        publisher confirms use, or tx.rollback discards them. Per 0-9-1,
        rollback returns settled-in-tx deliveries to the unacked set
        WITHOUT redelivering — a client wanting redelivery issues
        basic.recover."""
        method = command.method
        channel = self._channel(command)
        cid = command.channel
        if isinstance(method, am.Tx.Select):
            if channel.mode is ChannelMode.CONFIRM:
                # confirm and tx are mutually exclusive (RabbitMQ contract;
                # mirror of the guard in _on_confirm)
                raise ChannelError(
                    ErrorCode.PRECONDITION_FAILED, "channel is in confirm mode",
                    method.CLASS_ID, method.METHOD_ID)
            channel.mode = ChannelMode.TX
            self.send_method(cid, am.Tx.SelectOk())
        elif isinstance(method, am.Tx.Commit):
            self._require_tx(channel, method)
            await self._tx_commit(channel)
            self.send_method(cid, am.Tx.CommitOk())
        elif isinstance(method, am.Tx.Rollback):
            self._require_tx(channel, method)
            n_ops = len(channel.tx_ops)
            channel.tx_rollback()
            self.broker.metrics.semantics_tx_rollbacks += 1
            bus = events.ACTIVE
            if bus is not None:
                bus.emit("tx.rolledback", {
                    "vhost": self.vhost_name, "channel": channel.id,
                    "ops": n_ops,
                }, vhost_name=self.vhost_name)
            self.send_method(cid, am.Tx.RollbackOk())
        else:
            raise HardError(
                ErrorCode.COMMAND_INVALID, f"unexpected {method.NAME}",
                method.CLASS_ID, method.METHOD_ID)

    @staticmethod
    def _require_tx(channel: ServerChannel, method: am.Method) -> None:
        if channel.mode is not ChannelMode.TX:
            raise ChannelError(
                ErrorCode.PRECONDITION_FAILED, "channel is not transactional",
                method.CLASS_ID, method.METHOD_ID)

    async def _tx_commit(self, channel: ServerChannel) -> None:
        """Replay the buffered ops in arrival order. Mandatory/immediate
        Basic.Returns render before Tx.CommitOk (RabbitMQ ordering), and
        CommitOk is only sent after (a) every clustered push the replay
        buffered has been accepted by its owner and (b) the store has
        committed every persistent write the replay enqueued — the same
        promise a publisher confirm makes, per-op mark windows included.

        Single-node on a WalStore, the whole replay runs inside a WAL
        transaction scope: every persistent write the commit enqueues is
        sealed into ONE tx_batch record, so a SIGKILL between Tx.Commit
        receipt and the WAL fsync replays all-or-nothing — a group-commit
        batch of separate records can tear at record granularity and leave
        a durable prefix of the transaction. The replay loop itself never
        suspends on this path (publish() degenerates to publish_sync and
        settles are plain calls), which is what keeps the scope atomic
        with respect to the commit loop and checkpointer."""
        ops, channel.tx_ops = channel.tx_ops, []
        if channel.tx_bytes:
            self.broker.account_memory(-channel.tx_bytes)
            channel.tx_bytes = 0
        prof = profile.ACTIVE
        t_tx = time.perf_counter_ns() if prof is not None else 0
        store = self.broker.store
        scoped = (self.broker.cluster is None
                  and getattr(store, "tx_begin", None) is not None)
        marks: list[tuple[int, int]] = []
        touched: list = []
        federation = self.broker.federation
        staged_federated: list = []
        mark0 = 0
        if scoped:
            mark0 = store.mark()
            store.tx_begin()
        idx = 0
        try:
            while idx < len(ops):
                op = ops[idx]
                if op[0] == "publish":
                    pub = op[1]
                    method = pub.method
                    if ((method.mandatory or method.immediate)
                            and (self._remote_pending
                                 or self._remote_chain is not None)):
                        # same guard as _on_publish: a mandatory/immediate
                        # publish awaits its remote push inline, so drain
                        # the buffered pipeline first to keep per-queue FIFO
                        await self._drain_remote()
                    props = pub.properties or BasicProperties()
                    buffered_before = len(self._remote_pending)
                    routed, deliverable = await self.broker.publish(
                        self.vhost_name, method.exchange, method.routing_key,
                        props, pub.body,
                        mandatory=method.mandatory, immediate=method.immediate,
                        header_raw=pub.header_raw, marks=marks,
                        exrk_raw=method._values.get("exrk_raw"),
                        pending=self._remote_pending)
                    if len(self._remote_pending) > buffered_before:
                        # a commit-replayed push is always strict: a lost
                        # remote push must fail the commit, never be
                        # silently dropped
                        self._remote_strict = True
                    self._publish_aftermath(
                        channel, pub, props, routed, deliverable, None)
                    if federation is not None:
                        # federated Tx: stage the publish for the link
                        # boundary; the whole staging ships as ONE batch
                        # only after this commit succeeds locally
                        staged_federated.append((
                            method.exchange, method.routing_key,
                            pub.header_raw
                            or props.encode_header(len(pub.body)),
                            pub.body))
                else:
                    kind, delivery = op
                    channel.tx_release_held(delivery)
                    before = store.mark()
                    if kind == "ack":
                        channel.ack(delivery)
                    elif kind == "requeue":
                        channel.requeue(delivery)
                    else:
                        channel.drop(delivery)
                    if scoped:
                        # the settle buffered its unack delete / watermark
                        # for the next loop tick — pull it into the open
                        # scope so staged acks commit atomically with the
                        # staged publishes
                        queue = delivery.queue
                        if queue not in touched:
                            touched.append(queue)
                    else:
                        # the settle path never awaits, so this window
                        # covers exactly the deletes this settle enqueued
                        marks.append((before, store.mark()))
                idx += 1
            if scoped:
                for queue in touched:
                    queue.flush_store_buffers()
        except BaseException:
            # partial-commit failure (e.g. a replayed publish hit a deleted
            # exchange): the error closes the channel, but ops not yet
            # applied must not vanish — parked settles return to unacked so
            # the channel teardown requeues their deliveries. The failed op
            # itself is consumed (a raising publish routed nowhere; settles
            # never raise); later publishes drop, matching implicit-rollback
            # semantics. An open WAL scope aborts whole: the client never
            # got CommitOk, so nothing from this transaction may become
            # durable (no partial replay on recovery). Settle bookkeeping
            # still buffered on the queues is NOT pulled in — it flushes
            # on the next loop tick, outside the aborted scope, so applied
            # settles keep their durable records.
            if scoped:
                store.tx_abort()
            channel.tx_restore_settles(ops[idx + 1:])
            raise
        if scoped:
            lsn = store.tx_seal()
            if lsn > mark0:
                marks = [(mark0, lsn)]
        if prof is not None:
            # staged replay, scope open -> sealed; the awaited flush below
            # is group-commit wall time and lands in WAL_COMMIT already
            prof.stage_ns[profile.TX_COMMIT] += time.perf_counter_ns() - t_tx
            prof.stage_calls[profile.TX_COMMIT] += 1
        self.broker.metrics.semantics_tx_commits += 1
        bus = events.ACTIVE
        if bus is not None:
            bus.emit("tx.committed", {
                "vhost": self.vhost_name, "channel": channel.id,
                "ops": len(ops), "atomic": scoped,
            }, vhost_name=self.vhost_name)
        await self._settle_remote_failures()
        await store.flush(marks)
        if federation is not None and staged_federated:
            # the commit is durable locally (the WAL flush above
            # succeeded): only now hand each link its slice as one
            # all-or-nothing batch — staging any earlier could ship a
            # batch the local cluster never durably committed, leaving
            # the clusters diverged with remote-only messages. Links
            # with no matching exchange see nothing; a down link stages
            # and ships after heal.
            federation.stage_tx_batch(self.vhost_name, staged_federated)
