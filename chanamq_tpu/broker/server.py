"""AMQP/AMQPS TCP listener.

Capability parity with the reference's transport extension + process entry
(chana-mq-base Amqp.scala:39-331 startServer/sslTlsStage; chana-mq-server
AMQPServer.scala:39-111): plain AMQP listener (5672), optional TLS listener
(5671), per-connection protocol engine instances, clean shutdown.

Run standalone:  python -m chanamq_tpu.broker.server [--port 5672]
"""

from __future__ import annotations

import asyncio
import logging
import os
import ssl
from typing import Optional

from .. import loopbooks
from ..store.api import StoreService
from .broker import Broker
from .connection import AMQPConnection

log = logging.getLogger("chanamq.server")


class BrokerServer:
    def __init__(
        self,
        broker: Optional[Broker] = None,
        host: str = "0.0.0.0",
        port: int = 5672,
        *,
        tls_port: Optional[int] = None,
        ssl_context: Optional[ssl.SSLContext] = None,
        heartbeat_s: int = 30,
        frame_max: int = 131072,
        channel_max: int = 2047,
        store: Optional[StoreService] = None,
        max_connections: int = 0,
        backlog: int = 128,
        max_message_size: int = 128 * 1024 * 1024,
        users: "Optional[dict[str, str]]" = None,
        permissions: "Optional[dict[str, list[str]]]" = None,
        reuse_port: bool = False,
    ) -> None:
        self.broker = broker or Broker(store=store)
        self.host = host
        self.port = port
        self.tls_port = tls_port
        self.ssl_context = ssl_context
        self.heartbeat_s = heartbeat_s
        self.frame_max = frame_max
        self.channel_max = channel_max
        # listener resource limits (reference: ServerSettings
        # max-connections / backlog, Settings.scala:141-219); 0 = uncapped
        self.max_connections = max_connections
        self.backlog = backlog
        # optional SASL PLAIN verification: user -> password. None/empty
        # keeps the reference's behavior (parse but never verify,
        # SaslMechanism.scala:49-76); configuring users turns real
        # authentication on (EXCEEDS the reference, README "Status": auth
        # unimplemented there).
        self.users = users or None
        # per-user vhost allowlists (consulted only when users are set):
        # a user listed here may open ONLY those vhosts
        self.permissions = permissions or None
        self.max_message_size = max_message_size
        self.refused_connections = 0
        # sharded node (chanamq_tpu/shard/): sibling workers share one
        # AMQP port via SO_REUSEPORT; where that's unavailable the
        # supervisor accepts and ships fds to handoff_path instead
        self.reuse_port = reuse_port
        self.handoff_path: Optional[str] = None
        self._handoff = None
        self._servers: list[asyncio.AbstractServer] = []
        self._connections: set[AMQPConnection] = set()

    async def start(self, *, listen: bool = True) -> None:
        """Start the broker and (by default) open the listeners. Pass
        listen=False to defer the listeners until other layers are live —
        run_node starts the cluster first so no client ever connects to a
        half-clustered node."""
        await self.broker.start()
        if listen:
            await self.start_listeners()

    async def start_listeners(self) -> None:
        if self.handoff_path is not None:
            # reuse-port fallback: no TCP listener here — the shard
            # supervisor accepts and hands client sockets over Unix
            from ..shard.handoff import HandoffReceiver

            self._handoff = HandoffReceiver(self, self.handoff_path)
            await self._handoff.start()
            log.info("AMQP via fd handoff at %s", self.handoff_path)
            return
        kwargs: dict = {}
        if self.reuse_port:
            kwargs["reuse_port"] = True
        server = await asyncio.start_server(
            self._on_client, self.host, self.port, backlog=self.backlog,
            **kwargs)
        self._servers.append(server)
        log.info("AMQP listening on %s:%d%s", self.host, self.port,
                 " (reuse-port)" if self.reuse_port else "")
        if self.tls_port is not None and self.ssl_context is not None:
            tls_server = await asyncio.start_server(
                self._on_client, self.host, self.tls_port,
                ssl=self.ssl_context, backlog=self.backlog)
            self._servers.append(tls_server)
            log.info("AMQPS listening on %s:%d", self.host, self.tls_port)

    @property
    def bound_port(self) -> int:
        return self._servers[0].sockets[0].getsockname()[1]

    async def _on_client(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        if (self.max_connections
                and len(self._connections) >= self.max_connections):
            # refuse at accept: a TCP close before the protocol header is
            # the one refusal every client library understands at this
            # stage (Connection.Close can't be sent pre-Start). Existing
            # connections are untouched.
            self.refused_connections += 1
            self.broker.metrics.connections_refused += 1
            log.warning(
                "refusing connection: %d live >= max-connections %d",
                len(self._connections), self.max_connections)
            writer.close()
            try:
                await writer.wait_closed()
            except Exception:
                pass
            return
        connection = AMQPConnection(
            self.broker, reader, writer,
            heartbeat_s=self.heartbeat_s, frame_max=self.frame_max,
            channel_max=self.channel_max,
            max_message_size=self.max_message_size,
            users=self.users,
            permissions=self.permissions,
        )
        self._connections.add(connection)
        try:
            await connection.serve()
        finally:
            self._connections.discard(connection)

    async def stop(self) -> None:
        if self._handoff is not None:
            await self._handoff.stop()
            self._handoff = None
        for server in self._servers:
            server.close()
        # kick live connections first: in py3.12 Server.wait_closed() waits
        # for all connection handlers, which only finish once clients drop
        for connection in list(self._connections):
            connection.closing = True
            try:
                connection.writer.close()
            except Exception:
                pass
        # explicitly await per-connection teardown: a handler parked at the
        # memory gate wakes on its next bounded wait and must finish before
        # the loop goes away (Server.wait_closed alone doesn't guarantee it)
        if self._connections:
            await asyncio.gather(
                *(c.closed for c in list(self._connections)),
                return_exceptions=True)
        for server in self._servers:
            await server.wait_closed()
        self._servers.clear()
        await self.broker.stop()

    async def serve_forever(self) -> None:
        await self.start()
        try:
            await asyncio.Event().wait()
        finally:
            await self.stop()

    @classmethod
    def from_config(cls, config) -> "BrokerServer":
        """Build a server (broker + listeners) from a Config tree."""
        from ..config import Config

        assert isinstance(config, Config)
        store: Optional[StoreService] = None
        store_path = config.get("chana.mq.store.path")
        if store_path:
            from ..store.sqlite import SqliteStore

            store = SqliteStore(
                store_path,
                synchronous=config.str("chana.mq.store.synchronous"))
            if config.bool("chana.mq.wal.enabled"):
                from ..wal import WalStore

                store = WalStore(
                    store,
                    flush_ms=float(config.get("chana.mq.wal.flush-ms")),
                    flush_bytes=config.size_bytes(
                        "chana.mq.wal.flush-bytes") or (1 << 20),
                    segment_bytes=config.size_bytes(
                        "chana.mq.wal.segment-bytes") or (64 << 20),
                    sync=config.str("chana.mq.wal.sync"),
                    checkpoint_ms=float(
                        config.get("chana.mq.wal.checkpoint-ms")),
                    memtable_bytes=config.size_bytes(
                        "chana.mq.wal.memtable-bytes") or (64 << 20),
                    tier_keep_segments=config.int(
                        "chana.mq.wal.tier-keep-segments"),
                    compact_streams=config.bool(
                        "chana.mq.wal.compact-streams"),
                )
        ssl_context = None
        tls_port = None
        if config.bool("chana.mq.amqp.amqps.enabled"):
            certfile = config.get("chana.mq.amqp.amqps.certfile")
            keyfile = config.get("chana.mq.amqp.amqps.keyfile")
            if not certfile:
                from ..config import ConfigError

                raise ConfigError(
                    "chana.mq.amqp.amqps.enabled is true but "
                    "chana.mq.amqp.amqps.certfile is not set")
            ssl_context = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ssl_context.load_cert_chain(certfile, keyfile)
            tls_port = config.int("chana.mq.amqp.amqps.port")
        users = cls._config_users(config)
        heartbeat = config.duration_s("chana.mq.amqp.connection.heartbeat")
        sweep = config.duration_s("chana.mq.message.sweep-interval")
        low = config.size_bytes("chana.mq.memory.low-watermark")
        ack_timeout = config.duration_s("chana.mq.consumer.timeout")
        broker = Broker(
            store=store,
            message_sweep_interval_s=sweep if sweep is not None else 0.0,
            queue_max_resident=config.int("chana.mq.queue.max-resident"),
            memory_high_watermark=config.size_bytes(
                "chana.mq.memory.high-watermark") or 0,
            memory_low_watermark=low,
            consumer_timeout_ms=(
                int(ack_timeout * 1000) if ack_timeout else 0),
            store_max_bytes=config.size_bytes("chana.mq.store.max-bytes")
            or 0,
            stream_segment_bytes=config.size_bytes(
                "chana.mq.stream.segment-bytes") or (1 << 20),
            stream_segment_age_s=config.duration_s(
                "chana.mq.stream.segment-age") or 0.0,
            stream_cache_segments=config.int(
                "chana.mq.stream.cache-segments"),
            stream_delivery_batch=config.int(
                "chana.mq.stream.delivery-batch") or 128,
            # flow-control ladder (chana.mq.flow.*): thresholds default
            # off the memory watermarks; None keeps the derived defaults
            flow_page_watermark=config.size_bytes(
                "chana.mq.flow.page-watermark"),
            flow_cluster_watermark=config.size_bytes(
                "chana.mq.flow.cluster-watermark"),
            flow_refuse_watermark=config.size_bytes(
                "chana.mq.flow.refuse-watermark"),
            flow_hard_limit=config.size_bytes("chana.mq.flow.hard-limit"),
            flow_publish_credit=config.size_bytes(
                "chana.mq.flow.publish-credit") or 0,
            flow_consumer_buffer=config.size_bytes(
                "chana.mq.flow.consumer-buffer") or 0,
            park_buffer=config.size_bytes("chana.mq.flow.park-buffer"),
            flow_page_resident=config.int("chana.mq.flow.page-resident")
            or 0,
            router_enabled=config.bool("chana.mq.router.enabled"),
            router_backend=config.str("chana.mq.router.backend") or "jax",
            router_min_batch=config.int("chana.mq.router.min-batch") or 16,
            router_max_wildcards=config.int(
                "chana.mq.router.max-wildcards") or 512,
            router_max_queues=config.int("chana.mq.router.max-queues")
            or 4096,
            router_verify=config.bool("chana.mq.router.verify"),
            semantics_enabled=config.bool("chana.mq.semantics.enabled"),
            delay_tick_ms=max(1, round((config.duration_s(
                "chana.mq.semantics.delay-tick") or 0.05) * 1000)),
            native_egress=config.bool("chana.mq.native.egress"),
            native_pool_buffers=config.int("chana.mq.native.pool-buffers")
            or 16,
            native_pool_buffer_kb=config.int("chana.mq.native.pool-buffer-kb")
            or 256,
        )
        if store is not None and hasattr(store, "metrics"):
            # the WAL engine's wal_* counters must land in the broker
            # registry (Prometheus / admin metrics), not a placeholder
            store.metrics = broker.metrics
        return cls(
            broker=broker,
            host=config.str("chana.mq.amqp.interface"),
            port=config.int("chana.mq.amqp.port"),
            tls_port=tls_port,
            ssl_context=ssl_context,
            # sub-second configs round up to 1s rather than silently disabling
            heartbeat_s=max(1, round(heartbeat)) if heartbeat else 0,
            frame_max=config.size_bytes("chana.mq.amqp.connection.frame-max"),
            channel_max=config.int("chana.mq.amqp.connection.channel-max"),
            max_connections=config.int("chana.mq.server.max-connections") or 0,
            backlog=config.int("chana.mq.server.backlog") or 128,
            max_message_size=config.size_bytes("chana.mq.message.max-size")
            or 0,
            users=users,
            permissions=cls._config_permissions(config, users),
        )

    @staticmethod
    def _config_users(config) -> Optional[dict]:
        """chana.mq.auth.users, validated fail-closed: a non-mapping value
        (malformed file/env) must error out, never silently disable auth."""
        users = config.get("chana.mq.auth.users")
        if users is None or users == {}:
            return None
        if not isinstance(users, dict) or not all(
            isinstance(k, str) and isinstance(v, str)
            for k, v in users.items()
        ):
            from ..config import ConfigError

            raise ConfigError(
                "chana.mq.auth.users must map user names to passwords")
        return users

    @staticmethod
    def _config_permissions(config, users: Optional[dict]) -> Optional[dict]:
        """chana.mq.auth.permissions, validated fail-closed like users:
        allowlists without a user table (or naming unknown users) would be
        silently unenforced, so both are boot errors."""
        perms = config.get("chana.mq.auth.permissions")
        if perms is None or perms == {}:
            return None
        from ..config import ConfigError

        ok = isinstance(perms, dict) and all(
            isinstance(k, str) and isinstance(v, list)
            and all(isinstance(x, str) for x in v)
            for k, v in perms.items()
        )
        if not ok:
            raise ConfigError(
                "chana.mq.auth.permissions must map user names to vhost lists")
        if users is None:
            raise ConfigError(
                "chana.mq.auth.permissions requires chana.mq.auth.users")
        unknown = sorted(set(perms) - set(users))
        if unknown:
            raise ConfigError(
                f"chana.mq.auth.permissions names unknown users: {unknown}")
        return perms


async def run_node(config) -> None:
    """Boot a full node: broker + AMQP(+AMQPS) listeners + admin REST
    (the reference's AMQPServer.main composition, AMQPServer.scala:39-111).
    SIGTERM/SIGINT trigger a graceful drain: listeners close, live
    connections tear down (unacked requeue, store buffers flush), the
    group-commit queue drains, then the process exits 0 — the analogue of
    the reference's JVM shutdown hooks."""
    import signal as signal_module

    from ..rest.admin import AdminServer

    # multi-process sharding: with chana.mq.shard.count past 1 this
    # process becomes the supervisor (spawns one worker per shard and
    # returns when they're all down); workers carry CHANAMQ_SHARD_INDEX
    # and fall through to the normal boot below with shard wiring
    shard_index_env = os.environ.get("CHANAMQ_SHARD_INDEX")
    if shard_index_env is None:
        from ..shard import resolve_count

        if resolve_count(config) > 1:
            from ..shard.supervisor import run_supervisor

            await run_supervisor(config)
            return

    server = BrokerServer.from_config(config)
    shard_topo = None
    shard_index = 0
    if shard_index_env is not None:
        from ..shard import ShardTopology

        shard_index = int(shard_index_env)
        shard_topo = ShardTopology.from_env(config, shard_index)
        server.broker.shard_info = {
            "index": shard_index,
            "count": shard_topo.count,
            "name": shard_topo.name(shard_index),
        }
        server.broker.metrics.shard_restarts = int(
            os.environ.get("CHANAMQ_SHARD_RESTARTS", "0") or 0)
        router = server.broker.router
        log.info("shard %d of %d (pid %d): router backend=%s",
                 shard_index, shard_topo.count, os.getpid(),
                 router.backend if router is not None else "off")
        if config.bool("chana.mq.shard.reuse-port"):
            server.reuse_port = True
        else:
            server.handoff_path = shard_topo.handoff_path(shard_index)
    if config.bool("chana.mq.log.json"):
        # swap formatters before any traffic so every line is one JSON
        # object stamped with node id + active trace id
        from ..utils import logjson

        logjson.install(server.broker)
    admin = None
    cluster = None
    forecaster = None
    telemetry = None
    control = None
    federation = None
    otel = None
    started = False
    stop_event = asyncio.Event()
    loop = asyncio.get_running_loop()

    def on_signal() -> None:
        if stop_event.is_set():
            # second signal while draining: the operator wants OUT now
            os._exit(130)
        stop_event.set()

    for sig in (signal_module.SIGTERM, signal_module.SIGINT):
        try:
            loop.add_signal_handler(sig, on_signal)
        except (NotImplementedError, RuntimeError, ValueError):  # pragma: no cover
            pass  # non-unix platform or non-main thread: KeyboardInterrupt
    try:
        # boot order matters: broker state, then the cluster layer, then
        # the AMQP listeners — a client accepted before the cluster is live
        # would see a node that mis-routes clustered queues
        await server.start(listen=False)
        started = True
        # chaos wiring before any traffic: wraps the store, marks the
        # broker chaos-capable, optionally installs a boot plan. With
        # chana.mq.chaos.enabled unset this is a single bool check and the
        # seams stay no-op module-attribute loads.
        if config.bool("chana.mq.chaos.enabled"):
            from .. import chaos as chaos_mod

            chaos_mod.enable_from_config(config, server.broker)
        # tracing next (same ACTIVE-gate idiom as chaos): installed before
        # the cluster starts so ClusterNode.start can rename the runtime's
        # node tag from "local" to host:port
        if config.bool("chana.mq.trace.enabled"):
            from .. import trace as trace_mod

            trace_mod.enable_from_config(config, server.broker)
        # OTLP span exporter: hooks trace completion, so it must come
        # after tracing is installed. Without an endpoint it still arms
        # the bounded queue behind GET /admin/otel/spans (pull mode).
        if config.bool("chana.mq.otel.enabled"):
            from ..otel.export import OtelExporter

            otel = OtelExporter(
                server.broker,
                endpoint=config.str("chana.mq.otel.endpoint"),
                flush_ms=config.int("chana.mq.otel.flush-ms"),
                max_batch=config.int("chana.mq.otel.max-batch"),
                queue_size=config.int("chana.mq.otel.queue-size"))
            await otel.start()
            server.broker.otel = otel
        # cost ledger + sampling profiler (third ACTIVE-gate subsystem):
        # armed before traffic so stage counters cover the whole run, and
        # before the cluster so cluster-push batches are attributed
        if config.bool("chana.mq.profile.enabled"):
            from .. import profile as profile_mod

            profile_mod.enable_from_config(config, server.broker)
        # event bus + firehose (fourth ACTIVE-gate subsystem): installed
        # before the cluster so lifecycle transitions and chaos fires are
        # observable from the first moment they can happen
        if (config.bool("chana.mq.events.enabled")
                or config.bool("chana.mq.firehose.enabled")):
            from .. import events as events_mod

            bus, _ = events_mod.enable_from_config(config, server.broker)
            if bus is not None:
                restarts = int(
                    os.environ.get("CHANAMQ_SHARD_RESTARTS", "0") or 0)
                if restarts > 0:
                    # this worker is a supervisor respawn: the one boot
                    # event a consumer can alert on
                    bus.emit("shard.restarted", {
                        "shard": shard_index, "restarts": restarts})
        # tenant registry (fifth ACTIVE-gate subsystem): installed before
        # the listeners open so the first handshake already authenticates
        # against tenant user tables and lands under quota enforcement.
        # Called unconditionally: the enable path itself fail-closes when
        # tenants are declared while chana.mq.tenant.enabled is false.
        from .. import tenancy as tenancy_mod

        tenancy_mod.enable_from_config(config, server.broker)
        if config.bool("chana.mq.cluster.enabled"):
            from ..cluster.node import ClusterNode

            cluster = ClusterNode(
                server.broker,
                host=config.str("chana.mq.cluster.host"),
                port=config.int("chana.mq.cluster.port"),
                seeds=config.list("chana.mq.cluster.seeds"),
                virtual_nodes=config.int("chana.mq.cluster.virtual-nodes"),
                heartbeat_interval_s=config.duration_s(
                    "chana.mq.cluster.heartbeat-interval") or 1.0,
                failure_timeout_s=config.duration_s(
                    "chana.mq.cluster.failure-timeout") or 5.0,
                replicate_factor=config.int("chana.mq.replicate.factor"),
                replicate_sync=config.bool("chana.mq.replicate.sync"),
                replicate_batch_max=config.int(
                    "chana.mq.replicate.batch-max"),
                replicate_ack_timeout_ms=config.int(
                    "chana.mq.replicate.ack-timeout-ms"),
                streams=config.int("chana.mq.cluster.streams"),
                stream_inflight=config.int("chana.mq.cluster.stream-inflight"),
                flush_window_us=config.int("chana.mq.cluster.flush-window-us"),
                flush_max_bytes=config.size_bytes(
                    "chana.mq.cluster.flush-max-bytes") or (1 << 20),
                flush_max_count=config.int("chana.mq.cluster.flush-max-count"),
                consume_credit=config.int("chana.mq.cluster.consume-credit"),
                call_timeout_s=config.duration_s(
                    "chana.mq.cluster.call-timeout") or 10.0,
                drain_retry_limit=config.int(
                    "chana.mq.lifecycle.drain-retry-limit"),
                drain_backoff_ms=int((config.duration_s(
                    "chana.mq.lifecycle.drain-backoff") or 0.1) * 1000),
                drain_backoff_cap_ms=int((config.duration_s(
                    "chana.mq.lifecycle.drain-backoff-cap") or 2.0) * 1000),
                drain_budget_s=config.duration_s(
                    "chana.mq.lifecycle.drain-budget") or 30.0,
                uds_path=(shard_topo.uds_path(shard_index)
                          if shard_topo is not None else None),
                uds_map=(shard_topo.uds_map_for(shard_index)
                         if shard_topo is not None else None),
            )
            await cluster.start()
        if stop_event.is_set():
            # signalled during boot (e.g. while the cluster joined its
            # seeds): don't open listeners just to tear clients down
            return
        await server.start_listeners()
        if config.bool("chana.mq.federation.enabled"):
            # cross-cluster federation (federation/): the fed.* listener
            # (mirror side) plus one shipping link per configured remote.
            # Boots after the listeners so an inbound fed.resume can
            # declare its mirror streams on a fully-started broker; with
            # no links configured the only steady-state cost is the idle
            # listener and `broker.federation is None` checks staying hot
            from ..federation import enable_from_config as federation_enable

            federation = await federation_enable(config, server.broker)
        if config.bool("chana.mq.telemetry.enabled"):
            # per-entity telemetry + health + alerts (telemetry/): started
            # after the cluster layer so the first tick already sees the
            # real node name and replication state
            from ..telemetry import TelemetryService, default_rules

            telemetry = TelemetryService(
                server.broker,
                interval_s=config.duration_s("chana.mq.telemetry.interval")
                or 1.0,
                ring_ticks=config.int("chana.mq.telemetry.ring-ticks"),
                max_queues=config.int("chana.mq.telemetry.max-queues"),
                max_connections=config.int(
                    "chana.mq.telemetry.max-connections"),
                top_k=config.int("chana.mq.telemetry.top-k"),
                rules=default_rules(
                    backlog_growth=float(
                        config.int("chana.mq.alerts.backlog-growth")),
                    backlog_window=config.int("chana.mq.alerts.backlog-window"),
                    stall_ticks=config.int("chana.mq.alerts.stall-ticks"),
                    repl_lag=float(config.int("chana.mq.alerts.repl-lag")),
                    loop_lag_ms=float(
                        config.int("chana.mq.alerts.loop-lag-ms")),
                    memory_stage=float(
                        config.get("chana.mq.alerts.memory-stage") or 3.5),
                ),
                alerts_enabled=config.bool("chana.mq.alerts.enabled"),
                loop_lag_ready_ms=float(
                    config.int("chana.mq.telemetry.ready-loop-lag-ms")),
                repl_lag_ready=config.int("chana.mq.telemetry.ready-repl-lag"),
                store_error_window=config.int(
                    "chana.mq.telemetry.store-error-window"),
                federation_lag_records=config.int(
                    "chana.mq.slo.federation-lag-records"),
            )
            if config.bool("chana.mq.slo.enabled"):
                # burn-rate SLOs ride the telemetry tick (slo/): specs
                # from chana.mq.slo.* or POST /admin/slo/configure
                from ..slo import attach_tenant_latency, engine_from_config

                engine = engine_from_config(
                    config,
                    config.duration_s("chana.mq.telemetry.interval") or 1.0)
                telemetry.set_slo(engine)
                # tenant-scoped delivery-latency SLOs need their per-tenant
                # histogram allocated before the first delivery
                attach_tenant_latency(engine, server.broker.tenancy)
            server.broker.telemetry = telemetry
            await telemetry.start()
        if config.bool("chana.mq.forecast.enabled"):
            # live-telemetry forecaster (SURVEY.md §7.1's JAX role): samples
            # metrics on the loop, trains/predicts on a worker thread,
            # serves GET /admin/forecast + chanamq_forecast_* gauges.
            # start() claims the device (chanamq_tpu/device.py): a missing
            # jax, or a backend that is not the chip and was not asked
            # for, is a boot error here — not a traceback per train round
            # on the worker thread.
            from ..models.service import ForecastService

            forecaster = ForecastService(
                server.broker,
                interval_s=config.duration_s("chana.mq.forecast.interval")
                or 1.0,
                train_interval_s=config.duration_s(
                    "chana.mq.forecast.train-interval") or 30.0,
                seq_len=config.int("chana.mq.forecast.window"),
                history=config.int("chana.mq.forecast.history"),
                queue_top_k=(
                    config.int("chana.mq.forecast.queue-top-k")
                    if telemetry is not None else 0),
            )
            await forecaster.start()
        if config.bool("chana.mq.control.enabled"):
            # predictive control plane (control/): forecast/trend-driven
            # admission pre-arm, queue rebalancing and prefetch
            # autotuning. Boots after telemetry + forecaster (its inputs)
            # and works degraded without either — trend-only admission
            # against the flow ladder. Dry-run by default.
            from ..control import ControlService

            control = ControlService(
                server.broker,
                interval_s=config.duration_s("chana.mq.control.interval")
                or 1.0,
                dry_run=config.bool("chana.mq.control.dry-run"),
                admission=config.bool("chana.mq.control.admission.enabled"),
                rebalance=config.bool("chana.mq.control.rebalance.enabled"),
                prefetch=config.bool("chana.mq.control.prefetch.enabled"),
                horizon_s=config.duration_s("chana.mq.control.horizon")
                or 5.0,
                arm_ticks=config.int("chana.mq.control.arm-ticks"),
                cooldown_s=config.duration_s("chana.mq.control.cooldown")
                or 10.0,
                rebalance_cooldown_s=config.duration_s(
                    "chana.mq.control.rebalance.cooldown") or 30.0,
                credit_factor=float(config.get(
                    "chana.mq.control.admission.credit-factor") or 0.5),
                credit_min=config.size_bytes(
                    "chana.mq.control.admission.credit-min") or 4096,
                rebalance_ratio=float(config.get(
                    "chana.mq.control.rebalance.ratio") or 1.5),
                rebalance_min_rate=float(config.size_bytes(
                    "chana.mq.control.rebalance.min-rate") or 1024),
                prefetch_min=config.int("chana.mq.control.prefetch.min"),
                prefetch_max=config.int("chana.mq.control.prefetch.max"),
                log_size=config.int("chana.mq.control.log-size"),
                forecast_max_age_s=config.duration_s(
                    "chana.mq.control.forecast-max-age") or 10.0,
                forecast_error_gate=float(config.get(
                    "chana.mq.control.forecast-error-gate") or 0.5),
            )
            await control.start()
        if config.bool("chana.mq.admin.enabled"):
            admin = AdminServer(
                server.broker,
                host=config.str("chana.mq.admin.interface"),
                port=config.int("chana.mq.admin.port"),
            )
            await admin.start()
        await stop_event.wait()
        # readiness flips 503 the moment the drain starts — the admin
        # server is still up below, so a load balancer polling
        # /admin/health stops routing to this node before connections
        # actually tear down
        server.broker.draining = True
        log.info("shutdown signal received; draining")
    finally:
        server.broker.draining = True
        if admin:
            await admin.stop()
        if control:
            await control.stop()
        if telemetry:
            await telemetry.stop()
        if forecaster:
            await forecaster.stop()
        if federation:
            await federation.stop()
        if otel:
            await otel.stop()
        if cluster:
            await cluster.stop()
        if started:
            await server.stop()


def main() -> None:
    import argparse

    from ..config import Config

    parser = argparse.ArgumentParser(description="chanamq-tpu AMQP broker")
    parser.add_argument("--config", default=None, help="JSON config file")
    parser.add_argument("--host", default=None)
    parser.add_argument("--port", type=int, default=None)
    parser.add_argument("--admin-port", type=int, default=None)
    parser.add_argument("--no-admin", action="store_true")
    parser.add_argument("--store", default=None,
                        help="sqlite db path (default: in-memory transient)")
    parser.add_argument("--log-level", default="INFO")
    args = parser.parse_args()
    logging.basicConfig(
        level=args.log_level,
        format="%(asctime)s %(levelname)s %(name)s %(message)s")

    overrides: dict = {}
    if args.host is not None:
        overrides["chana.mq.amqp.interface"] = args.host
    if args.port is not None:
        overrides["chana.mq.amqp.port"] = args.port
    if args.admin_port is not None:
        overrides["chana.mq.admin.port"] = args.admin_port
    if args.no_admin:
        overrides["chana.mq.admin.enabled"] = False
    if args.store is not None:
        overrides["chana.mq.store.path"] = args.store
    config = Config(overrides, file=args.config)
    try:
        # the loop over the timed selector: its waits and turns are
        # counted from inside (chanamq_tpu/loopbooks.py)
        asyncio.run(run_node(config), loop_factory=loopbooks.new_event_loop)
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
