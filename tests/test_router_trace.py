"""The router launch measured from inside the program: the per-launch
counters of `Metrics` (stamped in router/compile.py `_launch`), where they
are served, the kernels' names, and `device.span` — what it costs a process
without JAX, and what it writes into a real `jax.profiler` trace.
"""

import asyncio
import glob
import json
import os
import subprocess
import sys

import pytest

from chanamq_tpu import device, native_ext, profile
from chanamq_tpu.amqp.properties import BasicProperties
from chanamq_tpu.broker.broker import Broker
from chanamq_tpu.broker.server import BrokerServer
from chanamq_tpu.client import AMQPClient
from chanamq_tpu.profile.runtime import ProfileRuntime
from chanamq_tpu.rest.admin import AdminServer
from chanamq_tpu.router import compile as rcompile
from chanamq_tpu.utils.metrics import Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PER_LAUNCH = (
    "router_tokenize_ns", "router_dispatch_ns", "router_wait_ns",
    "router_decode_ns", "router_kernel_keys", "router_kernel_rows",
    "router_h2d_bytes", "router_table_uploads", "router_mask_decodes")
PER_FLUSH = ("router_route_ns",)
SPANS = ("conn.ingress", "router.lookup", "router.tokenize", "router.decode",
         "broker.enqueue", "broker.dispatch", "conn.confirms")


def test_the_registry_names_every_counter_once():
    assert Metrics.ROUTER_LAUNCH == PER_LAUNCH + PER_FLUSH
    snap = Metrics().snapshot()
    assert all(snap[name] == 0 for name in Metrics.ROUTER_LAUNCH)


def _broker(loop, kind: str) -> Broker:
    broker = Broker()
    run = loop.run_until_complete
    run(broker.create_vhost("/"))
    run(broker.declare_exchange("/", "ex", kind))
    for queue in ("q1", "q2"):
        run(broker.declare_queue("/", queue))
    if kind == "topic":
        run(broker.bind_queue("/", "q1", "ex", "a.*"))
        run(broker.bind_queue("/", "q2", "ex", "#.z"))
    else:
        run(broker.bind_queue("/", "q1", "ex", "", {"x-match": "all", "k": 1}))
        run(broker.bind_queue("/", "q2", "ex", "", {"x-match": "any", "k": 2}))
    broker.router.min_batch = 1
    return broker


def _entries(kind: str, n: int, duplicates: int, tag: str = "") -> list:
    """n rows for exchange `ex`, the last `duplicates` repeating the first
    ones: routing keys for a topic exchange, header sets for headers."""
    rows = []
    for i in range(n):
        j = i if i < n - duplicates else i - (n - duplicates)
        if kind == "topic":
            rows.append(("ex", f"a.k{tag}{j}", BasicProperties(), b"x", None,
                         None, False))
        else:
            rows.append(("ex", "", BasicProperties(headers={"k": j % 3}),
                         b"x", None, None, False))
    return rows


def _arg_bytes(broker: Broker, kind: str, real_rows: int) -> "tuple[int, int, int]":
    """(bucket, bytes of the snapshot's tables, bytes of one batch of
    `real_rows`): a snapshot's first launch hands up both, every later one
    the batch alone."""
    compiled = broker.router._compiled[("/", "ex")]
    b = rcompile._bucket(real_rows, 16)
    if kind == "topic":
        words = 4 * b * (compiled.wild["p"] + compiled.wild["s"] + 1)
    else:
        words = 4 * b * 2  # one header a message: the bucket's floor of 2
    return b, sum(t.nbytes for t in compiled.kernel_tables()), words


@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_counters_advance_once_a_launch_and_agree_with_the_batch(
        event_loop, kind):
    broker = _broker(event_loop, kind)
    router, metrics = broker.router, broker.metrics
    n, d = 20, 5
    router.route_pending("/", _entries(kind, n, d))
    # a topic launch carries each unseen key once; headers has no key memo
    real = n - d if kind == "topic" else n
    bucket, tables, words = _arg_bytes(broker, kind, real)
    assert metrics.router_kernel_launches == 1
    assert metrics.router_kernel_keys == real
    assert metrics.router_kernel_rows == bucket
    # a generation's first launch: its tables go up with the batch
    assert metrics.router_table_uploads == 1
    assert metrics.router_h2d_bytes == tables + words
    first = metrics.router_launch()
    assert all(first[name] > 0 for name in (
        "router_tokenize_ns", "router_dispatch_ns", "router_wait_ns",
        "router_decode_ns", "router_route_ns"))
    # the stamps nest: the four stages of the launch inside the flush
    assert sum(first[name] for name in PER_LAUNCH[:4]) <= \
        first["router_route_ns"]

    router.route_pending("/", _entries(kind, n, d))
    again = metrics.router_launch()
    assert again["router_route_ns"] > first["router_route_ns"]
    if kind == "topic":  # the key memo serves every position: no launch
        assert metrics.router_kernel_launches == 1
        assert all(again[name] == first[name] for name in PER_LAUNCH)
    else:  # every headers flush reaches the kernel: the batch alone goes up
        assert metrics.router_kernel_launches == 2
        assert again["router_kernel_keys"] == 2 * n
        assert again["router_h2d_bytes"] == tables + 2 * words
        assert again["router_table_uploads"] == 1


@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_the_table_goes_up_once_a_generation(event_loop, kind):
    """Later launches through a snapshot hand up the batch alone; a bind
    makes a new generation, whose first launch uploads its own tables."""
    broker = _broker(event_loop, kind)
    router, metrics = broker.router, broker.metrics
    n = 20
    for flush in range(3):  # fresh keys every flush: three launches
        router.route_pending("/", _entries(kind, n, 0, f"f{flush}-"))
    bucket, tables, words = _arg_bytes(broker, kind, n)
    assert metrics.router_kernel_launches == 3
    assert metrics.router_table_uploads == 1
    assert metrics.router_h2d_bytes == tables + 3 * words
    old = router._compiled[("/", "ex")]

    run = event_loop.run_until_complete
    run(broker.declare_queue("/", "q3"))
    if kind == "topic":
        run(broker.bind_queue("/", "q3", "ex", "a.*.much.longer.pattern"))
    else:
        run(broker.bind_queue("/", "q3", "ex", "",
                              {"x-match": "all", "k": 1, "j": 2, "i": 3}))
    router.route_pending("/", _entries(kind, n, 0, "g-"))
    new = router._compiled[("/", "ex")]
    assert new is not old and new.generation > old.generation
    _, new_tables, new_words = _arg_bytes(broker, kind, n)
    assert new_tables > tables  # a wider table: not the old one's bytes
    assert metrics.router_kernel_launches == 4
    assert metrics.router_table_uploads == 2
    assert metrics.router_h2d_bytes == \
        tables + 3 * words + new_tables + new_words
    assert old._resident is not None and new._resident is not old._resident


def _masked(kind: str, marks: list, tag: str = "") -> list:
    """One row a mark for `_broker`'s table: 1 reaches q1, 2 reaches q2,
    3 both (topic only), 0 neither; every topic key but 3's is distinct."""
    rows = []
    for i, mark in enumerate(marks):
        if kind == "topic":  # a.* -> q1, #.z -> q2
            key = (f"m.n{tag}{i}", f"a.k{tag}{i}", f"m{tag}{i}.z", "a.z")[mark]
            props = BasicProperties()
        else:
            key, props = "", BasicProperties(headers={"k": mark})
        rows.append(("ex", key, props, b"x", None, None, False))
    return rows


@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_mask_decodes_count_the_distinct_new_masks_of_a_launch(
        event_loop, kind):
    broker = _broker(event_loop, kind)
    router, metrics = broker.router, broker.metrics
    first = _masked(kind, [1, 1, 0, 1, 0])
    router.route_pending("/", first)
    assert metrics.router_kernel_launches == 1
    assert metrics.router_mask_decodes == 1  # three rows, one mask; 0 is free
    # the same batch again, the key memo gone: a launch, nothing to decode
    compiled = router._compiled[("/", "ex")]
    compiled._route_memo.clear()
    router.route_pending("/", first)
    assert metrics.router_kernel_launches == 2
    assert metrics.router_mask_decodes == 1
    # unseen masks only: q2's, and for topic q1+q2's
    marks = [2, 1, 2, 0] + ([3] if kind == "topic" else [])
    router.route_pending("/", _masked(kind, marks, "later"))
    assert metrics.router_kernel_launches == 3
    assert metrics.router_mask_decodes == (3 if kind == "topic" else 2)
    assert len(compiled._mask_memo) == metrics.router_mask_decodes
    assert Metrics().snapshot()["router_mask_decodes"] == 0
    assert broker.metrics.snapshot()["router_mask_decodes"] == \
        metrics.router_mask_decodes


@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_the_numpy_backend_advances_no_launch_counter(event_loop, kind):
    broker = _broker(event_loop, kind)
    broker.router.backend = "python"
    broker.router.route_pending("/", _entries(kind, 20, 5))
    counters = broker.metrics.router_launch()
    assert broker.metrics.router_kernel_launches == 0
    assert all(counters[name] == 0 for name in PER_LAUNCH)
    assert counters["router_route_ns"] > 0  # the flush is timed all the same


@pytest.mark.parametrize("kind", ["topic", "headers"])
def test_the_profile_page_reads_the_counters_per_launch(event_loop, kind):
    """`/admin/profile`'s `router` block is the program's own reader of the
    launch counters: what one launch cost, by the same integers."""
    broker = _broker(event_loop, kind)
    rt = ProfileRuntime(metrics=broker.metrics, slow_callback_ms=0,
                        broker=broker)
    assert "per_launch" not in rt.snapshot()["router"]  # nothing launched
    n, d = 20, 5
    broker.router.route_pending("/", _entries(kind, n, d))
    real = n - d if kind == "topic" else n
    bucket, tables, words = _arg_bytes(broker, kind, real)
    block = rt.snapshot()["router"]
    per = block["per_launch"]
    assert block["kernel_launches"] == 1
    assert per["keys"] == real and per["h2d_bytes"] == tables + words
    assert block["router_table_uploads"] == 1
    assert per["table_resident_pct"] == 0.0  # the one launch uploaded
    assert per["useful_row_pct"] == round(100.0 * real / bucket, 1)
    # to the tenth the page rounds to, not to a rounding of one's own: the
    # page multiplies by 1e-3 where `/ 1e3` lands on the other side of a
    # ...50 ns tie (1,150 ns: 1.1 and 1.2), about one stamp in 550
    for stage in ("tokenize", "dispatch", "wait", "decode"):
        assert per[f"{stage}_us"] == pytest.approx(
            block[f"router_{stage}_ns"] / 1e3, abs=0.05 + 1e-9)
    # every topic key reaches q1 alone; the header sets reach q1, q2 or
    # nothing, and a row that reaches nothing is never decoded
    masks = 1 if kind == "topic" else 2
    assert block["router_mask_decodes"] == masks
    assert per["mask_memo_hit_pct"] == round(100.0 * (1 - masks / real), 1)
    # a second launch through the snapshot finds its table on the device
    broker.router.route_pending("/", _entries(kind, n, d, "again-"))
    per = rt.snapshot()["router"]["per_launch"]
    assert per["table_resident_pct"] == 50.0
    assert per["h2d_bytes"] == round((tables + 2 * words) / 2)


def test_the_kernels_have_names():
    """The host event reads PjitFunction(topic_match), the module
    jit_topic_match: a trace tells the two kernels apart."""
    compiled = rcompile.compile_exchange("topic", [("a.*", "q", None)])
    topic, headers, _put = rcompile._jit_kernels()
    lowered = topic.lower(
        *compiled.kernel_tables(),
        rcompile._tokenize_topic(compiled.wild, ["a.b"], 16)).as_text()
    assert "module @jit_topic_match" in lowered
    compiled = rcompile.compile_exchange(
        "headers", [("", "q", {"x-match": "all", "k": 1})])
    lowered = headers.lower(
        *compiled.kernel_tables(),
        rcompile._tokenize_headers(compiled.headers, [{"k": 1}], 16)).as_text()
    assert "module @jit_headers_match" in lowered


async def _http(port: int, path: str) -> "tuple[int, bytes]":
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: localhost\r\n\r\n".encode())
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(-1), 5)  # the server closes
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), body


def test_the_admin_surfaces_carry_every_new_name(event_loop):
    async def run():
        broker = Broker()
        rt = ProfileRuntime(metrics=broker.metrics, slow_callback_ms=0,
                            broker=broker)
        broker.profile = rt
        admin = AdminServer(broker, port=0)
        await admin.start()
        try:
            broker.metrics.router_dispatch_ns = 1234
            status, body = await _http(admin.bound_port, "/admin/overview")
            served = json.loads(body)["metrics"]
            assert status == 200
            assert set(Metrics.ROUTER_LAUNCH) <= set(served)
            assert served["router_dispatch_ns"] == 1234
            status, body = await _http(admin.bound_port, "/metrics")
            text = body.decode()
            for name in Metrics.ROUTER_LAUNCH:
                assert f"# TYPE chanamq_{name} counter" in text
            assert "chanamq_router_dispatch_ns 1234" in text
            assert "# TYPE chanamq_router_mask_decodes counter" in text
            status, body = await _http(admin.bound_port, "/admin/profile")
            block = json.loads(body)["router"]
            assert set(block) == {"kernel_launches", *Metrics.ROUTER_LAUNCH}
            assert block["router_dispatch_ns"] == 1234
        finally:
            await admin.stop()

    assert profile.ACTIVE is None  # the block needs the ledger's page only
    event_loop.run_until_complete(run())


def test_span_without_a_device_never_imports_jax():
    code = (
        "import sys\n"
        "from chanamq_tpu import device\n"
        "from chanamq_tpu.broker.broker import Broker\n"
        "broker = Broker(router_backend='python')\n"
        "with device.span('router.lookup') as one:\n"
        "    pass\n"
        "assert device.span('conn.ingress') is device.span('router.decode')\n"
        "assert device.claimed() is None\n"
        "print('jax' in sys.modules)\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, PYTHONPATH=ROOT))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "False"


def test_span_outside_a_profiler_session_is_the_shared_noop():
    device.claim()
    assert device.span("router.lookup") is device.span("conn.ingress")


def _host_events(trace_dir: str) -> dict:
    """thread line name -> [(name, start_ns, end_ns)] of /host:CPU."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    lines: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                lines.setdefault(line.name, []).append(
                    (e.name, int(e.start_ns),
                     int(e.start_ns) + int(e.duration_ns)))
    return lines


def _flat_spans_on_the_loops_line(trace_dir: str, names: tuple) -> tuple:
    """(the loop's line's events, its spans of `names` in time order) of a
    trace in which every one of `names` was written: bare, all on the one
    line that carries `router.tokenize`, flat and disjoint."""
    lines = _host_events(trace_dir)
    every = [e for events in lines.values() for e in events]
    # bare names: nothing of the `name#key=value#` form
    assert not [e for e in every if e[0].startswith(names) and "#" in e[0]]
    ours = {name: [line for line, events in lines.items()
                   if any(e[0] == name for e in events)] for name in names}
    loop_line = ours["router.tokenize"]
    assert len(loop_line) == 1
    assert all(found == loop_line for found in ours.values()), ours
    on_loop = lines[loop_line[0]]
    spans = sorted((e for e in on_loop if e[0] in names), key=lambda e: e[1])
    # flat and disjoint: no program span starts before the last one ended
    for before, after in zip(spans, spans[1:]):
        assert before[2] <= after[1], (before, after)
    return on_loop, spans


async def _traced(trace_dir, server: BrokerServer, drive) -> None:
    """`drive(port)` against `server` inside a real `jax.profiler` session
    that records host events only, as the benchmark's traced run does."""
    import jax

    await server.start()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        await drive(server.bound_port)
    finally:
        jax.profiler.stop_trace()
        await server.stop()


@pytest.mark.skipif(not native_ext.pipeline_available(),
                    reason="the router batches only behind the native scan")
def test_a_profiler_trace_holds_the_spans_flat_on_the_loops_line(
        event_loop, tmp_path):
    """A rehearsal of the benchmark's traced run on the CPU: a real
    `jax.profiler` session, a flush driven through a real connection."""

    async def drive(port: int) -> None:
        c = await AMQPClient.connect("127.0.0.1", port)
        ch = await c.channel()
        await ch.exchange_declare("ex", "topic")
        await ch.queue_declare("q1")
        await ch.queue_bind("q1", "ex", "a.*.c")
        got = []
        await ch.basic_consume("q1", got.append, no_ack=True)
        await ch.confirm_select()
        for burst in range(3):
            for i in range(64):
                ch.basic_publish(b"m", exchange="ex",
                                 routing_key=f"a.{burst}-{i}.c")
            await ch.wait_unconfirmed_below(1)
        for _ in range(200):
            if len(got) == 192:
                break
            await asyncio.sleep(0.01)
        assert len(got) == 192
        await c.close()

    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    event_loop.run_until_complete(_traced(tmp_path, server, drive))
    assert server.broker.metrics.router_kernel_launches >= 1
    on_loop, spans = _flat_spans_on_the_loops_line(str(tmp_path), SPANS)
    # JAX's two events of the launch lie between tokenize and decode
    names = {e[0] for e in on_loop}
    assert "PjitFunction(topic_match)" in names
    launch = next(e for e in on_loop if e[0] == "PjitFunction(topic_match)")
    assert not [s for s in spans if s[1] < launch[2] and launch[1] < s[2]]
    # `broker.dispatch` is one span a drain (a synchronous callback, so never
    # open across an await), whatever the drain delivered: as many as the
    # drains that delivered, or more, and not one a delivery
    metrics = server.broker.metrics
    drains = sum(1 for e in spans if e[0] == "broker.dispatch")
    assert 1 <= metrics.dispatch_drains <= drains < metrics.delivered_msgs


@pytest.mark.skipif(not native_ext.pipeline_available(),
                    reason="the router batches only behind the native scan")
def test_a_graph_routes_under_the_same_flat_spans_and_compiles_once_a_bind(
        event_loop, tmp_path):
    """A publish into an exchange graph's root takes the publish path of a
    plain exchange, through the flattened snapshot: the same spans, flat.
    The closure compiles at the first publish after a bind in the graph
    and at no burst or message after it; its time is a counter's."""

    async def drive(port: int) -> None:
        c = await AMQPClient.connect("127.0.0.1", port)
        ch = await c.channel()
        await ch.exchange_declare("ingest", "topic")
        await ch.exchange_declare("region", "fanout")
        for queue in ("dash1", "dash2"):
            await ch.queue_declare(queue)
        await ch.queue_bind("dash1", "region", "")
        await ch.exchange_bind("region", "ingest", "r1.#")
        got = []
        await ch.basic_consume("dash1", got.append, no_ack=True)
        await ch.basic_consume("dash2", got.append, no_ack=True)
        await ch.confirm_select()
        for burst in range(4):
            if burst == 2:  # a bind below the root: the next publish recompiles
                await ch.queue_bind("dash2", "region", "")
            for i in range(64):
                ch.basic_publish(b"m", exchange="ingest",
                                 routing_key=f"r1.{burst}-{i}")
            await ch.wait_unconfirmed_below(1)
        for _ in range(200):
            if len(got) == 2 * 64 + 2 * 128:
                break
            await asyncio.sleep(0.01)
        assert len(got) == 2 * 64 + 2 * 128
        await c.close()

    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0)
    event_loop.run_until_complete(_traced(tmp_path, server, drive))
    metrics = server.broker.metrics
    assert metrics.router_closure_compiles == 2
    assert metrics.router_closure_flattens == 4
    assert metrics.router_closure_msgs == 256 - metrics.router_fallback_msgs
    assert metrics.router_closure_flatten_ns > 0
    on_loop, _ = _flat_spans_on_the_loops_line(str(tmp_path), SPANS)
    assert "PjitFunction(topic_match)" in {e[0] for e in on_loop}


DURABLE_SPANS = ("wal.commit", "store.settle", "store.deliver",
                 "wal.checkpoint")


@pytest.mark.skipif(not native_ext.pipeline_available(),
                    reason="the router batches only behind the native scan")
def test_a_profiler_trace_holds_the_durable_paths_spans_flat(
        event_loop, tmp_path):
    """The same rehearsal over a durable deployment (PR 35): durable queue,
    persistent publishes confirmed at the log's commit, a consumer that
    acknowledges every delivery, checkpoints every 50 ms. The log's, the
    store's and the settle path's loop-side work is on the loop's line under
    four names, flat among the others, a span a callback and never one a
    message."""
    from chanamq_tpu.store.sqlite import SqliteStore
    from chanamq_tpu.wal import WalStore

    bursts, burst = 4, 64
    messages = bursts * burst

    store = WalStore(SqliteStore(str(tmp_path / "store.db")),
                     flush_ms=1.0, checkpoint_ms=50.0)
    server = BrokerServer(host="127.0.0.1", port=0, heartbeat_s=0,
                          store=store)
    # as server.main() wires them: the log counts into the broker's registry
    metrics = store.metrics = server.broker.metrics

    async def drive(port: int) -> None:
        c = await AMQPClient.connect("127.0.0.1", port)
        ch = await c.channel()
        await ch.exchange_declare("ex", "topic", durable=True)
        await ch.queue_declare("q1", durable=True)
        await ch.queue_bind("q1", "ex", "a.*.c")
        got = []
        await ch.basic_qos(prefetch_count=1000)

        def on_msg(msg) -> None:
            got.append(msg)
            ch.basic_ack(msg.delivery_tag)

        await ch.basic_consume("q1", on_msg, no_ack=False)
        await ch.confirm_select()
        for n in range(bursts):
            for i in range(burst):
                ch.basic_publish(
                    b"m", exchange="ex", routing_key=f"a.{n}-{i}.c",
                    properties=BasicProperties(delivery_mode=2))
            await ch.wait_unconfirmed_below(1)
            await asyncio.sleep(0.06)  # a checkpoint's drain in between
        for _ in range(300):
            if (metrics.wal_settle_rows == messages
                    and metrics.wal_memtable_drains >= 2):
                break
            await asyncio.sleep(0.01)
        assert len(got) == messages
        assert metrics.acked_msgs == metrics.wal_settle_rows == messages
        await c.close()

    event_loop.run_until_complete(_traced(tmp_path / "trace", server, drive))
    assert metrics.router_kernel_launches >= 1
    assert metrics.wal_queue_msgs_committed == messages
    assert metrics.wal_commit_errors == 0
    # all four are emitted, on the loop's line and flat among the router's
    _, spans = _flat_spans_on_the_loops_line(
        str(tmp_path / "trace"), SPANS + DURABLE_SPANS)
    count = {name: sum(1 for e in spans if e[0] == name)
             for name in DURABLE_SPANS}
    # a commit is two spans (either side of the executor's write + fsync);
    # a settle or a deliver callback is one a queue and a loop tick: far
    # fewer than the messages they cover
    assert 2 * bursts <= count["wal.commit"] <= messages // 4, count
    assert 1 <= count["store.settle"] <= messages // 4, count
    assert 1 <= count["store.deliver"] <= messages // 4, count
    assert 2 <= count["wal.checkpoint"] <= 2 * (bursts + 8), count
