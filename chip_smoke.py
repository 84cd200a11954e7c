#!/usr/bin/env python3
"""The quickest proof that the broker still starts, routes and trains on
the chip: `python chip_smoke.py`.

One broker child (`python -m chanamq_tpu.broker.server`, real sockets, the
in-repo client) holds the chip; this parent never imports jax. In one run:

  native    build native/ from the committed source, say native or Python
  boot 1    start the broker, read the device it claimed from /admin/overview
  topic     one topic exchange at the router's caps (512 wildcard patterns,
            ~10,000 exact patterns, 640 queues), >=20,000 confirmed publishes
            over >=10,000 distinct keys from 3 producer connections, every
            queue consumed and compared with the Python TopicMatcher
  headers   one headers exchange, 256 x-match all/any bindings, >=5,000
            confirmed publishes, compared with the Python HeadersMatcher
  forecast  /admin/forecast shows >=2 rounds, a finite loss, no error
  shutdown  SIGTERM, exit code 0
  boot 2    same compile cache, a replay of the first seeded traffic; the
            second boot must report compile-cache hits

Every line but the last is a diagnostic. The last line of standard output
is one JSON object, {"ok": ..., "device": {"platform", "kind", "count"}},
with the device as JAX reported it inside the broker. `ok` is true only
when every phase passed AND that platform is "tpu"; any failure exits
non-zero. Under JAX_PLATFORMS=cpu (the sandbox, tests/test_chip_smoke.py)
the same phases run as a rehearsal and the run ends with "ok": false.

Arguments shrink or relocate the smoke, never the broker: --scale small is
the CPU rehearsal's size, --no-build keeps the native library as it is
(the test suite's workers share that file), --out is where child logs go.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import json
import logging
import math
import os
import random
import signal
import socket
import subprocess
import sys
import time
import traceback
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

log = logging.getLogger("chip_smoke")

SCALES = {
    # name: topic (exact, wildcard, queues, keys), headers (bindings,
    # queues, messages), messages replayed on the second boot
    "full": {"exact": 10_000, "wild": 512, "queues": 640, "keys": 12_000,
             "h_bindings": 256, "h_queues": 128, "h_msgs": 6_000,
             "replay": 9_000},
    "small": {"exact": 300, "wild": 64, "queues": 32, "keys": 400,
              "h_bindings": 32, "h_queues": 16, "h_msgs": 300,
              "replay": 400},
}
PRODUCERS = 3
CONSUMER_CONNS = 2
CONFIRM_WINDOW = 1_000
# one router flush stalls the broker's loop for a first compile; confirms
# and deliveries behind it wait that long
WAIT_S = 300.0
BOOT_S = 300.0
FORECAST_S = 300.0

ROUTER_COUNTERS = ("router_kernel_launches", "router_batches",
                   "router_batch_msgs", "router_fallback_msgs",
                   "router_compiles")


class SmokeFailure(Exception):
    pass


def say(line: str) -> None:
    print(line, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, path: str, timeout: float = 10.0):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as resp:
        return json.loads(resp.read())


# -- the broker child ---------------------------------------------------------


class BrokerChild:
    """One `python -m chanamq_tpu.broker.server` with its output in a file."""

    def __init__(self, name: str, out_dir: str) -> None:
        self.name = name
        self.port = free_port()
        self.admin_port = free_port()
        self.log_path = os.path.join(out_dir, f"broker-{name}.log")
        self.proc: "subprocess.Popen | None" = None

    def start(self) -> None:
        env = dict(os.environ)
        env["PYTHONPATH"] = HERE
        env.update({
            # a few forecaster rounds inside the run: 65 samples fill the
            # default 64-tick window in ~3 s, then a round every 2 s
            "CHANAMQ_FORECAST_ENABLED": "true",
            "CHANAMQ_FORECAST_INTERVAL": "50ms",
            "CHANAMQ_FORECAST_TRAIN_INTERVAL": "2s",
            # the telemetry tick is the broker's loop-lag probe
            "CHANAMQ_TELEMETRY_ENABLED": "true",
            "CHANAMQ_TELEMETRY_INTERVAL": "100ms",
        })
        with open(self.log_path, "wb") as log_file:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "chanamq_tpu.broker.server",
                 "--host", "127.0.0.1", "--port", str(self.port),
                 "--admin-port", str(self.admin_port),
                 "--log-level", "INFO"],
                cwd=HERE, env=env, stdin=subprocess.DEVNULL,
                stdout=log_file, stderr=log_file)

    def wait_ready(self) -> dict:
        """The /admin/overview document once the node serves it (the admin
        server starts last, after the listeners and the forecaster)."""
        deadline = time.monotonic() + BOOT_S
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"broker {self.name} exited rc={self.proc.returncode} "
                    f"during boot: {self.log_tail()}")
            try:
                return http_json(self.admin_port, "/admin/overview", 5.0)
            except OSError:
                time.sleep(0.25)
        raise SmokeFailure(f"broker {self.name} not ready in {BOOT_S:.0f}s: "
                           f"{self.log_tail()}")

    def metrics(self) -> dict:
        return http_json(self.admin_port, "/admin/metrics")

    def terminate(self) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"broker {self.name} still draining 60s after SIGTERM: "
                f"{self.log_tail()}") from None

    def kill(self) -> None:
        """Make sure the child is gone and reaped (a no-op once it exited)."""
        if self.proc is not None:
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait()

    def log_tail(self, limit: int = 1500) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - limit))
                return " | ".join(
                    f.read().decode("utf-8", "replace").splitlines())
        except OSError as exc:
            return f"(no log: {exc})"


# -- seeded workloads and their plain reference -------------------------------


class Workload:
    """One exchange's bindings, its messages, and what the Python matcher
    says each message must reach."""

    def __init__(self, kind: str, exchange: str) -> None:
        self.kind = kind
        self.exchange = exchange
        self.queues: list[str] = []
        self.bindings: list[tuple[str, str, "dict | None"]] = []
        # (routing_key, headers-or-None) per message, in publish order
        self.messages: list[tuple[str, "dict | None"]] = []
        self.expected: list[frozenset] = []

    def head(self, n: int) -> "Workload":
        sub = Workload(self.kind, self.exchange)
        sub.queues, sub.bindings = self.queues, self.bindings
        sub.messages, sub.expected = self.messages[:n], self.expected[:n]
        return sub


def topic_workload(scale: dict, rng: random.Random) -> Workload:
    from chanamq_tpu.broker.matchers import TopicMatcher

    w = Workload("topic", "smoke.topic")
    n_exact, n_wild, n_queues = scale["exact"], scale["wild"], scale["queues"]
    w.queues = [f"tq{i}" for i in range(n_queues)]
    for i in range(n_exact):
        w.bindings.append(
            (f"t{i % 97}.k{i}.s{i % 31}", f"tq{i % n_queues}", None))
    # the kernel rows: a mix of '*' and single-'#' shapes (prefix.#,
    # a.*.c, #.suffix, *.b.#), each on its own queue so the kernel's
    # destination mask spans min(wild, queues) queues
    for j in range(n_wild):
        pattern = (f"w{j % 97}.k{j}.#", f"t{j % 97}.*.s{j % 31}",
                   f"#.z{j}", f"*.k{j}.#")[j % 4]
        w.bindings.append((pattern, f"tq{j % n_queues}", None))
    keys: set[str] = set()
    n_keys = scale["keys"]
    exact_ids = rng.sample(range(n_exact), min(n_exact, n_keys * 55 // 100))
    keys.update(f"t{i % 97}.k{i}.s{i % 31}" for i in exact_ids)
    serial = 0
    while len(keys) < n_keys:
        serial += 1
        j = rng.randrange(n_wild)
        shape = rng.random()
        if shape < 0.30:    # a.*.c rows (half aimed at one, half anywhere)
            a, b = ((j % 97, j % 31) if rng.random() < 0.5
                    else (rng.randrange(97), rng.randrange(31)))
            keys.add(f"t{a}.x{serial}.s{b}")
        elif shape < 0.55:  # prefix.# rows, '#' taking 0..3 words
            tail = "".join(f".u{serial}" for _ in range(rng.randrange(4)))
            keys.add(f"w{j % 97}.k{j}{tail}" if tail
                     else f"w{j % 97}.k{rng.randrange(n_wild)}")
        elif shape < 0.75:  # #.suffix rows
            keys.add(f"m{serial}.z{j}" if rng.random() < 0.5
                     else f"m{serial}.n.o.z{j}")
        else:               # routes nowhere
            keys.add(f"miss.{serial}.z")
    matcher = TopicMatcher()
    for pattern, queue, _ in w.bindings:
        matcher.bind(pattern, queue)
    ordered = sorted(keys)
    rng.shuffle(ordered)
    routed = {key: frozenset(matcher.route(key)) for key in ordered}
    # every key twice: the second sight may come from the router's key memo
    # or, once 8,192 keys have cleared it, from the kernel again
    stream = ordered + ordered
    rng.shuffle(stream)
    w.messages = [(key, None) for key in stream]
    w.expected = [routed[key] for key in stream]
    return w


def headers_workload(scale: dict, rng: random.Random) -> Workload:
    from chanamq_tpu.broker.matchers import HeadersMatcher

    w = Workload("headers", "smoke.headers")
    n_bind, n_queues = scale["h_bindings"], scale["h_queues"]
    w.queues = [f"hq{i}" for i in range(n_queues)]
    names = [f"h{i}" for i in range(12)]
    values: list = [f"v{i}" for i in range(5)] + [1, 2, 3]
    seen: set = set()
    while len(w.bindings) < n_bind:
        b = len(w.bindings)
        args: dict = {"x-match": "all" if b % 2 else "any"}
        for name in rng.sample(names, rng.randrange(1, 4)):
            args[name] = rng.choice(values)
        key = (f"hq{b % n_queues}", repr(sorted(args.items(), key=str)))
        if key in seen:
            continue
        seen.add(key)
        w.bindings.append(("", f"hq{b % n_queues}", args))
    matcher = HeadersMatcher()
    for _, queue, args in w.bindings:
        matcher.bind("", queue, args)
    for _ in range(scale["h_msgs"]):
        headers = {name: rng.choice(values)
                   for name in rng.sample(names, rng.randrange(1, 5))}
        w.messages.append(("", headers))
        w.expected.append(frozenset(matcher.route("", headers)))
    return w


# -- traffic ------------------------------------------------------------------


async def declare(port: int, w: Workload) -> None:
    from chanamq_tpu.client import AMQPClient

    conn = await AMQPClient.connect("127.0.0.1", port)
    try:
        ch = await conn.channel()
        await ch.exchange_declare(w.exchange, w.kind)
        for queue in w.queues:
            await ch.queue_declare(queue)
        for key, queue, args in w.bindings:
            await ch.queue_bind(queue, w.exchange, key, arguments=args)
    finally:
        await conn.close()


async def drive(port: int, w: Workload) -> dict:
    """Consume every queue, publish every message with confirms from
    PRODUCERS connections, wait for the deliveries, and compare each
    queue's deliveries with the reference. Bodies are message indexes."""
    from chanamq_tpu.amqp.properties import BasicProperties
    from chanamq_tpu.client import AMQPClient

    delivered: dict[str, list[int]] = {q: [] for q in w.queues}
    got = 0

    def on_message(msg) -> None:
        nonlocal got
        got += 1
        delivered[msg.consumer_tag].append(int(msg.body))

    conns = []
    try:
        for c in range(CONSUMER_CONNS):
            conn = await AMQPClient.connect("127.0.0.1", port)
            conns.append(conn)
            ch = await conn.channel()
            for queue in w.queues[c::CONSUMER_CONNS]:
                await ch.basic_consume(
                    queue, on_message, consumer_tag=queue, no_ack=True)

        async def produce(p: int) -> int:
            conn = await AMQPClient.connect("127.0.0.1", port)
            conns.append(conn)
            ch = await conn.channel()
            await ch.confirm_select()
            sent = 0
            for idx in range(p, len(w.messages), PRODUCERS):
                key, headers = w.messages[idx]
                props = (BasicProperties(headers=headers)
                         if headers is not None else None)
                ch.basic_publish(b"%d" % idx, exchange=w.exchange,
                                 routing_key=key, properties=props)
                sent += 1
                if len(ch.unconfirmed) >= CONFIRM_WINDOW:
                    await ch.wait_unconfirmed_below(
                        CONFIRM_WINDOW // 2, timeout=WAIT_S)
            await ch.wait_unconfirmed_below(1, timeout=WAIT_S)
            return sent

        confirmed = sum(await asyncio.gather(
            *(produce(p) for p in range(PRODUCERS))))
        want = sum(len(names) for names in w.expected)
        deadline = time.monotonic() + WAIT_S
        while got < want and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.5)  # a duplicate or stray would land now
    finally:
        for conn in conns:
            try:
                await conn.close()
            except Exception:  # noqa: BLE001 — already failing or done
                log.debug("client close failed", exc_info=True)

    should: dict[str, list[int]] = {q: [] for q in w.queues}
    for idx, names in enumerate(w.expected):
        for queue in names:
            should[queue].append(idx)
    wrong_queues = 0
    duplicates = 0
    for queue in w.queues:
        counts = collections.Counter(delivered[queue])
        duplicates += sum(n - 1 for n in counts.values() if n > 1)
        if sorted(counts) != should[queue]:
            wrong_queues += 1
    return {"published": len(w.messages), "confirmed": confirmed,
            "expected": want, "delivered": got,
            "wrong_queues": wrong_queues, "duplicates": duplicates}


def run_workload(broker: BrokerChild, w: Workload, label: str) -> dict:
    """Declare, drive, compare; one diagnostic line; fail on any mismatch
    or when no jitted kernel call happened during the phase."""
    t0 = time.monotonic()
    asyncio.run(declare(broker.port, w))
    before = broker.metrics()
    result = asyncio.run(drive(broker.port, w))
    after = broker.metrics()
    moved = {name: after[name] - before[name] for name in ROUTER_COUNTERS}
    wild = sum(1 for key, _, args in w.bindings
               if args is not None or "*" in key or "#" in key)
    say(f"{label}: bindings={len(w.bindings)} kernel_rows={wild} "
        f"queues={len(w.queues)} "
        f"distinct_keys={len({m[0] for m in w.messages})} "
        + " ".join(f"{k}={v}" for k, v in result.items()) + " "
        + " ".join(f"{k}=+{v}" for k, v in moved.items())
        + f" seconds={time.monotonic() - t0:.1f}")
    if (result["confirmed"] != result["published"]
            or result["delivered"] != result["expected"]
            or result["wrong_queues"] or result["duplicates"]):
        raise SmokeFailure(
            f"{label}: deliveries differ from the Python matcher: {result}")
    if moved["router_kernel_launches"] <= 0:
        raise SmokeFailure(
            f"{label}: no jitted kernel call during the phase — the memo, "
            f"the fallback or the numpy twin served it ({moved})")
    return moved


def wait_forecast(broker: BrokerChild, rounds: int) -> None:
    """ForecastService survives a failing round by design (it keeps
    last_error and the broker stays up), so the smoke reads the fields."""
    deadline = time.monotonic() + FORECAST_S
    doc: dict = {}
    while time.monotonic() < deadline:
        doc = http_json(broker.admin_port, "/admin/forecast")
        if doc.get("rounds", 0) >= rounds or broker.proc.poll() is not None:
            break
        time.sleep(0.5)
    loss = doc.get("loss")
    say(f"{broker.name} forecast: rounds={doc.get('rounds')} "
        f"trained_steps={doc.get('trained_steps')} loss={loss} "
        f"samples={doc.get('samples')} window={doc.get('window')} "
        f"forecast_features={len(doc.get('forecast') or {})} "
        f"error={doc.get('error')}")
    if not (doc.get("rounds", 0) >= rounds and doc.get("trained_steps", 0) > 0
            and isinstance(loss, float) and math.isfinite(loss)
            and doc.get("forecast") and doc.get("error") is None):
        raise SmokeFailure(f"forecaster did not train cleanly: {doc}")
    bad = [k for k, v in doc["forecast"].items() if not math.isfinite(v)]
    if bad:
        raise SmokeFailure(f"non-finite forecast features: {bad}")


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for name in os.listdir(cache_dir)
                   if name.endswith("-cache"))
    except FileNotFoundError:
        return 0


# -- phases -------------------------------------------------------------------


def build_native(build: bool) -> None:
    if build:
        for target in (["clean"], []):
            proc = subprocess.run(
                ["make", "-C", os.path.join(HERE, "native"), *target],
                stdin=subprocess.DEVNULL, capture_output=True, text=True,
                timeout=300)
            if proc.returncode != 0:
                raise SmokeFailure(
                    f"make -C native {' '.join(target)} failed "
                    f"rc={proc.returncode}: {proc.stderr[-800:]}")
    from chanamq_tpu import native_ext

    native = native_ext.pipeline_available()
    say(f"native: {'rebuilt from source' if build else 'build skipped'}; "
        f"the broker will run {'native (C++ scan/encode)' if native else 'pure Python'}")
    if not native:
        # the router only batches behind the native frame scan: without it
        # no publish reaches the kernels and the phases below cannot pass
        raise SmokeFailure("native library unavailable after the build")


def boot(name: str, out_dir: str, brokers: list) -> "tuple[BrokerChild, dict]":
    broker = BrokerChild(name, out_dir)
    brokers.append(broker)
    t0 = time.monotonic()
    broker.start()
    overview = broker.wait_ready()
    device = overview.get("device")
    if not device:
        raise SmokeFailure(f"broker {name} claimed no device: "
                           f"{broker.log_tail()}")
    say(f"{name}: ready in {time.monotonic() - t0:.1f}s pid={broker.proc.pid} "
        f"device platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} router_backend={overview['router_backend']} "
        f"native={overview['native']} "
        f"compile_cache={device['compile_cache']['dir']}")
    if overview["router_backend"] != "jax" or not overview["native"]:
        raise SmokeFailure(f"broker {name} is not on the device path: "
                           f"{overview['router_backend']=} {overview['native']=}")
    return broker, device


def shutdown(broker: BrokerChild) -> dict:
    """Loop-lag and cache counts as the broker saw them, then SIGTERM."""
    metrics = broker.metrics()
    device = http_json(broker.admin_port, "/admin/overview")["device"]
    rc = broker.terminate()
    say(f"{broker.name}: SIGTERM -> exit code {rc}; longest event-loop stall "
        f"seen by the 100 ms lag probe "
        f"{metrics.get('telemetry_loop_lag_max_ms')} ms; compile cache "
        f"hits={device['compile_cache']['hits']} "
        f"misses={device['compile_cache']['misses']} "
        f"entries={cache_entries(device['compile_cache']['dir'])}")
    if rc != 0:
        raise SmokeFailure(f"broker {broker.name} exited {rc} on SIGTERM: "
                           f"{broker.log_tail()}")
    return device["compile_cache"]


def run(args, brokers: list, found: dict) -> None:
    """Every phase in order. ``found`` receives the device the first boot
    names, as soon as it is known, so a later failure still reports it."""
    scale = SCALES[args.scale]
    say(f"chip_smoke: scale={args.scale} seed={args.seed} out={args.out} "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', 'unset')} "
        f"JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', 'unset')}")
    build_native(args.build)
    rng = random.Random(args.seed)
    topic = topic_workload(scale, rng)
    headers = headers_workload(scale, rng)

    broker, device = boot("boot1", args.out, brokers)
    found.update({k: device[k] for k in ("platform", "kind", "count")})
    cache_dir = device["compile_cache"]["dir"]
    say(f"compile cache: dir={cache_dir} entries_before_traffic="
        f"{cache_entries(cache_dir)}")
    run_workload(broker, topic, "topic")
    run_workload(broker, headers, "headers")
    wait_forecast(broker, rounds=2)
    shutdown(broker)

    broker2, device2 = boot("boot2", args.out, brokers)
    if ({k: device2[k] for k in found} != found
            or device2["compile_cache"]["dir"] != cache_dir):
        raise SmokeFailure(f"second boot saw another device or cache: "
                           f"{device} vs {device2}")
    run_workload(broker2, topic.head(scale["replay"]), "replay-topic")
    run_workload(broker2, headers.head(scale["replay"] // 4),
                 "replay-headers")
    # the train step's shapes never vary: once it has run here, this boot
    # has read at least that much back from the first boot's cache
    wait_forecast(broker2, rounds=1)
    cache2 = shutdown(broker2)
    if cache2["hits"] <= 0:
        raise SmokeFailure(
            f"second boot read nothing back from the compile cache "
            f"{cache_dir}: {cache2}")


def main() -> "tuple[bool, dict | None]":
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--scale", choices=sorted(SCALES), default="full")
    parser.add_argument("--seed", type=int, default=22)
    parser.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"))
    parser.add_argument("--no-build", dest="build", action="store_false")
    args = parser.parse_args()
    logging.basicConfig(stream=sys.stderr, level=logging.INFO,
                        format="%(asctime)s %(levelname)s %(name)s %(message)s")
    brokers: list[BrokerChild] = []
    found: dict = {}
    passed = False
    try:
        import chanamq_tpu  # noqa: F401 — fail here when run without the repo

        os.makedirs(args.out, exist_ok=True)
        run(args, brokers, found)
        passed = True
    except Exception as exc:  # noqa: BLE001 — every failure ends the same way
        traceback.print_exc(file=sys.stderr)
        say(f"FAILED: {type(exc).__name__}: {exc}"[:2000].replace("\n", " "))
    finally:
        for broker in brokers:
            broker.kill()
    if "jax" in sys.modules:
        say("FAILED: the smoke's parent imported jax")
        passed = False
    if passed and found["platform"] != "tpu":
        say(f"every phase passed, but on {found['platform']!r}: a rehearsal, "
            "not a chip run")
        passed = False
    return passed, found or None


if __name__ == "__main__":
    ok, device = main()
    if not ok:
        print(json.dumps({"ok": False, "device": device}), flush=True)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
