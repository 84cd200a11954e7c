#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (benchmarks/configs/<config>.json: the
deployment's binding table, which may be a graph of exchanges, and in its
`applied` section whether it is durable, whether its publishes are
persistent, how its consumers acknowledge and which options its broker is
started with: reference.applied) and a traffic mix
(benchmarks/traffic/<traffic>.json). This parent never imports jax. It
builds native/, starts ONE broker child that holds the chip (through
broker_launch.py, which is `chanamq_tpu.broker.server` with the profiler's
two signal handlers and a watch for stalls of its event loop in front), and meanwhile works out from the plain
reference which queues every pool entry must reach; declares and binds over
8 connections; starts the consumer and producer processes of loadgen.py;
lets them warm up; measures for --seconds; waits for the last delivery;
compares every delivery of the whole run with the reference; SIGTERMs the
broker (exit 0 required); prints diagnostics and, as the LAST line of
standard output, the result object. The first run of a cell in a checkout
does all that twice: once for a second, to fill the compile cache (see
`primed`), and then for the result.

--trace 0 reports the cell's end-to-end metrics, profiler off. --trace 1
reports its per-layer metrics: host counters over the untraced part of the
window, device numbers from a `jax.profiler` trace of the window's last
seconds, taken inside the broker process and reduced by trace_reduce.py.

Arguments of the harness, not of the program: --scale small is the CPU
rehearsal's size (with JAX_PLATFORMS=cpu, the only way past the look for a
chip); --control puts the reference, one guarantee broken, in the program's
place (reference.CONTROLS) and must end `correct: false`; --fault plants a
fault under the timed path (the benchmark's tests); --out is where logs go.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback
import urllib.request

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

import hoststat  # noqa: E402
import reference  # noqa: E402

BOOT_S = 300.0
DRAIN_S = 60.0     # "a minute past the close if need be"
STRAY_S = 0.5      # a duplicate or stray delivery would land now
SETTLE_S = 2.0     # no delivery for this long and no queue holds a message
SPAWN_LEAD_S = 2.5  # child start-up before its first publish
DECLARE_CONNS = 8
TRACE_SPAN_S = 3.0
PRIME_S = 1.0      # window of the run that only fills the compile cache
# one cache per checkout at a fixed path (the path is part of the cache's
# key); device.py takes the one it is given and sets no other
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# the load generator's children hash their strings alike in every run. The
# broker's hash seed is --seed's: users run it under Python's per-process
# hash randomisation, which moves the order in which it walks its sets of
# queue names and with it the latency quantiles by a tenth (PERF.md 6.4), so
# the runs of a set sample that as they sample the arrivals, and the same
# --seed gives the same run
LOADGEN_ENV = {"PYTHONHASHSEED": "0"}
# cores: the broker process (event loop, JAX's and the chip runtime's
# threads) keeps BROKER_CORES of the cores this process may use, every
# producer and consumer one of its own, so none is moved about or shares a
# core with another; on a host with too few cores nothing is pinned
BROKER_CORES = 4


class RunFailure(Exception):
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


def proc_cpu_s(pid: int) -> float:
    """Cumulative user+system CPU seconds of a process (/proc/<pid>/stat:
    the real fields start after the last ')'; utime, stime are 14, 15)."""
    with open(f"/proc/{pid}/stat", "rb") as f:
        fields = f.read().decode("ascii", "replace").rpartition(")")[2].split()
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


def sleep_until_ns(deadline_ns: int) -> None:
    delay = (deadline_ns - time.monotonic_ns()) / 1e9
    if delay > 0:
        time.sleep(delay)


def tail(path: str, limit: int = 1500) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - limit))
            return " | ".join(f.read().decode("utf-8", "replace").splitlines())
    except OSError as exc:
        return f"(no log: {exc})"


# -- the broker child ----------------------------------------------------------


class BrokerChild:
    """The one process that holds the chip, its output in a file."""

    def __init__(self, out_dir: str, fault: "str | None", seed: int,
                 cores: "set | None", applied: dict) -> None:
        self.seed, self.cores = seed, cores
        self.out_dir, self.applied = out_dir, applied
        self.port = free_port()
        self.admin_port = free_port()
        self.control = os.path.join(out_dir, "control")
        self.log_path = os.path.join(out_dir, "broker.log")
        self.fault = fault
        self.proc: "subprocess.Popen | None" = None

    def server_args(self) -> list:
        """What the configuration states of its broker, as server.main()'s
        own arguments: its options in a --config file, and for a durable
        deployment a --store; both inside the run's directory, which every
        run (the priming run too) empties first, so each starts from an
        empty store."""
        args: list = []
        if self.applied["broker_options"]:
            options = os.path.join(self.out_dir, "broker_options.json")
            with open(options, "w", encoding="utf-8") as f:
                json.dump(self.applied["broker_options"], f, indent=1)
            args += ["--config", options]
        if self.applied["durable"]:
            os.makedirs(os.path.join(self.out_dir, "store"))
            args += ["--store",
                     os.path.join(self.out_dir, "store", "broker.db")]
        return args

    def start(self) -> None:
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT
        # JAX does not make the directory: without it every write fails
        # with a warning and every run compiles again
        env["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        os.makedirs(CACHE_DIR, exist_ok=True)
        env["PYTHONHASHSEED"] = str(self.seed % 2**32)
        env.pop("BENCH_RUN", None)
        # a size cap switches JAX's cache to LRU bookkeeping, and one entry
        # written without it (no -atime file) then fails every later write;
        # this cache holds a few MB and needs no eviction
        env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
        command = [sys.executable, os.path.join(HERE, "broker_launch.py"),
                   "--control", self.control]
        if self.fault:
            command += ["--fault", self.fault]
        command += ["--", "--host", "127.0.0.1", "--port", str(self.port),
                    "--admin-port", str(self.admin_port),
                    "--log-level", "INFO", *self.server_args()]
        with open(self.log_path, "wb") as log_file:
            self.proc = subprocess.Popen(
                command, cwd=ROOT, env=env, stdin=subprocess.DEVNULL,
                stdout=log_file, stderr=log_file)
        pin(self.proc.pid, self.cores)

    def overview(self) -> dict:
        return http_json(self.admin_port, "/admin/overview")

    def wait_ready(self) -> dict:
        deadline = time.monotonic() + BOOT_S
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RunFailure(
                    f"broker exited rc={self.proc.returncode} during boot: "
                    f"{tail(self.log_path)}")
            try:
                return http_json(self.admin_port, "/admin/overview", 5.0)
            except OSError:
                time.sleep(0.1)
        raise RunFailure(f"broker not ready in {BOOT_S:.0f}s: "
                         f"{tail(self.log_path)}")

    def signal_and_wait(self, sig: int, marker: str, timeout: float):
        path = os.path.join(self.control, marker)
        self.proc.send_signal(sig)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if os.path.exists(path):
                with open(path, encoding="utf-8") as f:
                    return json.load(f)
            if self.proc.poll() is not None:
                break
            time.sleep(0.01)
        raise RunFailure(f"broker did not write {marker}: "
                         f"{tail(self.log_path)}")

    def terminate(self) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise RunFailure(f"broker still draining 60s after SIGTERM: "
                             f"{tail(self.log_path)}") from None

    def kill(self) -> None:
        if self.proc is not None:
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait()


def place(loadgen_procs: int) -> "tuple[set | None, list]":
    """(the broker's cores, one core for each load-generator process), or
    (None, []) where this process may use too few."""
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < BROKER_CORES + loadgen_procs + 1:
        return None, []
    return (set(cores[:BROKER_CORES]),
            cores[BROKER_CORES:BROKER_CORES + loadgen_procs])


def pin(pid: int, cores: "set | None") -> None:
    """Threads the child starts later inherit it; it has one so far."""
    if cores:
        os.sched_setaffinity(pid, cores)


def build_native() -> None:
    """`make -C native` (a no-op once built: a checkout holds the source
    only) and whether the C++ scan/encode loaded. The router only batches
    behind the native frame scan, so without it no cell can run."""
    proc = subprocess.run(
        ["make", "-C", os.path.join(ROOT, "native")],
        stdin=subprocess.DEVNULL, capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        raise RunFailure(f"make -C native failed rc={proc.returncode}: "
                         f"{proc.stderr[-800:]}")
    from chanamq_tpu import native_ext

    if not native_ext.pipeline_available():
        raise RunFailure("native library unavailable after the build")


# -- topology and load ---------------------------------------------------------


async def declare(port: int, table: dict, applied: dict) -> None:
    """Exchanges, queues, then bindings (a graph's exchange-to-exchange
    bindings last, once every exchange exists), spread over DECLARE_CONNS
    connections (one connection spends a round trip per bind)."""
    from chanamq_tpu.client import AMQPClient

    flags = {"durable": True} if applied["durable"] else {}
    root = table["exchange"]
    queue_binds = [(root, queue, key, args)
                   for key, queue, args in table["bindings"]]
    queue_binds += table.get("queue_bindings", [])
    conns = [await AMQPClient.connect("127.0.0.1", port)
             for _ in range(DECLARE_CONNS)]
    try:
        chans = [await conn.channel() for conn in conns]
        for name, kind in reference.table_exchanges(table):
            await chans[0].exchange_declare(name, kind, **flags)

        async def queues(i: int) -> None:
            for queue in table["queues"][i::DECLARE_CONNS]:
                await chans[i].queue_declare(queue, **flags)

        async def binds(i: int) -> None:
            for exchange, queue, key, args in queue_binds[i::DECLARE_CONNS]:
                await chans[i].queue_bind(queue, exchange, key, arguments=args)

        async def exchange_binds(i: int) -> None:
            for source, destination, key, args in table.get(
                    "exchange_bindings", [])[i::DECLARE_CONNS]:
                await chans[i].exchange_bind(
                    destination, source, key, arguments=args)

        for step in (queues, binds, exchange_binds):
            await asyncio.gather(*(step(i) for i in range(DECLARE_CONNS)))
    finally:
        for conn in conns:
            await conn.close()


class LoadChildren:
    """The consumer and producer processes of one run."""

    def __init__(self, args, cell: dict, mix: dict, port: int,
                 out_dir: str, cores: list) -> None:
        self.cores = list(cores)
        self.base = [
            sys.executable, os.path.join(HERE, "loadgen.py"), "ROLE",
            "--port", str(port), "--config", cell["config"],
            "--traffic", cell["traffic"], "--scale", args.scale,
            "--seed", str(args.seed), "--out", out_dir]
        self.mix = mix
        self.out_dir = out_dir
        self.consumers: list = []
        self.producers: list = []
        self.logs: list = []

    def _spawn(self, role: str, index: int, extra: list, stdin):
        command = list(self.base)
        command[2] = role
        log = open(os.path.join(self.out_dir, f"{role}-{index}.err"), "wb")
        self.logs.append(log)
        env = dict(os.environ, **LOADGEN_ENV)
        env.pop("BENCH_RUN", None)
        child = subprocess.Popen(
            command + ["--index", str(index), *extra], cwd=ROOT, env=env,
            stdin=stdin, stdout=subprocess.PIPE, stderr=log, text=True)
        if self.cores:
            pin(child.pid, {self.cores.pop()})
        return child

    def start_consumers(self) -> None:
        for i in range(self.mix["consumers"]):
            self.consumers.append(
                self._spawn("consumer", i, [], subprocess.PIPE))
        for child in self.consumers:
            line = child.stdout.readline()
            if "ready" not in line:
                raise RunFailure(f"consumer did not subscribe: {line!r}")

    def start_producers(self, start_ns: int, end_ns: int) -> None:
        for i in range(self.mix["producers"]):
            self.producers.append(self._spawn(
                "producer", i,
                ["--start-ns", str(start_ns), "--end-ns", str(end_ns)],
                subprocess.DEVNULL))

    def pids(self) -> list:
        return [c.pid for c in self.consumers + self.producers]

    def _result(self, role: str, i: int, child, stdout: str) -> dict:
        lines = stdout.strip().splitlines()
        if child.returncode != 0 or not lines:
            raise RunFailure(
                f"{role} {i} rc={child.returncode}: " + tail(
                    os.path.join(self.out_dir, f"{role}-{i}.err")))
        return json.loads(lines[-1])

    def reap_producers(self, timeout: float) -> list:
        out = []
        for i, child in enumerate(self.producers):
            try:
                stdout, _ = child.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                raise RunFailure(f"producer {i} did not end") from None
            out.append(self._result("producer", i, child, stdout))
        return out

    def delivered(self) -> int:
        total = 0
        for child in self.consumers:
            child.stdin.write("count\n")
            child.stdin.flush()
            total += json.loads(child.stdout.readline())["count"]
        return total

    def stop_consumers(self) -> list:
        out = []
        for i, child in enumerate(self.consumers):
            try:
                stdout, _ = child.communicate("stop\n", timeout=120)
            except subprocess.TimeoutExpired:
                raise RunFailure(f"consumer {i} did not end") from None
            out.append(self._result("consumer", i, child, stdout))
        return out

    def kill(self) -> None:
        for child in self.consumers + self.producers:
            if child.poll() is None:
                child.kill()
            child.wait()
        for log in self.logs:
            log.close()


# -- one run -------------------------------------------------------------------


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise RunFailure(f"no workload {name!r} in BENCHMARK.json")


def metric_applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def check_device(device: "dict | None", chips: int) -> None:
    """A run without the chip fails, unless the CPU was asked for by name
    (a rehearsal: the line then says `cpu`)."""
    if not device:
        raise RunFailure("the broker claimed no device")
    asked = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    if device["platform"] != "tpu" and device["platform"] != asked:
        raise RunFailure(f"the broker runs on {device['platform']!r}, not on "
                         "the TPU")
    if device["platform"] == "tpu" and device["count"] < chips:
        raise RunFailure(f"the cell needs {chips} chips, JAX found "
                         f"{device['count']}")


def snapshot(broker: BrokerChild, loadgen: LoadChildren) -> dict:
    pid = broker.proc.pid
    threads = hoststat.threads(pid)
    return {"ns": time.monotonic_ns(),
            "admin": broker.overview(),
            "cpu": {"broker": proc_cpu_s(pid),
                    "broker_loop": hoststat.loop_cpu_s(pid, threads),
                    "loadgen": sum(proc_cpu_s(p) for p in loadgen.pids())},
            "threads": threads}


def wait_for_deliveries(broker: BrokerChild, loadgen: LoadChildren,
                        due: int) -> int:
    """Until the consumers hold every delivery that is due — a minute past
    the close if need be; a late one is late, not wrong. It ends sooner only
    when none can come any more: every queue of the broker is empty and the
    consumers' count has stood still for SETTLE_S. Then STRAY_S more, in
    which a duplicate or a stray would land."""
    deadline = time.monotonic() + DRAIN_S
    got, since = -1, time.monotonic()
    while time.monotonic() < deadline:
        now_got = loadgen.delivered()
        if now_got != got:
            got, since = now_got, time.monotonic()
        if got >= due:
            break
        if time.monotonic() - since > SETTLE_S and not sum(
                v["messages"] for v in broker.overview()["vhosts"].values()):
            break
        time.sleep(0.05)
    time.sleep(STRAY_S)
    return loadgen.delivered()


def report_stalls(control_dir: str, from_ns: int, start_ns: int) -> None:
    """What broker_launch.py's watch thread saw from `from_ns` on: every
    answer of the event loop that came over 1.5 s late, when (in
    seconds from the window's start) and, of the longest, where every
    thread of the broker stood while the loop was stuck."""
    try:
        with open(os.path.join(control_dir, "stalls.txt"),
                  encoding="utf-8") as f:
            text = f.read()
    except OSError:
        text = ""
    stalls, dumps = [], {}
    for line in text.splitlines():
        if line.startswith("STALL asked_ns="):
            asked, seconds = (part.split("=")[1] for part in line.split()[1:])
            if int(asked) >= from_ns:
                stalls.append((float(seconds), int(asked)))
    for block in text.split("STUCK at_ns=")[1:]:
        head, _, rest = block.partition("\n")
        dumps[int(head)] = rest.partition("STALL asked_ns=")[0]
    say(f"broker stalls over 1.5 s since the producers started: "
        f"{len(stalls)} " + " ".join(
            f"[{seconds:.2f}s at {(asked - start_ns) / 1e9:+.2f}s]"
            for seconds, asked in stalls))
    if stalls:
        seconds, asked = max(stalls)
        at = [t for t in dumps if asked <= t <= asked + seconds * 1e9]
        if at:
            say("the broker's threads during the longest: " + " | ".join(
                line.strip() for line in dumps[at[0]].splitlines()
                if line.strip())[-3000:])


def reduce_trace(control_dir: str) -> dict:
    """trace_reduce.py in a child (it imports jax to read the .xplane.pb;
    this parent does not), on the CPU, after the broker has gone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"),
         os.path.join(control_dir, "trace")],
        stdin=subprocess.DEVNULL, capture_output=True, text=True, env=env,
        timeout=240)
    if proc.returncode != 0:
        raise RunFailure(f"trace_reduce failed: {proc.stderr[-1500:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(args, state: dict) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = find_cell(bench, args.workload)
    cfg = reference.load_config(cell["config"], args.scale)
    applied = reference.applied(cfg)
    mix = reference.load_traffic(cell["traffic"], args.scale)
    out_dir = args.out or os.path.join(
        ROOT, "bench_out", f"{args.workload}-{args.seed}-t{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    say(f"{'run' if primed(args) else 'priming run'}: "
        f"workload={args.workload} config={cell['config']} "
        f"traffic={cell['traffic']} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} scale={args.scale} control={args.control} "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', 'unset')} "
        f"applied={json.dumps(applied)}")

    import chanamq_tpu  # noqa: F401 — fail here when run without the repo

    build_native()
    broker_cores, loadgen_cores = place(mix["producers"] + mix["consumers"])
    broker = state["broker"] = BrokerChild(
        out_dir, args.fault, args.seed, broker_cores, applied)
    t_boot = time.monotonic()
    broker.start()

    # while the broker claims the device: the table, the pool, and what the
    # plain reference says every pool entry reaches
    table = reference.build_table(cfg)
    pool = reference.build_pool(cfg, table, mix)
    draws = reference.stream_draws(mix, args.seed)
    expected = reference.expected_sets(table, pool)
    t_reference = time.monotonic() - t_boot

    overview = broker.wait_ready()
    boot_s = time.monotonic() - t_boot
    device = overview.get("device")
    check_device(device, cell["chips"])
    if overview["router_backend"] != "jax" or not overview["native"]:
        raise RunFailure(f"the broker is not on the device path: "
                         f"{overview['router_backend']=} {overview['native']=}")
    state["device"] = {k: device[k] for k in ("platform", "kind", "count")}
    say(f"broker: ready in {boot_s:.1f}s pid={broker.proc.pid} "
        f"platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} router_backend={overview['router_backend']} "
        f"path={'native (C++ scan/encode)' if overview['native'] else 'pure Python'} "
        f"compile_cache={device['compile_cache']['dir']} "
        f"cores={sorted(broker_cores) if broker_cores else 'not pinned'} "
        f"loadgen_cores={loadgen_cores} "
        f"reference_ready_after={t_reference:.1f}s")

    t_declare = time.monotonic()
    asyncio.run(declare(broker.port, table, applied))
    say(f"declared: queues={len(table['queues'])} "
        f"bindings={len(table['bindings'])} over {DECLARE_CONNS} connections "
        f"in {time.monotonic() - t_declare:.1f}s"
        + (f" exchanges={len(table['exchanges'])} "
           f"queue_bindings={len(table.get('queue_bindings', []))} "
           f"exchange_bindings={len(table.get('exchange_bindings', []))}"
           if "exchanges" in table else ""))

    loadgen = state["loadgen"] = LoadChildren(
        args, cell, mix, broker.port, out_dir, loadgen_cores)
    loadgen.start_consumers()
    start_ns = time.monotonic_ns() + int(
        (SPAWN_LEAD_S + mix["warmup_s"]) * 1e9)
    end_ns = start_ns + int(args.seconds * 1e9)
    spawned_ns = time.monotonic_ns()
    loadgen.start_producers(start_ns, end_ns)

    # -- the measured window
    sleep_until_ns(start_ns)
    setup_s = time.monotonic() - T0
    snaps = {"window0": snapshot(broker, loadgen)}
    if args.trace:
        span_ns = int(min(TRACE_SPAN_S, args.seconds / 2) * 1e9)
        sleep_until_ns(end_ns - span_ns)
        snaps["window1"] = snapshot(broker, loadgen)
        broker.signal_and_wait(signal.SIGUSR1, "trace_started", 60)
        snaps["span0"] = snapshot(broker, loadgen)
    sleep_until_ns(end_ns)
    snaps["span1" if args.trace else "window1"] = snapshot(broker, loadgen)
    stopped = broker.signal_and_wait(signal.SIGUSR2, "stopped.json", 120)

    # -- wait for every delivery that is due, then read what came
    reports = loadgen.reap_producers(DRAIN_S + 30)
    step = mix["producers"]
    seqs = np.concatenate([
        r["index"] + step * np.arange(r["published"], dtype=np.int64)
        for r in reports])
    in_window = np.concatenate([
        np.arange(r["published"]) >= r["window_first"] for r in reports])
    entries = draws[seqs % len(draws)].astype(np.int64)
    expected_pairs = expected.pairs(seqs, entries)
    got = wait_for_deliveries(broker, loadgen, expected_pairs.size)
    drain_s = (time.monotonic_ns() - end_ns) / 1e9
    consumer_reports = loadgen.stop_consumers()
    report_stalls(broker.control, spawned_ns, start_ns)
    final = broker.overview()
    rc = broker.terminate()
    if rc != 0:
        raise RunFailure(f"broker exited {rc} on SIGTERM: "
                         f"{tail(broker.log_path)}")

    files = [np.load(r["file"]) for r in consumer_reports]
    pairs = np.concatenate([f["pairs"] for f in files])
    sent = np.concatenate([f["sent"] for f in files])
    received = np.concatenate([f["received"] for f in files])
    published = int(sum(r["published"] for r in reports))
    confirmed = int(sum(r["confirmed"] for r in reports))
    # what the broker itself says of the guarantees the configuration adds,
    # read after the consumers' last ack and before SIGTERM
    held: dict = {}
    if applied["consumer_ack"]:
        ready = sum(v["messages"] for v in final["vhosts"].values())
        unacked = final["metrics"]["queue_unacked"]
        held["unsettled"] = int(ready + unacked)
        say(f"settled: acks={sum(r['acks'] for r in consumer_reports)} "
            f"ready={ready} unacked={unacked}")
    if applied["durable"]:
        say("durability (counters of the broker's log since boot; one append "
            "is one record of any kind, not one message and queue): "
            + " ".join(f"{k}={final['metrics'][k]}" for k in (
                "wal_appends", "wal_append_bytes", "wal_commits",
                "wal_fsyncs", "wal_commit_errors")))

    def compared(delivered: np.ndarray) -> "tuple[dict, np.ndarray]":
        numbers, bad = reference.compare(
            expected_pairs, delivered, published, confirmed)
        numbers.update(held)
        return numbers, bad

    if args.control:
        # the program's own answers first, then every control in its place;
        # the result line is the named control's
        controls = {"program": pairs}
        for control in reference.CONTROLS:
            controls[control] = reference.control_pairs(
                control, table, pool, seqs, entries, expected,
                mix["confirm_window"])
        for name, stood_in in controls.items():
            numbers, _ = compared(stood_in)
            say(f"control {name}: correct={reference.is_correct(numbers)} "
                f"{numbers}")
        pairs = controls[args.control]
    numbers, bad_seqs = compared(pairs)
    correct = reference.is_correct(numbers)

    window_seqs = seqs[in_window]
    attempted = int(window_seqs.size)
    failed = min(attempted, int(np.isin(bad_seqs, window_seqs).sum())
                 + numbers["unconfirmed"])
    # the rate counts what the consumers RECEIVED inside the window (a
    # backlog that drains after the close is not throughput); the latency is
    # that of every message DUE inside it, whenever it came
    arrived = int(((received >= start_ns) & (received < end_ns)).sum())
    inside = (sent >= start_ns) & (sent < end_ns)
    latency = (received - sent)[inside]
    say(f"traffic: published={published} confirmed={confirmed} "
        f"nacks={sum(r['nacks'] for r in reports)} "
        f"in_window={attempted} deliveries={got} "
        f"expected={expected_pairs.size} "
        f"fan_out={expected_pairs.size / max(1, published):.4f} "
        f"received_in_window={arrived} "
        f"of_messages_due_in_window={int(inside.sum())} "
        f"generator_late_mean_ms="
        f"{max(r['late_mean_ms'] for r in reports):.3f} "
        f"generator_late_max_ms={max(r['late_max_ms'] for r in reports):.3f} "
        f"rate={mix.get('rate', 0)} drain_s={drain_s:.2f} "
        f"setup_s={setup_s:.2f}")
    if latency.size:
        say(f"latency of the messages due in the window, all pooled: "
            f"p50_ms={reference.percentile_ms(latency, 50):.3f} "
            f"p95_ms={reference.percentile_ms(latency, 95):.3f} "
            f"max_ms={float(latency.max()) / 1e6:.3f} n={latency.size}")
    w0, w1 = snaps["window0"], snaps["window1"]
    say(f"window (its untraced part): seconds={(w1['ns'] - w0['ns']) / 1e9:.3f} "
        f"broker_cpu_s={w1['cpu']['broker'] - w0['cpu']['broker']:.2f} "
        f"loadgen_cpu_s={w1['cpu']['loadgen'] - w0['cpu']['loadgen']:.2f} "
        f"published=+{w1['admin']['metrics']['published_msgs'] - w0['admin']['metrics']['published_msgs']} "
        f"delivered=+{w1['admin']['metrics']['delivered_msgs'] - w0['admin']['metrics']['delivered_msgs']}")
    say(hoststat.describe(broker.proc.pid, w0["threads"], w1["threads"]))
    moved = {k: final["metrics"][k] - snaps["window0"]["admin"]["metrics"][k]
             for k in ("router_kernel_launches", "router_batch_msgs",
                       "router_fallback_msgs", "router_compiles")}
    cache = final["device"]["compile_cache"]
    say("broker counters since the window opened: "
        + " ".join(f"{k}=+{v}" for k, v in moved.items())
        + f"; compile cache hits={cache['hits']} misses={cache['misses']}")
    if not stopped["memory_stats_reported"]:
        say(f"device reports no memory statistics on "
            f"{device['platform']!r}: memory_peak_bytes is 0")

    result_device = dict(state["device"],
                         memory_peak_bytes=stopped["memory_peak_bytes"])
    metrics: dict = {}
    breakdown = None
    if not args.trace:
        values = {
            "setup_s": setup_s,
            "delivered_msgs_per_s": reference.rate_per_s(
                arrived, args.seconds),
        }
        if latency.size:
            values["deliver_latency_p50_ms"] = reference.percentile_ms(
                latency, 50)
        for metric in bench["end_to_end"]:
            if metric_applies(metric, cell["name"]) \
                    and values.get(metric["name"]) is not None:
                metrics[metric["name"]] = {
                    "value": values[metric["name"]], "unit": metric["unit"]}
    else:
        trace = reduce_trace(broker.control)
        if trace["busy_s"] <= 0:
            raise RunFailure("no operation ran on the device in the trace")
        result_device.update(busy_s=trace["busy_s"],
                             window_s=trace["window_s"])
        breakdown = trace["breakdown"]
        say(f"trace: planes={trace['device_planes']} ops={trace['n_ops']} "
            f"launches_in_trace={trace['launches']} "
            f"busy_s={trace['busy_s']:.6f} window_s={trace['window_s']:.6f} "
            f"file_bytes={trace['file_bytes']}")
        untraced = inside & (sent < snaps["window1"]["ns"])
        ctx = {"snaps": snaps, "trace": trace, "table": table,
               "client": {"latency_ns": (received - sent)[untraced]},
               "loadgen_procs": len(loadgen.pids()),
               # words of a routing key / headers of a message, on average
               "cells_per_msg": float(np.mean([
                   len(h) if h is not None else len(k.split("."))
                   for k, h in pool])),
               "peaks": reference.load_json("peaks.json"),
               "platform": device["platform"],
               "device_kind": device["kind"]}
        for metric in bench["per_layer"]:
            if not metric_applies(metric, cell["name"]):
                continue
            spec = reference.load_json(
                "layer_metrics", f"{metric['name']}.json")
            reader = importlib.import_module(f"readers.{spec['reader']}")
            value = reader.read(spec.get("params", {}), ctx)
            if value is not None:
                metrics[metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
    if not args.out:  # a directory the caller named is the caller's to keep
        shutil.rmtree(out_dir, ignore_errors=True)

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.control:
        result["control"] = args.control
    result["compared"] = reference.compared_report(numbers)
    return result


def attempt(args) -> "dict | None":
    """One run and its clean-up: no process it started outlives it."""
    state: dict = {}
    try:
        return run(args, state)
    except Exception as exc:  # noqa: BLE001 — every failure ends the same way
        traceback.print_exc(file=sys.stderr)
        say(f"FAILED: {type(exc).__name__}: {exc}"[:2000].replace("\n", " "))
        return None
    finally:
        if "loadgen" in state:
            state["loadgen"].kill()
        if "broker" in state:
            state["broker"].kill()


def prime_marker(args) -> str:
    return os.path.join(CACHE_DIR, f"primed-{args.workload}-{args.scale}")


def primed(args) -> bool:
    """Whether this checkout's compile cache already holds the cell's
    kernels. A broker that COMPILED them routes a fifth slower for tens of
    seconds afterwards than one that read them from the cache (PERF.md
    §6.3), so the first run of a cell in a checkout first makes a short run
    to fill the cache, and then measures a fresh broker like every later
    run's. The priming run is part of that first run's `setup_s`."""
    return os.path.exists(prime_marker(args))


def mark_primed(args) -> None:
    with open(prime_marker(args), "w", encoding="utf-8"):
        pass


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--scale", choices=("full", "small"), default="full")
    parser.add_argument("--control", choices=reference.CONTROLS, default=None)
    parser.add_argument("--fault", default=None)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    result = None
    if not primed(args):
        first = attempt(argparse.Namespace(**dict(
            vars(args), seconds=PRIME_S, trace=0, control=None, out=None)))
        if first is not None:
            mark_primed(args)
            say("priming run, a broker that compiled its kernels: "
                + json.dumps(first["metrics"]))
    if primed(args):
        result = attempt(args)
    if "jax" in sys.modules:
        say("FAILED: the benchmark's parent imported jax")
        result = None
    if result is None:
        return 1
    for name, pair in result["compared"].items():
        print(f"compared {name}: value={pair['value']} limit={pair['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
