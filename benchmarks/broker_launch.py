#!/usr/bin/env python3
"""The benchmark's entry to the broker process: `chanamq_tpu.broker.server`
`main()` unchanged, with two signal handlers in front of it, because only
the process that holds the chip can trace it or read its memory.

    python benchmarks/broker_launch.py --control DIR [--fault NAME] -- \
        --host 127.0.0.1 --port P --admin-port A --log-level INFO

SIGUSR1  start a `jax.profiler` trace into DIR/trace (Python tracer off:
         the event loop makes millions of calls a second), then write
         DIR/trace_started
SIGUSR2  stop the trace if one runs, then write DIR/stopped.json with the
         device's memory statistics

A watch thread asks the admin port, which the broker's one event loop
serves, for /admin/health five times a second. When an answer is STALL_S (1.5 s:
a saturated loop answers up to 0.9 s late in the headers cell)
late it writes every thread's Python stack to DIR/stalls.txt while the loop
is still stuck, and afterwards how long the answer took: a run that reads
low says where the loop was (PERF.md 6, "stalls").

The log opens with the arguments the broker was started with and, where a
configuration states broker options, the --config file that holds them.

Each handler only starts a thread: a Python signal handler runs between two
bytecodes of whatever the loop is doing, possibly inside JAX, and must not
call into it from there.

--fault plants a fault under the timed path for the benchmark's own tests
(see `plant`). No run of the benchmark passes it.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import signal
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def _write(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


class TraceControl:
    def __init__(self, control_dir: str) -> None:
        self.dir = control_dir
        self.tracing = False
        self.lock = threading.Lock()

    def start(self) -> None:
        import jax

        with self.lock:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(
                os.path.join(self.dir, "trace"), profiler_options=options)
            self.tracing = True
        _write(os.path.join(self.dir, "trace_started"), {})

    def stop(self) -> None:
        import jax

        with self.lock:
            if self.tracing:
                jax.profiler.stop_trace()
                self.tracing = False
            devices = jax.devices()
            stats = [d.memory_stats() or {} for d in devices]
        _write(os.path.join(self.dir, "stopped.json"), {
            "memory_peak_bytes": max(
                (s.get("peak_bytes_in_use", 0) for s in stats), default=0),
            "memory_stats_reported": any(stats),
            "devices": len(devices)})

    def install(self) -> None:
        for sig, target in ((signal.SIGUSR1, self.start),
                            (signal.SIGUSR2, self.stop)):
            signal.signal(sig, lambda *_, t=target: threading.Thread(
                target=t, daemon=True).start())


STALL_S = 1.5


def watch_stalls(control_dir: str, admin_port: int) -> None:
    url = f"http://127.0.0.1:{admin_port}/admin/health"
    path = os.path.join(control_dir, "stalls.txt")

    def stuck() -> None:
        with open(path, "a", encoding="utf-8") as f:
            f.write(f"STUCK at_ns={time.monotonic_ns()}\n")
            f.flush()
            faulthandler.dump_traceback(file=f, all_threads=True)

    while True:
        time.sleep(0.2)
        timer = threading.Timer(STALL_S, stuck)
        asked = time.monotonic_ns()
        timer.start()
        try:
            urllib.request.urlopen(url, timeout=300).read()
        except urllib.error.HTTPError:
            pass  # an answer all the same
        except OSError:
            timer.cancel()
            continue  # not listening yet, or gone
        timer.cancel()
        took = time.monotonic_ns() - asked
        if took > STALL_S * 1e9:
            with open(path, "a", encoding="utf-8") as f:
                f.write(f"STALL asked_ns={asked} seconds={took / 1e9:.3f}\n")


FAULTS = ("alter_answer", "half_batch")


def plant(fault: str) -> None:
    """alter_answer: every 97th routed message loses a queue (or, routed
    nowhere, gains one) where the router produces its answer. half_batch:
    the second half of every routed batch is left out (routed nowhere)."""
    from chanamq_tpu.router import compile as rcompile

    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault!r}")
    route_batch = rcompile.route_batch
    seen = 0

    def faulty(compiled, items, *args, **kwargs):
        nonlocal seen
        out = list(route_batch(compiled, items, *args, **kwargs))
        if fault == "half_batch":
            half = len(out) // 2
            return out[:half] + [frozenset()] * (len(out) - half)
        for i in range(len(out)):
            seen += 1
            if seen % 97 == 0:
                names = set(out[i])
                out[i] = (frozenset(sorted(names)[1:]) if names
                          else frozenset(compiled.bit_names[:1]))
        return out

    rcompile.route_batch = faulty


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--control", required=True)
    parser.add_argument("--fault", default=None)
    parser.add_argument("server_args", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    os.makedirs(args.control, exist_ok=True)
    TraceControl(args.control).install()
    server_args = [a for a in args.server_args if a != "--"]
    # the broker's log opens with what it was started with: its arguments
    # and, where a configuration states broker options, the file of them
    print(f"broker_launch: server arguments {server_args}", file=sys.stderr)
    if "--config" in server_args:
        with open(server_args[server_args.index("--config") + 1],
                  encoding="utf-8") as f:
            print(f"broker_launch: --config holds {json.load(f)}",
                  file=sys.stderr, flush=True)
    threading.Thread(
        target=watch_stalls, daemon=True, args=(
            args.control,
            int(server_args[server_args.index("--admin-port") + 1]))).start()
    if args.fault:
        plant(args.fault)
    from chanamq_tpu.broker import server

    sys.argv = ["chanamq_tpu.broker.server", *server_args]
    server.main()


if __name__ == "__main__":
    main()
