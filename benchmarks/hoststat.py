"""CPU seconds of each thread of the broker, read from
/proc/<pid>/task/<tid>/stat: the event loop's own thread (tid == pid: the
broker is one asyncio loop on its main thread) apart from JAX's and the chip
runtime's. The chip machines are gVisor sandboxes (`uname` says runsc;
PERF.md 6.9): /proc/stat, /proc/interrupts, a thread's core and its context
switches all read nought there, the threads' CPU times are real."""

from __future__ import annotations

import os

TICK = os.sysconf("SC_CLK_TCK")


def threads(pid: int) -> dict:
    """{tid: (name, CPU seconds so far)}; whatever can be read."""
    out = {}
    try:
        tids = os.listdir(f"/proc/{pid}/task")
    except OSError:
        return out
    for tid in tids:
        try:
            with open(f"/proc/{pid}/task/{tid}/stat", "rb") as f:
                raw = f.read().decode("ascii", "replace")
            fields = raw.rpartition(")")[2].split()
            out[int(tid)] = (raw[raw.index("(") + 1:raw.rindex(")")],
                             (int(fields[11]) + int(fields[12])) / TICK)
        except (OSError, ValueError, IndexError):
            continue  # a thread that ended meanwhile
    return out


def loop_cpu_s(pid: int, snap: dict) -> float:
    return snap.get(pid, ("", 0.0))[1]


def describe(pid: int, before: dict, after: dict) -> str:
    """One line for the run's output: the busiest threads between two
    `threads` readings."""
    busy = sorted(((cpu - before.get(tid, ("", 0.0))[1], tid, name)
                   for tid, (name, cpu) in after.items()), reverse=True)
    return ("broker threads over the window (cpu seconds): " + "; ".join(
        f"{'LOOP ' if tid == pid else ''}{name} {cpu:.2f}"
        for cpu, tid, name in busy[:8] if cpu >= 0.05 or tid == pid)
        + f"; {len(after)} threads in all")
