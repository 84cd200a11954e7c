#!/usr/bin/env python3
"""Records the calls that a harness makes on the in-repo client, with a
recording fake in the client's place and no broker:

    python3 benchmarks/tests/client_calls.py <a benchmarks/ directory>

For every cell of the BENCHMARK.json beside that directory (the program is
taken from there too), at the small scale: every
call of run.py's `declare()`, the first PRODUCER_CALLS calls of producer 0 of
loadgen.py (its warm-up bursts and what follows) and every call of consumer 0
up to `stop`. A call is [name, positional arguments, keyword arguments]; a
publish's body is recorded as its stream position (its other eight bytes are
the clock), its properties as the fields that are set. Printed as one JSON
object: for each cell and role the number of calls, their SHA-256, and the
first and last few written out, so that a difference can be read.

data/parent_client_calls.json is this script's output for the harness of
the commit before the `applied` section existed (4dac9c0): the test holds
today's harness, on configurations without the section and tables without
a graph, to exactly those calls.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import hashlib
import io
import json
import os
import sys
import tempfile
import time

PRODUCER_CALLS = 2400  # the warm-up's 2,008 publishes and a few windows
SHOWN = 4


class Enough(Exception):
    """The producer has made as many calls as are recorded."""


class Recorder:
    def __init__(self, limit: "int | None" = None) -> None:
        self.calls: list = []
        self.limit = limit

    def add(self, name: str, args: tuple, kwargs: dict) -> None:
        self.calls.append([name, list(args), kwargs])
        if self.limit is not None and len(self.calls) >= self.limit:
            raise Enough


def plain(value):
    """A call's argument as JSON can hold it."""
    if dataclasses.is_dataclass(value):
        return {k: v for k, v in dataclasses.asdict(value).items()
                if v is not None}
    if callable(value):
        return "<callback>"
    return value


class FakeChannel:
    """Records every public call. Publishes stay unconfirmed until the
    producer waits for them, then all are confirmed at once."""

    def __init__(self, recorder: Recorder) -> None:
        self._recorder = recorder
        self.unconfirmed: list = []

    def _on_confirm(self, tag: int, multiple: bool, nack: bool) -> None:
        pass

    def basic_publish(self, body: bytes, **kwargs) -> None:
        self.unconfirmed.append(len(self.unconfirmed))
        position = int.from_bytes(body[8:], "big")
        self._recorder.add("basic_publish", (position,),
                           {k: plain(v) for k, v in kwargs.items()})

    def basic_ack(self, *args, **kwargs) -> None:
        self._recorder.add("basic_ack", args, kwargs)

    async def wait_unconfirmed_below(self, n: int, timeout=None) -> None:
        self._recorder.add("wait_unconfirmed_below", (n,), {})
        self.unconfirmed.clear()

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)

        async def call(*args, **kwargs):
            self._recorder.add(name, tuple(plain(a) for a in args),
                               {k: plain(v) for k, v in kwargs.items()})
        return call


class FakeConnection:
    def __init__(self, recorder: Recorder) -> None:
        self._recorder = recorder

    async def channel(self) -> FakeChannel:
        return FakeChannel(self._recorder)

    async def drain(self) -> None:
        self._recorder.add("drain", (), {})

    async def close(self) -> None:
        self._recorder.add("close", (), {})


def fake_client(recorder: Recorder):
    class AMQPClient:
        @staticmethod
        async def connect(host: str, port: int) -> FakeConnection:
            return FakeConnection(recorder)
    return AMQPClient


def summary(calls: list) -> dict:
    text = json.dumps(calls, sort_keys=True)
    return {"calls": len(calls),
            "sha256": hashlib.sha256(text.encode()).hexdigest(),
            "first": calls[:SHOWN], "last": calls[-SHOWN:]}


def record(bench_dir: str) -> dict:
    root = os.path.dirname(bench_dir)
    sys.path.insert(0, bench_dir)
    sys.path.insert(0, root)
    import chanamq_tpu.client as client_module
    import loadgen
    import reference
    import run

    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        cells = json.load(f)["workloads"]
    out: dict = {}
    for cell in cells:
        cfg = reference.load_config(cell["config"], "small")
        mix = reference.load_traffic(cell["traffic"], "small")
        table = reference.build_table(cfg)
        roles: dict = {}

        recorder = Recorder()
        client_module.AMQPClient = fake_client(recorder)
        # the parent's declare() took the table alone
        extra = (reference.applied(cfg),) if hasattr(reference, "applied") \
            else ()
        asyncio.run(run.declare(0, table, *extra))
        roles["declare"] = summary(recorder.calls)

        recorder = Recorder(PRODUCER_CALLS)
        client_module.AMQPClient = fake_client(recorder)
        now = time.monotonic_ns()
        scratch = tempfile.TemporaryDirectory()
        args = argparse.Namespace(
            index=0, port=0, seed=2**31 + 7, out=scratch.name,
            start_ns=now, end_ns=now + 60 * 10**9)
        try:
            asyncio.run(loadgen.producer(args, cfg, mix))
        except Enough:
            pass
        roles["producer"] = summary(recorder.calls)

        recorder = Recorder()
        client_module.AMQPClient = fake_client(recorder)
        stdin, stdout = sys.stdin, sys.stdout
        sys.stdin, sys.stdout = io.StringIO("stop\n"), io.StringIO()
        try:
            asyncio.run(loadgen.consumer(args, cfg, mix))
        finally:
            sys.stdin, sys.stdout = stdin, stdout
        scratch.cleanup()
        roles["consumer"] = summary(recorder.calls)
        out[cell["name"]] = roles
    return out


if __name__ == "__main__":
    print(json.dumps(record(os.path.abspath(sys.argv[1])), indent=1))
