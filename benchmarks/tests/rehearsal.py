#!/usr/bin/env python3
"""Assembles a scratch checkout in which the rehearsal deployments of
tests/data/rehearsal are cells, so that run.py runs them as it runs any
cell: no argument and no environment variable of run.py exists for it.

    python3 benchmarks/tests/rehearsal.py <directory>
    cd <directory> && python3 benchmarks/run.py --workload rehearsal_durable ...

The tree is a copy of benchmarks/ with the rehearsal's configs/, traffic/
and tables/ files laid over it, links to the program (chanamq_tpu/, native/)
and a BENCHMARK.json of its own: the repository's, plus what
tests/data/rehearsal/cells.json adds. The deployments are test data, not
configurations of the benchmark: they show that the harness applies what a
configuration file states (durability, acknowledgements, broker options, a
graph of exchanges) before a `model_config` PR adds such a file, and stay
as tests when one has. Every file and name of theirs begins `rehearsal`, a
prefix no file of the benchmark takes, so that a real configuration can
never share one; should one all the same, `assemble()` raises and
overwrites nothing.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data", "rehearsal")
KINDS = ("configs", "traffic", "tables")


class Collision(Exception):
    """A file or a name of the rehearsal is already the benchmark's."""


def checkout(dest: str, benchmark: dict, root: str = ROOT,
             tests: bool = False) -> str:
    """A directory that run.py runs from as from a checkout: a copy of
    `root`'s benchmarks/ (with its tests/ only where asked), links to the
    program (chanamq_tpu/, native/) and `benchmark` written as its
    BENCHMARK.json."""
    os.makedirs(dest)
    skipped = ["__pycache__"] + ([] if tests else ["tests"])
    shutil.copytree(os.path.join(root, "benchmarks"),
                    os.path.join(dest, "benchmarks"),
                    ignore=shutil.ignore_patterns(*skipped))
    for program in ("chanamq_tpu", "native"):
        os.symlink(os.path.join(ROOT, program), os.path.join(dest, program))
    with open(os.path.join(dest, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(benchmark, f, indent=1)
    return dest


def benchmark_json(root: str = ROOT) -> dict:
    """`root`'s BENCHMARK.json with the rehearsal's configurations and
    cells added; a cell joins every metric that lists the saturated cells
    (they report `delivered_msgs_per_s`), but for `not_reported`. A name
    that BENCHMARK.json already has raises Collision."""
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    with open(os.path.join(DATA, "cells.json"), encoding="utf-8") as f:
        added = json.load(f)
    for kind in ("configs", "workloads"):
        taken = {entry["name"] for entry in bench[kind]}
        twice = [e["name"] for e in added[kind] if e["name"] in taken]
        if twice:
            raise Collision(f"BENCHMARK.json already has {kind} {twice}")
        bench[kind] += added[kind]
    names = [cell["name"] for cell in added["workloads"]]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if (metric.get("moves", metric["name"]) == "delivered_msgs_per_s"
                and metric["name"] not in added["not_reported"]):
            metric["workloads"] += names
    return bench


def assemble(dest: str, root: str = ROOT) -> str:
    """The scratch checkout of `root` (the repository). A rehearsal file
    whose name a file of benchmarks/ already has raises Collision, and
    nothing is overwritten."""
    checkout(dest, benchmark_json(root), root)
    for kind in KINDS:
        for name in sorted(os.listdir(os.path.join(DATA, kind))):
            if name.startswith("__"):
                continue
            target = os.path.join(dest, "benchmarks", kind, name)
            if os.path.exists(target):
                raise Collision(f"benchmarks/{kind}/{name} is already there")
            shutil.copy(os.path.join(DATA, kind, name), target)
    return dest


if __name__ == "__main__":
    print(assemble(sys.argv[1]))
