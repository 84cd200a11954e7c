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
graph of exchanges) before a `model_config` PR adds such a file.
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


def benchmark_json() -> dict:
    """The repository's BENCHMARK.json with the rehearsal's configurations
    and cells added; a cell joins every metric that lists the saturated
    cells (they report `delivered_msgs_per_s`), but for `not_reported`."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    with open(os.path.join(DATA, "cells.json"), encoding="utf-8") as f:
        added = json.load(f)
    bench["configs"] += added["configs"]
    bench["workloads"] += added["workloads"]
    names = [cell["name"] for cell in added["workloads"]]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if (metric.get("moves", metric["name"]) == "delivered_msgs_per_s"
                and metric["name"] not in added["not_reported"]):
            metric["workloads"] += names
    return bench


def assemble(dest: str) -> str:
    os.makedirs(dest)
    shutil.copytree(BENCH, os.path.join(dest, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for kind in ("configs", "traffic", "tables"):
        for name in os.listdir(os.path.join(DATA, kind)):
            if not name.startswith("__"):
                shutil.copy(os.path.join(DATA, kind, name),
                            os.path.join(dest, "benchmarks", kind, name))
    for program in ("chanamq_tpu", "native"):
        os.symlink(os.path.join(ROOT, program), os.path.join(dest, program))
    with open(os.path.join(dest, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(benchmark_json(), f, indent=1)
    return dest


if __name__ == "__main__":
    print(assemble(sys.argv[1]))
