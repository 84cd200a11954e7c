"""One headers exchange: bindings that alternate `x-match` all/any over 1-3
of 12 header names with 8 values, and messages that carry 1-4 of them —
chip_smoke.py's table (PR 22).

params: bindings, queues, table_seed. The table is part of the deployment,
so it comes from the configuration's `table_seed`, not from --seed: 256
random bindings move the fan-out by a tenth from one draw to the next, and
a run's seed may change the order of the work but not its amount.
"""

from __future__ import annotations

import random

NAMES = [f"h{i}" for i in range(12)]
VALUES: list = [f"v{i}" for i in range(5)] + [1, 2, 3]


def table(params: dict) -> dict:
    n_bind, n_queues = params["bindings"], params["queues"]
    rng = random.Random(params["table_seed"])
    bindings: list = []
    seen: set = set()
    while len(bindings) < n_bind:
        b = len(bindings)
        args: dict = {"x-match": "all" if b % 2 else "any"}
        for name in rng.sample(NAMES, rng.randrange(1, 4)):
            args[name] = rng.choice(VALUES)
        key = (f"hq{b % n_queues}", repr(sorted(args.items(), key=str)))
        if key in seen:
            continue
        seen.add(key)
        bindings.append(("", f"hq{b % n_queues}", args))
    return {"exchange": "bench.headers", "type": "headers",
            "queues": [f"hq{i}" for i in range(n_queues)],
            "bindings": bindings}


def pool(params: dict, table: dict, n: int, rng) -> list:
    """n distinct header sets of 1-4 headers."""
    seen: set = set()
    out = []
    while len(out) < n:
        headers = {name: rng.choice(VALUES)
                   for name in rng.sample(NAMES, rng.randrange(1, 5))}
        key = repr(sorted(headers.items()))
        if key in seen:
            continue
        seen.add(key)
        out.append(("", headers))
    return out
