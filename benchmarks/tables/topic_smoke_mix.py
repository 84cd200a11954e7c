"""One topic exchange: exact patterns plus wildcard rows of four shapes, and
keys aimed at them — chip_smoke.py's table (PR 22), with a pool of distinct
keys in place of its fixed message list.

params: exact, wildcards, queues.
"""

from __future__ import annotations


def _exact(i: int) -> str:
    return f"t{i % 97}.k{i}.s{i % 31}"


def table(params: dict) -> dict:
    n_exact, n_wild, n_queues = (
        params["exact"], params["wildcards"], params["queues"])
    bindings = [(_exact(i), f"tq{i % n_queues}", None)
                for i in range(n_exact)]
    # the kernel rows: prefix.#, a.*.c, #.suffix, *.b.# in turn, each on its
    # own queue so the destination mask spans min(wildcards, queues) queues
    for j in range(n_wild):
        pattern = (f"w{j % 97}.k{j}.#", f"t{j % 97}.*.s{j % 31}",
                   f"#.z{j}", f"*.k{j}.#")[j % 4]
        bindings.append((pattern, f"tq{j % n_queues}", None))
    return {"exchange": "bench.topic", "type": "topic",
            "queues": [f"tq{i}" for i in range(n_queues)],
            "bindings": bindings}


def pool(params: dict, table: dict, n: int, rng) -> list:
    """n distinct routing keys: up to 55% are the key of an exact pattern
    (all of them once n outgrows the table), the others are aimed at the
    wildcard rows in the smoke's shares (30% a.*.c, 25% prefix.#, 20%
    #.suffix) or route nowhere (25%)."""
    n_exact, n_wild = params["exact"], params["wildcards"]
    keys = {_exact(i) for i in rng.sample(
        range(n_exact), min(n_exact, n * 55 // 100))}
    serial = 0
    while len(keys) < n:
        serial += 1
        shape = rng.random()
        # a row of the shape aimed at: shapes take turns over the rows
        j = 4 * rng.randrange(n_wild // 4) + (1 if shape < 0.30 else
                                              0 if shape < 0.55 else 2)
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
    ordered = sorted(keys)
    rng.shuffle(ordered)
    return [(key, None) for key in ordered]
