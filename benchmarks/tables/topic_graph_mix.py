"""A graph of exchanges behind one topic exchange, as RabbitMQ's
exchange-to-exchange bindings are used for: a fan-out tree that keeps the
subscribers' binding churn off the exchange the publishers know. The
published exchange `bench.graph` holds wildcard bindings of its own to
queues (topic_smoke_mix's four shapes), a hop `r<k>.#` into one fanout
exchange per region, and ONE hop `cmd.#` into a direct exchange, where the
per-command bindings live (`command_keys` keys `cmd.<i>` over `commands`
queues): a command bound or unbound touches that exchange and never the
published one. Shaped so that the router flattens the closure into kernel
rows: no wildcard hop leads to an exchange whose own bindings hold wildcards.

params: wildcards, regions, region_queues, commands, command_keys.
"""

from __future__ import annotations

ROOT, COMMANDS = "bench.graph", "bench.graph.commands"


def _region(k: int) -> str:
    return f"bench.graph.region{k}"


def table(params: dict) -> dict:
    n_wild, n_regions, per_region, n_commands, n_keys = (
        params["wildcards"], params["regions"], params["region_queues"],
        params["commands"], params["command_keys"])
    # the published exchange's own rows: prefix.#, a.*.c, #.suffix, *.b.#
    # in turn, a queue each
    bindings = [((f"w{j % 97}.k{j}.#", f"t{j % 97}.*.s{j % 31}",
                  f"#.z{j}", f"*.k{j}.#")[j % 4], f"gw{j}", None)
                for j in range(n_wild)]
    exchanges = [(ROOT, "topic"), (COMMANDS, "direct")]
    queue_bindings, exchange_bindings = [], []
    for k in range(n_regions):
        exchanges.append((_region(k), "fanout"))
        exchange_bindings.append((ROOT, _region(k), f"r{k}.#", None))
        queue_bindings += [(_region(k), f"gr{k}.{i}", "", None)
                           for i in range(per_region)]
    exchange_bindings.append((ROOT, COMMANDS, "cmd.#", None))
    queue_bindings += [(COMMANDS, f"gc{i % n_commands}", f"cmd.{i}", None)
                       for i in range(n_keys)]
    return {"exchange": ROOT, "type": "topic",
            "queues": ([queue for _, queue, _ in bindings]
                       + [f"gr{k}.{i}" for k in range(n_regions)
                          for i in range(per_region)]
                       + [f"gc{i}" for i in range(n_commands)]),
            "bindings": bindings, "exchanges": exchanges,
            "queue_bindings": queue_bindings,
            "exchange_bindings": exchange_bindings}


def pool(params: dict, table: dict, n: int, rng) -> list:
    """n distinct routing keys: up to a fifth are a command's key (all of
    them once n outgrows five times `command_keys`); of the others half
    take a region's hop (`#` taking 0..3 fresh words), a quarter are aimed
    at the published exchange's own wildcard rows, a quarter route
    nowhere."""
    n_wild, n_regions, n_keys = (
        params["wildcards"], params["regions"], params["command_keys"])
    keys = {f"cmd.{i}" for i in rng.sample(
        range(n_keys), min(n_keys, n // 5))}
    serial = 0
    while len(keys) < n:
        serial += 1
        shape = rng.random()
        if shape < 0.50:    # a region's fanout, by r<k>.#
            tail = "".join(f".d{serial}" for _ in range(rng.randrange(4)))
            keys.add(f"r{rng.randrange(n_regions)}{tail}")
        elif shape < 0.75:  # the published exchange's own rows
            j = rng.randrange(n_wild)
            keys.add((f"w{j % 97}.k{j}.u{serial}",
                      f"t{j % 97}.x{serial}.s{j % 31}",
                      f"m{serial}.n.z{j}", f"y{serial}.k{j}")[j % 4])
        else:               # routes nowhere
            keys.add(f"miss.{serial}.z")
    ordered = sorted(keys)
    rng.shuffle(ordered)
    return [(key, None) for key in ordered]
