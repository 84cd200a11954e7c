#!/usr/bin/env python3
"""The load generator's child roles: one process per producer or consumer,
each on one real socket to the broker, using the in-repo client.

    python benchmarks/loadgen.py producer --port P --config C --traffic T \
        --seed S --scale full --index 0 --start-ns A --end-ns B
    python benchmarks/loadgen.py consumer ... --out DIR

A producer publishes the seeded stream's positions index, index+producers,
... with publisher confirms on, a window of `confirm_window` in flight
refilled below `refill_below`, the socket flushed every `flush_every`
publishes (a publisher that only yields when its window is full sends whole
windows in lock-step: the broker confirms a read batch at once, so it never
sees the window half empty); `rate` 0 is a closed loop at saturation, a
rate above 0 an open loop: every 10 ms each producer sends the burst that
is due (producer i's ticks stand i/producers of 10 ms after producer 0's, at
the same instants of the window in every run), each message stamped with
the time its burst was DUE (so a stall shows in the latency of what queued
behind it; a generator that falls
behind sends the overdue bursts at once under their own stamps) and the
generator's lateness reported. The body is 8 bytes of
CLOCK_MONOTONIC ns (one clock for every process of the host) and 4 bytes of
stream position. It starts as soon as it is connected — that is the
warm-up — and stops at --end-ns; the window opens at --start-ns.

A consumer subscribes to its share of the queues, records (queue, position,
sent, received) of every delivery, answers `count` on its standard input
with how many it has, and on `stop` writes them to
<out>/consumer-<index>.npz and ends.

What the configuration's `applied` section states (reference.applied) is
applied here: `delivery_mode` on every publish's properties; `consumer_ack`
as basic.qos and manual acks, one every `multiple_every` deliveries of the
channel and one last at `stop`. Without the section a publish carries the
pool entry's headers or no properties at all, and consumers are `no_ack`.

Last stdout line of either: one JSON object for the parent.
"""

from __future__ import annotations

import argparse
import array
import asyncio
import json
import os
import struct
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

import reference  # noqa: E402

BODY = struct.Struct(">QI")
BURST_NS = 10_000_000
CONFIRM_WAIT_S = 60.0


async def producer(args, cfg: dict, mix: dict) -> dict:
    from chanamq_tpu.amqp.properties import BasicProperties
    from chanamq_tpu.client import AMQPClient

    table = reference.build_table(cfg)
    pool = reference.build_pool(cfg, table, mix)
    draws = reference.stream_draws(mix, args.seed)
    keys = [key for key, _ in pool]
    # without a delivery_mode a message without headers carries no
    # properties at all
    mode = reference.applied(cfg)["delivery_mode"]
    bare = BasicProperties(delivery_mode=mode) if mode is not None else None
    props = [BasicProperties(headers=h, delivery_mode=mode)
             if h is not None else bare for _, h in pool]
    exchange = table["exchange"]
    step, window, refill = (
        mix["producers"], mix["confirm_window"], mix["refill_below"])
    rate = mix.get("rate", 0) / step  # this producer's share, msgs/s
    flush = mix["flush_every"]
    stream_len = len(draws)

    conn = await AMQPClient.connect("127.0.0.1", args.port)
    ch = await conn.channel()
    await ch.confirm_select()
    nacks = 0
    on_confirm = ch._on_confirm

    def counting(tag: int, multiple: bool, nack: bool) -> None:
        nonlocal nacks
        if nack:
            nacks += 1
        on_confirm(tag, multiple, nack)

    ch._on_confirm = counting
    now_ns = time.monotonic_ns
    k = 0           # messages published by this producer
    k_window = -1   # the first of them stamped inside the window
    late_ns = array.array("q")

    def publish(stamp: int) -> None:
        nonlocal k
        seq = args.index + k * step
        entry = draws[seq % stream_len]
        ch.basic_publish(BODY.pack(stamp, seq), exchange=exchange,
                         routing_key=keys[entry], properties=props[entry])
        k += 1

    if args.index == 0:
        for size in reference.warmup_bursts(mix):
            for _ in range(size):
                publish(now_ns())
            await conn.drain()
            await ch.wait_unconfirmed_below(1, timeout=CONFIRM_WAIT_S)
    if rate > 0:
        # every run offers the same arrivals: this producer's bursts are due
        # at start_ns + index/producers of a burst + whole bursts (before
        # the window too), so the producers' ticks stand evenly apart and
        # not wherever their start-up happened to leave them
        origin = args.start_ns + args.index * BURST_NS // step
        first = origin - (origin - now_ns()) // BURST_NS * BURST_NS
        k0, burst = k, 0
        while True:
            due = first + burst * BURST_NS
            if due >= args.end_ns:
                break
            await asyncio.sleep(max(0.0, (due - now_ns()) / 1e9))
            burst += 1
            if k_window < 0 and due >= args.start_ns:
                k_window = k
            while k < k0 + int(burst * rate * BURST_NS / 1e9):
                if k_window >= 0:
                    late_ns.append(now_ns() - due)
                publish(due)
                if len(ch.unconfirmed) >= window:
                    await conn.drain()
                    await ch.wait_unconfirmed_below(
                        refill, timeout=CONFIRM_WAIT_S)
            await conn.drain()
    else:
        while True:
            now = now_ns()
            if now >= args.end_ns:
                break
            if k_window < 0 and now >= args.start_ns:
                k_window = k
            publish(now)
            if len(ch.unconfirmed) >= window:
                await conn.drain()
                await ch.wait_unconfirmed_below(
                    refill, timeout=CONFIRM_WAIT_S)
            elif k % flush == 0:
                await conn.drain()
    await conn.drain()
    try:
        await ch.wait_unconfirmed_below(1, timeout=CONFIRM_WAIT_S)
    except asyncio.TimeoutError:
        pass
    unconfirmed = len(ch.unconfirmed)
    await conn.close()
    late = np.frombuffer(late_ns, dtype=np.int64) if len(late_ns) else \
        np.zeros(1, dtype=np.int64)
    return {"role": "producer", "index": args.index, "published": k,
            "window_first": k if k_window < 0 else k_window,
            "confirmed": k - unconfirmed - nacks, "nacks": nacks,
            "late_mean_ms": float(late.mean()) / 1e6,
            "late_max_ms": float(late.max()) / 1e6}


async def consumer(args, cfg: dict, mix: dict) -> dict:
    from chanamq_tpu.client import AMQPClient

    table = reference.build_table(cfg)
    queue_id = {q: i for i, q in enumerate(table["queues"])}
    mine = table["queues"][args.index::mix["consumers"]]
    pairs = array.array("Q")
    sent = array.array("q")
    received = array.array("q")
    now_ns = time.monotonic_ns
    unpack = BODY.unpack
    ack = reference.applied(cfg)["consumer_ack"]
    every = ack["multiple_every"] if ack else 0
    acks = owed = last_tag = 0

    def on_message(msg) -> None:
        nonlocal acks, owed, last_tag
        got = now_ns()
        stamp, seq = unpack(msg.body)
        pairs.append((queue_id[msg.consumer_tag] << 32) | seq)
        sent.append(stamp)
        received.append(got)
        if every:
            last_tag = msg.delivery_tag
            owed += 1
            if owed >= every:
                ch.basic_ack(last_tag, multiple=every > 1)
                acks, owed = acks + 1, 0

    conn = await AMQPClient.connect("127.0.0.1", args.port)
    ch = await conn.channel()
    if ack:
        await ch.basic_qos(prefetch_count=ack["prefetch"])
    for queue in mine:
        await ch.basic_consume(queue, on_message, consumer_tag=queue,
                               no_ack=not ack)
    loop = asyncio.get_running_loop()
    stop = asyncio.Event()

    def commands() -> None:
        for line in sys.stdin:
            if line.strip() == "count":
                print(json.dumps({"count": len(pairs)}), flush=True)
            else:
                break
        loop.call_soon_threadsafe(stop.set)

    threading.Thread(target=commands, daemon=True).start()
    print(json.dumps({"ready": len(mine)}), flush=True)
    await stop.wait()
    if owed:  # the last ack: nothing this consumer holds stays unsettled
        ch.basic_ack(last_tag, multiple=True)
        acks, owed = acks + 1, 0
        await conn.drain()
    await conn.close()
    path = os.path.join(args.out, f"consumer-{args.index}.npz")
    np.savez(path,
             pairs=np.frombuffer(pairs, dtype=np.uint64),
             sent=np.frombuffer(sent, dtype=np.int64),
             received=np.frombuffer(received, dtype=np.int64))
    return {"role": "consumer", "index": args.index, "queues": len(mine),
            "delivered": len(pairs), "acks": acks, "file": path}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("role", choices=("producer", "consumer"))
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--config", required=True)
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--scale", default="full")
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--index", type=int, required=True)
    parser.add_argument("--start-ns", type=int, default=0)
    parser.add_argument("--end-ns", type=int, default=0)
    parser.add_argument("--out", default=".")
    args = parser.parse_args()
    cfg = reference.load_config(args.config, args.scale)
    mix = reference.load_traffic(args.traffic, args.scale)
    role = producer if args.role == "producer" else consumer
    print(json.dumps(asyncio.run(role(args, cfg, mix))), flush=True)


if __name__ == "__main__":
    main()
