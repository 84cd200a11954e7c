"""CPU seconds (user+system, /proc/<pid>/stat, an outside clock) of the
`broker` process, of its event loop's thread alone (`broker_loop`) or of the
`loadgen` processes over the window, per unit of
`per`: a counter's movement, or `process_seconds` (the window's length times
the number of processes: the share of its cores the group kept busy)."""

from . import delta, seconds


def read(params: dict, ctx: dict):
    over = params.get("over", "window")
    snaps = ctx["snaps"]
    procs = params["procs"]
    cpu = snaps[over + "1"]["cpu"][procs] - snaps[over + "0"]["cpu"][procs]
    if params["per"] == "process_seconds":
        count = ctx["loadgen_procs"] if procs == "loadgen" else 1
        den = seconds(ctx, over) * count
    else:
        den = delta(ctx, over, params["per"])
    if den <= 0:
        return None
    return params.get("scale", 1) * cpu / den
