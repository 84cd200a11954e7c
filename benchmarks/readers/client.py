"""What the load generator's own processes saw over the untraced part of the
window: `latency_percentile_ms`, publish->deliver of all deliveries pooled,
percentile `q`."""

import numpy as np


def read(params: dict, ctx: dict):
    latency_ns = ctx["client"]["latency_ns"]
    if params["what"] != "latency_percentile_ms":
        raise ValueError(f"unknown client reading {params['what']!r}")
    if latency_ns.size == 0:
        return None
    return float(np.percentile(latency_ns, params["q"])) / 1e6
