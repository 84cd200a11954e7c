"""Per-layer metric readers, one kind per module, found by the `reader` name
in benchmarks/layer_metrics/<metric>.json: `read(params, ctx)` returns the
number, or None when it finds nothing to read (the metric is then left out
of the line; a share of a roofline is never reported as 0).

`ctx["snaps"]` holds the run's snapshots — `window0`/`window1` around the
untraced part of the window, `span0`/`span1` around the traced span — each
{"ns", "admin": /admin/overview, "cpu": {"broker", "broker_loop", "loadgen"} seconds}.
"""

from __future__ import annotations


def pick(doc: dict, path: str):
    for part in path.split("."):
        doc = doc[part]
    return doc


def delta(ctx: dict, over: str, path: str) -> float:
    """How far a counter of /admin/overview (`metrics.published_msgs`,
    `device.compile_cache.hits`) moved over `window` or `span`."""
    snaps = ctx["snaps"]
    return (pick(snaps[over + "1"]["admin"], path)
            - pick(snaps[over + "0"]["admin"], path))


def seconds(ctx: dict, over: str) -> float:
    snaps = ctx["snaps"]
    return (snaps[over + "1"]["ns"] - snaps[over + "0"]["ns"]) / 1e9
