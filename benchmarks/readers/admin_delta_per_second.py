"""scale x (how far `counter` moved) / the seconds of `over` (`window`, the
default, or `span`): a counter of nanoseconds with scale 1e-7 reads as the
percentage of the time it took up. None when the program has no such
counter (a parent commit from before the counter)."""

from . import delta, seconds


def read(params: dict, ctx: dict):
    over = params.get("over", "window")
    try:
        moved = delta(ctx, over, params["counter"])
    except KeyError:
        return None
    elapsed = seconds(ctx, over)
    if elapsed <= 0:
        return None
    return params.get("scale", 1) * moved / elapsed
