"""How far the named counters moved, added up."""

from . import delta


def read(params: dict, ctx: dict):
    over = params.get("over", "window")
    return sum(delta(ctx, over, path) for path in params["counters"])
