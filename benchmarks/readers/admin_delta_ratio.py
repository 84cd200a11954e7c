"""scale x (how far counter `num` moved) / (how far `den` moved)."""

from . import delta


def read(params: dict, ctx: dict):
    over = params.get("over", "window")
    den = delta(ctx, over, params["den"])
    if den <= 0:
        return None
    return params.get("scale", 1) * delta(ctx, over, params["num"]) / den
