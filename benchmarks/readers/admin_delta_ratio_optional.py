"""`admin_delta_ratio` over counters a program may lack: None, where that
reader raises, when /admin/overview has no such counter (a parent commit
from before the counter), so the metric is left out of the line."""

from . import admin_delta_ratio


def read(params: dict, ctx: dict):
    try:
        return admin_delta_ratio.read(params, ctx)
    except KeyError:
        return None
