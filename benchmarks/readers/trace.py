"""Device numbers from the reduced profiler trace (trace_reduce.py):

idle_share        100 x (1 - busy / traced window)
op_us_per_launch  device-op time per executable launched in the trace
roofline_share    100 x least time for the launches' work / their op time;
                  the work from roofline.py over the real table and the
                  messages routed in the span, the peak from peaks.json by
                  the device's kind (an unknown kind is an error). The
                  messages of a launch are taken from `router_batch_msgs`,
                  the messages of the flush: a topic flush sends only the
                  keys its memo has not seen, once each, and the program
                  does not count those, so the share reads high by the
                  memo's and the duplicates' part of the message bytes
                  (fresh keys: about 7% of the messages, 5% of the share).
                  A cell the memo serves does not list the metric.
                  Over a graph of exchanges it reads nothing: the rows
                  that reach the kernel are those of the flattened closure,
                  which roofline.py does not count yet

Off the TPU (a rehearsal under JAX_PLATFORMS=cpu) it reads nothing: a number
from a CPU run is never written under the name of a device metric.
"""

import roofline

from . import delta


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if ctx["platform"] != "tpu":
        return None  # a rehearsal on the CPU gives no device number
    if not trace or trace["n_ops"] == 0 or trace["window_s"] <= 0:
        return None
    what = params["reduce"]
    if what == "idle_share":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if trace["launches"] <= 0 or trace["device_op_s"] <= 0:
        return None
    if what == "op_us_per_launch":
        return 1e6 * trace["device_op_s"] / trace["launches"]
    if what == "roofline_share":
        if ctx["table"].get("exchange_bindings"):
            return None
        peak = ctx["peaks"][ctx["device_kind"]]
        msgs = delta(ctx, "span", params["msgs"]) / trace["launches"]
        least = roofline.least_seconds(roofline.launch_bytes(
            ctx["table"], msgs, ctx["cells_per_msg"]), peak)
        return 100.0 * least * trace["launches"] / trace["device_op_s"]
    raise ValueError(f"unknown trace reduction {what!r}")
