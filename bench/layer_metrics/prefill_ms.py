"""Model step: device time of the prefill program per execution (ms),
from the traced window's ``XLA Modules`` line: the mean over the
executions that lie whole inside the trace. The one the trace's first or
last instant cut is left out by the reduction (``trace_reduce``, ``cut``),
so the reading is the device's and not how the cut fell."""


def read(ctx):
    trace = ctx["trace"]
    p = (trace or {}).get("programs", {}).get("prefill_into_slot")
    if not p or not p["count"]:
        return None
    return 1e3 * p["total_s"] / p["count"]
