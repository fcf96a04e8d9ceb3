"""Model step: device time of the decode-step program per execution (ms),
from the traced window's ``XLA Modules`` line: the mean over the
executions that lie whole inside the trace (one cut by the trace's first
or last instant is left out by the reduction, ``trace_reduce``, ``cut``)."""


def read(ctx):
    trace = ctx["trace"]
    p = (trace or {}).get("programs", {}).get("decode_step_slots")
    if not p or not p["count"]:
        return None
    return 1e3 * p["total_s"] / p["count"]
