"""Model step: device time of the decode-step program per execution (ms),
from the traced window's ``XLA Modules`` line."""


def read(ctx):
    trace = ctx["trace"]
    p = (trace or {}).get("programs", {}).get("decode_step_slots")
    if not p or not p["count"]:
        return None
    return 1e3 * p["total_s"] / p["count"]
