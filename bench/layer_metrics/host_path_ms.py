"""HTTP edge and engine wave, timed from outside: over the harness's
per-request spans in the traced window, the median of a request's length
minus the time the device was busy inside it (ms)."""
import statistics


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["requests"]:
        return None
    return 1e3 * statistics.median(max(0.0, d - b) for d, b in trace["requests"])
