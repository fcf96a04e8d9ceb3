"""Device: 1 - the union of the device's operation intervals over the
traced window (%)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["busy_s"] or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
