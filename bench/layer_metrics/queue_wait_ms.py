"""Admission and batching: how long a request waited for a decode slot
(ms), the mean over the requests admitted in the window: from ``submit``
to the slot's acquisition, ``queue_wait_s / prefills`` of
``ContinuousBatcher.stats``."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    prefills = b.get("prefills", 0)
    if not prefills or "queue_wait_s" not in b:
        return None
    return 1e3 * b["queue_wait_s"] / prefills
