"""Admission and batching: how long ``ContinuousBatcher.submit`` spent
tokenising a prompt on its caller's thread (ms), the mean over the
requests submitted in the window: ``tokenize_s / submitted`` of
``ContinuousBatcher.stats``. It lies inside ``queue_wait_ms``, whose clock
starts where ``submit`` is entered."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    submitted = b.get("submitted", 0)
    if not submitted or "tokenize_s" not in b:
        return None
    return 1e3 * b["tokenize_s"] / submitted
