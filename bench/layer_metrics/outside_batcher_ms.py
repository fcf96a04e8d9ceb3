"""HTTP edge and engine wave: the part of an answer's time spent outside
the batcher (ms): HTTP, the REST connector, the engine's waves, encode,
search, the prompt and the reply. The mean of ``done - sent`` over the
window's requests that got a 200, on the client's clock, minus the mean
residence in the batcher (``residence_s / completed`` of
``ContinuousBatcher.stats``, from ``submit`` to the last token). The two
populations are not quite the same: the records are the requests sent in
the window, the counters those finished in it, so they differ by the
requests in flight at the window's two ends (at most the clients, 16 of
about 232 in the backlog cell); every request does the same work there."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    completed = b.get("completed", 0)
    answered = [r.done - r.sent for r in ctx["records"] if r.status == 200]
    if not completed or not answered or "residence_s" not in b:
        return None
    return 1e3 * (sum(answered) / len(answered) - b["residence_s"] / completed)
