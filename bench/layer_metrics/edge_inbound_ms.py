"""HTTP edge and engine wave: an answer's time from the REST handler's
entry until ``ContinuousBatcher.submit`` was entered (ms): the stages
``in`` (the request's JSON, the row staged), ``ingress`` (the pump's poll
and the waves up to the first async node), ``embed``, ``search`` and
``prompt`` (the waves behind the search, the answering UDF's template) of
the program's request clocks (``pathway_tpu.internals.observability``
``STAGES``; ``io.http.route_stats()[route]["recent"]`` keeps the 200s'
clocks as 13 instants on ``time.monotonic()``, the harness's own clock).
The mean over the clocks whose handler entry lies between the first and
the last ``sent`` of the window's records; a program without the clock
(the parent commit) reads nothing, and so does a window out of which the
route's bounded ``recent`` may already have dropped a clock.

``window_clocks`` and ``stage_means`` are what the other readers of the
clock take it from (``retrieve_wait_ms``, ``edge_outbound_ms``,
``client_side_ms``)."""


def window_clocks(ctx):
    """(the stages' names, the window's clocks in the order of their
    handler entries); None where the program keeps no clock, none fell
    into the window, or the window's may not all be there: ``recent`` is
    full (it keeps the ``RECENT_CLOCKS`` that finished last) and the oldest
    it still has replied inside the window, so one that replied before it
    and was dropped may have been entered inside the window too."""
    try:
        from pathway_tpu.internals.observability import STAGES
        from pathway_tpu.io.http import RECENT_CLOCKS, route_stats
    except ImportError:  # no request clock in this program
        return None
    sent = [r.sent for r in ctx["records"]]
    recent = route_stats().get(ctx["mix"]["route"], {}).get("recent")
    if not sent or not recent:
        return None
    lo, hi = min(sent), max(sent)
    if len(recent) >= RECENT_CLOCKS and recent[0][-1] >= lo:
        return None
    clocks = sorted(c for c in recent if lo <= c[0] <= hi)
    return (STAGES, clocks) if clocks else None


def stage_means(ctx):
    """Stage -> its mean seconds over the window's clocks; None where
    ``window_clocks`` finds none."""
    found = window_clocks(ctx)
    if found is None:
        return None
    stages, clocks = found
    return {
        stage: sum(c[i + 1] - c[i] for c in clocks) / len(clocks)
        for i, stage in enumerate(stages)
    }


def read(ctx):
    means = stage_means(ctx)
    if means is None:
        return None
    return 1e3 * sum(
        means[s] for s in ("in", "ingress", "embed", "search", "prompt")
    )
