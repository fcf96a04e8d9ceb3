"""Worker-count invariance: the tier-2 invariant of the reference suite.

The reference runs its whole test suite under multiple timely workers and
requires identical results (SURVEY §4; docs 10.worker-architecture.md).
Here each representative pipeline runs under PATHWAY_THREADS in {1, 2, 4}
— stateful operators shard their state across worker replicas and inputs
are exchanged on each operator's key (engine/workers.py) — and both the
final state AND the consolidated per-timestamp update stream must be
identical across worker counts.
"""

from __future__ import annotations

import os

import pytest

import pathway_tpu as pw
from pathway_tpu.engine.core import freeze_row
from tests.utils import T, run_capture

WORKER_COUNTS = (1, 2, 4)


def _run_under(n: int, build):
    """Build + run a pipeline under n workers; return normalized results."""
    old = os.environ.get("PATHWAY_THREADS")
    os.environ["PATHWAY_THREADS"] = str(n)
    try:
        cap = run_capture(build())
        state = {k.value: freeze_row(row) for k, row in cap.state.rows.items()}
        stream: dict[tuple, int] = {}
        for (t, key, row, diff) in cap.stream:
            token = (t, key.value, freeze_row(row))
            stream[token] = stream.get(token, 0) + diff
        return state, {tok: d for tok, d in stream.items() if d != 0}
    finally:
        if old is None:
            del os.environ["PATHWAY_THREADS"]
        else:
            os.environ["PATHWAY_THREADS"] = old


def assert_worker_invariant(build) -> None:
    base = _run_under(1, build)
    for n in WORKER_COUNTS[1:]:
        got = _run_under(n, build)
        assert got[0] == base[0], f"final state differs at {n} workers"
        assert got[1] == base[1], f"update stream differs at {n} workers"
    assert base[0], "pipeline produced no rows — vacuous invariance"


def _stream_table():
    # content-addressed ids: the invariance harness rebuilds the pipeline
    # per worker count, so auto-assigned sequential ids would differ
    # between runs for reasons unrelated to sharding
    return T(
        """
        k  | grp | v  | __time__ | __diff__
        a  | x   | 1  | 2        | 1
        b  | y   | 2  | 2        | 1
        c  | x   | 3  | 2        | 1
        d  | z   | 4  | 4        | 1
        b  | y   | 2  | 4        | -1
        e  | y   | 5  | 4        | 1
        f  | x   | 6  | 6        | 1
        a  | x   | 1  | 6        | -1
        g  | z   | 7  | 6        | 1
        h  | y   | 8  | 8        | 1
        """
    ).with_id_from(pw.this.k)


def test_groupby_native_and_python_reducers():
    def build():
        t = _stream_table()
        return t.groupby(t.grp).reduce(
            t.grp,
            n=pw.reducers.count(),
            s=pw.reducers.sum(t.v),
            m=pw.reducers.avg(t.v),
            mx=pw.reducers.max(t.v),
            tup=pw.reducers.sorted_tuple(t.v),
        )

    assert_worker_invariant(build)


def test_joins_all_modes():
    def right():
        return T(
            """
            grp | label | __time__ | __diff__
            x   | ex    | 2        | 1
            y   | wy    | 4        | 1
            w   | ww    | 4        | 1
            y   | wy    | 6        | -1
            y   | wy2   | 6        | 1
            """
        ).with_id_from(pw.this.grp, pw.this.label)

    for mode in ("inner", "left", "right", "outer"):
        def build(mode=mode):
            t = _stream_table()
            r = right()
            join = getattr(
                t, {"inner": "join", "left": "join_left",
                    "right": "join_right", "outer": "join_outer"}[mode]
            )
            return join(r, t.grp == r.grp).select(
                t.k, r.label, v=pw.left.v
            )

        assert_worker_invariant(build)


def test_rowwise_filter_concat_flatten():
    def build():
        t = _stream_table()
        big = t.filter(t.v >= 2).select(t.k, doubled=t.v * 2, tag=pw.this.grp)
        other = T(
            """
            k | doubled | tag | __time__ | __diff__
            q | 100     | w   | 2        | 1
            r | 200     | w   | 6        | 1
            """
        ).with_id_from(pw.this.k)
        both = big.concat_reindex(other)
        return both.select(both.k, both.doubled, split=pw.apply(lambda s: list(s), both.tag)).flatten(
            pw.this.split
        )

    assert_worker_invariant(build)


def test_update_rows_setops_ix():
    def build():
        t = _stream_table()
        override = T(
            """
            k | grp | v   | __time__ | __diff__
            a | x   | 10  | 4        | 1
            d | z   | 40  | 6        | 1
            """
        ).with_id_from(pw.this.k)
        keyed = t.with_id_from(t.k)
        merged = keyed.update_rows(override)
        small = keyed.filter(keyed.v <= 4)
        inter = merged.intersect(small)
        return inter.select(inter.k, inter.v, peer=inter.ix(inter.id, optional=True).grp)

    assert_worker_invariant(build)


def test_dedup_and_sort_prev_next():
    def build():
        t = _stream_table()
        latest = t.deduplicate(
            value=t.v, instance=t.grp, acceptor=lambda new, old: new > old
        )
        return latest.select(latest.grp, latest.v)

    assert_worker_invariant(build)

    def build_sorted():
        t = _stream_table()
        s = t.sort(key=t.v, instance=t.grp)
        return t.select(t.k, t.grp, has_prev=s.ix(t.id).prev.is_not_none())

    assert_worker_invariant(build_sorted)


def test_dedup_order_sensitive_acceptor():
    """Keep-latest (always-accept) dedup: within one wave the winner must
    be chosen canonically, not by shard-concatenation arrival order."""

    def build():
        t = _stream_table()
        return t.deduplicate(
            value=t.v, instance=t.grp, acceptor=lambda new, old: True
        )

    assert_worker_invariant(build)


def test_windows_temporal():
    def build():
        t = T(
            """
            at | v | __time__ | __diff__
            1  | 1 | 2        | 1
            3  | 2 | 2        | 1
            5  | 3 | 4        | 1
            7  | 4 | 4        | 1
            9  | 5 | 6        | 1
            12 | 6 | 6        | 1
            """
        )
        return t.windowby(
            t.at, window=pw.temporal.tumbling(duration=4)
        ).reduce(
            start=pw.this._pw_window_start,
            n=pw.reducers.count(),
            s=pw.reducers.sum(pw.this.v),
        )

    assert_worker_invariant(build)


def test_iterate_pagerank():
    def build():
        edges = T(
            """
            u | w | __time__ | __diff__
            a | b | 2        | 1
            b | c | 2        | 1
            c | a | 2        | 1
            a | c | 4        | 1
            d | a | 4        | 1
            """
        ).with_id_from(pw.this.u, pw.this.w)
        from pathway_tpu.stdlib.graphs import pagerank

        ranks = pagerank(edges.select(u=edges.u, v=edges.w), steps=8)
        # float sums are semigroup-accumulated; different shardings sum in
        # different orders, so compare ranks beyond float associativity
        return ranks.select(ranks.vid, r=pw.apply(lambda x: round(x, 9), ranks.rank))

    assert_worker_invariant(build)


def test_async_udf_memo_and_invariance():
    """Sharded AsyncApplyNode: results invariant AND each insertion runs the
    UDF exactly once per run (retractions hit the per-shard memo)."""
    calls: list[str] = []

    def build():
        calls.clear()
        t = _stream_table()

        @pw.udf(deterministic=False)
        async def slug(k: str, v: int) -> str:
            calls.append(k)
            return f"{k}:{v}"

        return t.select(t.k, tag=slug(t.k, t.v))

    base = _run_under(1, build)
    n_calls_1 = len(calls)
    # 8 insertion events in _stream_table (retractions must not re-run)
    assert n_calls_1 == 8, calls
    for n in (2, 4):
        got = _run_under(n, build)
        assert got == base, f"differs at {n} workers"
        assert len(calls) == n_calls_1, "udf re-ran under sharding"


def test_groupby_invariance_parallel_shards_large_stream():
    """Sharded native aggregation stays correct under a bigger stream
    (worker-count INVARIANCE at volume — engine throughput itself is
    not asserted here)."""
    import random

    rng = random.Random(7)
    lines = ["g | v | __time__ | __diff__"]
    for w in range(40):
        for _ in range(50):
            lines.append(f"g{rng.randrange(16)} | {rng.randrange(1000)} | {(w + 1) * 2} | 1")
    txt = "\n".join(lines)

    def build():
        t = T(txt)
        return t.groupby(t.g).reduce(
            t.g, n=pw.reducers.count(), s=pw.reducers.sum(t.v)
        )

    assert_worker_invariant(build)
