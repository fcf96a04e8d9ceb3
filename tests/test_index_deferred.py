"""The index operator's search runs off the pump's thread.

Under a pump that defers (`FrontierScheduler.allow_async`: the streaming
runtime), `ExternalIndexNode` hands a wave with queries to the index
worker and holds its watermark, as an async UDF's wave does: the engine's
thread goes on with everything that does not depend on the matches (a
finished answer's way out among it), and the matches are emitted at the
wave's own time, in time order."""

import threading
import time as _time

import pytest

import pathway_tpu as pw
from pathway_tpu.stdlib.indexing import BruteForceKnn, DataIndex
from pathway_tpu.stdlib.indexing.host_indexes import VectorSlabIndex

SEARCH_S = 0.3


def _docs():
    return pw.debug.table_from_rows(
        pw.schema_from_types(vec=object, text=str),
        [
            ((1.0, 0.0), "the x axis"),
            ((0.0, 1.0), "the y axis"),
            ((0.7, 0.7), "the diagonal"),
        ],
    )


def _streamed_queries(n_waves: int):
    vecs = [(0.9, 0.1), (0.1, 0.9), (0.6, 0.8), (0.8, 0.6)]
    rows = [(vecs[i % 4], i, 2 * i + 2, 1) for i in range(n_waves)]
    return pw.debug.table_from_rows(
        pw.schema_from_types(qvec=object, n=int), rows, is_stream=True
    )


@pytest.fixture
def slow_search(monkeypatch):
    """Every search takes SEARCH_S and says which thread ran it and when
    it ended."""
    ran: list[tuple[str, float]] = []
    search = VectorSlabIndex.search_batch

    def slow(self, items):
        _time.sleep(SEARCH_S)
        out = search(self, items)
        ran.append((threading.current_thread().name, _time.perf_counter()))
        return out

    monkeypatch.setattr(VectorSlabIndex, "search_batch", slow)
    return ran


def test_search_does_not_hold_the_pump(slow_search):
    """What needs the engine's thread and not the matches does not wait
    for a search: a branch beside the index delivers every wave while the
    first wave's search is still running, and the searches ran on the
    index worker."""
    docs = _docs()
    queries = _streamed_queries(4)
    index = DataIndex(docs, BruteForceKnn(data_column=docs.vec, dimensions=2))
    found = index.query_as_of_now(queries.qvec, number_of_matches=1)

    # an async UDF in the graph: the static runner then pumps by frontier
    # and defers, as the streaming runtime always does
    @pw.udf(executor=pw.udfs.async_executor())
    async def plus(n: int) -> int:
        return n + 100

    beside = queries.select(m=plus(pw.this.n))
    at: dict = {"found": [], "beside": []}
    pw.io.subscribe(
        found,
        on_change=lambda key, row, time, is_addition: at["found"].append(
            (_time.perf_counter(), time, row["text"])
        ),
    )
    pw.io.subscribe(
        beside,
        on_change=lambda key, row, time, is_addition: at["beside"].append(
            (_time.perf_counter(), row["m"])
        ),
    )
    pw.run()
    assert sorted(m for _t, m in at["beside"]) == [100, 101, 102, 103]
    assert [text for _t, _time_, text in at["found"]] == [
        ("the x axis",), ("the y axis",), ("the diagonal",), ("the diagonal",),
    ]
    # emitted at each wave's own time, in time order
    times = [time for _t, time, _text in at["found"]]
    assert times == sorted(times) and len(set(times)) == 4
    first_found = min(t for t, _time_, _text in at["found"])
    early = [m for t, m in at["beside"] if t < first_found]
    assert sorted(early) == [100, 101, 102, 103], (at, first_found)
    assert slow_search and all(
        name.startswith("pw-engine-index") for name, _end in slow_search
    ), slow_search


def test_an_answer_leaves_while_a_later_search_runs(slow_search):
    """The serving path's shape: an async UDF behind the index (the
    answerer) finishes wave t while the search of wave t + 1 runs; its
    result is delivered before that search ends, not after it."""
    import asyncio

    docs = _docs()
    queries = _streamed_queries(3)
    index = DataIndex(docs, BruteForceKnn(data_column=docs.vec, dimensions=2))
    found = index.query_as_of_now(queries.qvec, number_of_matches=1)

    @pw.udf(executor=pw.udfs.async_executor())
    async def answer(text: tuple) -> str:
        await asyncio.sleep(0.05)
        return text[0].upper()

    answers = found.select(a=answer(pw.this.text))
    seen: list = []
    pw.io.subscribe(
        answers,
        on_change=lambda key, row, time, is_addition: seen.append(
            (_time.perf_counter(), row["a"])
        ),
    )
    pw.run()
    assert [a for _t, a in seen] == ["THE X AXIS", "THE Y AXIS", "THE DIAGONAL"]
    # the searches run one after another on the worker; an answer leaves
    # after its own wave's search and before the next wave's has ended
    # (a pump standing in that search would deliver it only afterwards)
    ends = [end for _name, end in slow_search]
    assert len(ends) == 3
    for i in range(2):
        assert ends[i] < seen[i][0] < ends[i + 1], (i, seen, ends)


def test_an_index_change_behind_a_search_in_flight_waits_its_turn(slow_search):
    """A wave that changes the index while a search is in flight goes to
    the worker behind it: the earlier query sees the index as of its own
    time, the later one the changed index."""
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(vec=object, text=str),
        [
            ((1.0, 0.0), "first", 2, 1),
            ((0.9, 0.1), "closer", 6, 1),
        ],
        is_stream=True,
    )
    queries = pw.debug.table_from_rows(
        pw.schema_from_types(qvec=object, n=int),
        [((0.89, 0.11), 0, 4, 1), ((0.89, 0.11), 1, 8, 1)],
        is_stream=True,
    )
    index = DataIndex(docs, BruteForceKnn(data_column=docs.vec, dimensions=2))
    found = index.query_as_of_now(queries.qvec, number_of_matches=1)

    @pw.udf(executor=pw.udfs.async_executor())  # a pump that defers
    async def same(n: int) -> int:
        return n

    found = found.select(pw.this.text, n=same(pw.this.n))
    got: dict = {}
    pw.io.subscribe(
        found,
        on_change=lambda key, row, time, is_addition: got.__setitem__(
            row["n"], row["text"]
        ),
    )
    pw.run()
    assert got == {0: ("first",), 1: ("closer",)}
    # the query of time 4 went to the worker, and the document of time 6
    # after it while it was there
    assert [name[:15] for name, _end in slow_search] == ["pw-engine-index"] * 2


def test_a_pump_that_does_not_defer_searches_in_place(slow_search):
    """Without `allow_async` (static runs, the mesh pump) the wave runs
    to its end inside the fire, on the caller's thread."""
    docs = _docs()
    queries = pw.debug.table_from_rows(
        pw.schema_from_types(qvec=object), [((0.9, 0.1),), ((0.1, 0.9),)]
    )
    index = DataIndex(docs, BruteForceKnn(data_column=docs.vec, dimensions=2))
    res = index.query_as_of_now(queries.qvec, number_of_matches=1)
    df = pw.debug.table_to_pandas(res, include_id=False)
    assert {r.qvec: r.text for r in df.itertuples()} == {
        (0.9, 0.1): ("the x axis",), (0.1, 0.9): ("the y axis",),
    }
    assert slow_search and not any(
        name.startswith("pw-engine-index") for name, _end in slow_search
    ), slow_search


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_deferred_waves_give_the_serial_schedules_answers(monkeypatch, seed):
    """Forty waves of documents and queries in a seeded order, searches of
    uneven length: every query is answered from the index as of its own
    time, which is what the pump's thread gave when it searched in place."""
    import numpy as np

    rng = np.random.default_rng(seed)
    search = VectorSlabIndex.search_batch

    def uneven(self, items):
        _time.sleep(float(rng.uniform(0.0, 0.02)))
        return search(self, items)

    monkeypatch.setattr(VectorSlabIndex, "search_batch", uneven)
    # documents on a grid of angles 0.15 rad apart, queries 0.04 rad off a
    # grid point: the nearest document is unambiguous in bfloat16
    def unit(angle: float) -> tuple:
        return (float(np.cos(angle)), float(np.sin(angle)))

    docs, queries, want = [(unit(0.0), "d0", 2, 1)], [], {}
    live = {0: "d0"}  # grid point -> document
    free = list(rng.permutation(np.arange(1, 10)))
    for wave in range(1, 40):
        t = 2 * wave + 2
        if free and rng.random() < 0.3:
            point = int(free.pop())
            live[point] = f"d{wave}"
            docs.append((unit(0.15 * point), f"d{wave}", t, 1))
        else:
            at = 0.15 * int(rng.integers(0, 10)) + 0.04
            queries.append((unit(at), wave, t, 1))
            nearest = min(live, key=lambda point: abs(0.15 * point - at))
            want[wave] = (live[nearest],)
    docs_t = pw.debug.table_from_rows(
        pw.schema_from_types(vec=object, text=str), docs, is_stream=True
    )
    queries_t = pw.debug.table_from_rows(
        pw.schema_from_types(qvec=object, n=int), queries, is_stream=True
    )
    index = DataIndex(
        docs_t, BruteForceKnn(data_column=docs_t.vec, dimensions=2)
    )
    found = index.query_as_of_now(queries_t.qvec, number_of_matches=1)

    @pw.udf(executor=pw.udfs.async_executor())  # a pump that defers
    async def same(n: int) -> int:
        return n

    found = found.select(pw.this.text, n=same(pw.this.n))
    got: dict = {}
    pw.io.subscribe(
        found,
        on_change=lambda key, row, time, is_addition: got.__setitem__(
            row["n"], row["text"]
        ),
    )
    pw.run()
    assert got == want
