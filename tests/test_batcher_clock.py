"""The clock inside the slot scheduler (ISSUE 26): phase counters and
profiler spans of `ContinuousBatcher`, device programs that carry their
names, the per-request clocks, and the per-layer readers of
bench/layer_metrics that read them (ISSUE 28 added `prefill_pad_pct`).

A CPU trace has no device plane; what the chip shows as `XLA Modules`
events is here the `hlo_module` stat of each XLA:CPU operation, and it
goes through the benchmark's own `trace_reduce.program_name`.
"""

from __future__ import annotations

import functools
import glob
import importlib.util
import re
import sys
import time
import types
from pathlib import Path

import pytest

import pathway_tpu as pw
from pathway_tpu.engine.device_plane import DevicePlane
from pathway_tpu.internals import observability as obs
from pathway_tpu.internals.keys import Key
from pathway_tpu.models import lm_config
from pathway_tpu.serving.continuous_batching import PHASES

BENCH = Path(__file__).resolve().parents[1] / "bench"

TINY = dict(
    vocab_size=256, d_model=16, n_heads=2, n_layers=1, d_ff=32, max_len=64
)
PROMPTS = ["a b c", "d", "hello world longer prompt", "x y", "q", "z z z"]
REQUEST_CLOCKS = ("queue_wait_s", "first_token_s", "residence_s")
# `observability.thread_cpu()` mirrored into `stats` over the loop's time
CPU_MIRROR = (
    "cpu_engine_s", "cpu_udf_s", "cpu_edge_s", "cpu_pool_s", "cpu_foreign_s",
)


@pytest.fixture(autouse=True)
def _plane_off():
    yield
    obs.disable()


def _chat(**kw):
    from pathway_tpu.xpacks.llm.llms import JaxLMChat

    kw.setdefault("config", lm_config(**TINY))
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("decode_slots", 2)
    return JaxLMChat(**kw)


def _run(cb, prompts=PROMPTS):
    out = [f.result(timeout=60) for f in [cb.submit(p) for p in prompts]]
    cb.drain()
    return out


def _traced(tmp_path, work):
    """Events of a profiler session around `work()` (Python tracer off, as
    the benchmark traces): [(event name, its stats)]."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return [
        (ev.name, {k: v for k, v in ev.stats})
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
    ]


# ------------------------------------------------------------- counters


def test_stats_hold_every_key_from_construction():
    """Readers copy `stats` from other threads while the loop adds to it:
    no key may appear after `__init__`, and all clocks start at 0 but
    `preload_s`, which the construction itself fills."""
    cb = _chat()._cb
    counts = {
        "submitted", "completed", "decode_steps", "prefills", "max_queue",
        "dispatched_ahead", "dispatched_past_prefill",
        "prompt_tokens", "padded_tokens",
        # an experts decoder's device counters (0 for this block)
        "routed_pairs", "expert_load_max", "experts_touched", "moe_layers_run",
        # and those of a decoder with sparse or linear layers
        "sparse_blocks_read", "sparse_blocks_visible", "linear_tokens",
        # of one that holds a share of its experts, and of latent layers
        "router_pairs", "zero_pairs", "absent_pairs", "latent_rows_read",
    }
    clocks = (
        set(PHASES) | {"loop_s", "host_cpu_s", "preload_s", "tokenize_s"}
        | set(REQUEST_CLOCKS) | set(CPU_MIRROR)
    )
    assert set(cb.stats) == counts | clocks
    assert all(v == 0 for k, v in cb.stats.items() if k != "preload_s")
    keys_before = list(cb.stats)
    _run(cb)
    assert list(cb.stats) == keys_before
    assert cb.pool.scheduler_stats is cb.stats


def test_phases_sum_to_loop_and_tokens_are_unchanged():
    chat = _chat()
    cb = chat._cb
    _run(cb, ["warm up prompt"])  # compiles land in the dispatch phases
    got = _run(cb)
    # the clock changes no token: the wave-aligned path is the reference
    assert got == chat._generate_batch(PROMPTS)
    s = cb.stats
    phases = sum(s[k] for k in PHASES)
    assert all(s[k] > 0 for k in PHASES)
    assert phases <= s["loop_s"]
    assert (s["loop_s"] - phases) / s["loop_s"] < 0.05  # the hand-offs
    waits = s["admit_wait_s"] + s["step_wait_s"]
    assert 0 < s["host_cpu_s"] <= s["loop_s"] - waits + 0.05
    assert s["prefills"] == s["completed"] == len(PROMPTS) + 1
    assert 0 < s["queue_wait_s"] <= s["first_token_s"] <= s["residence_s"]


def test_dispatched_ahead_counts_dispatches_behind_a_running_program():
    """The count is taken where a program goes out, of the result before
    it: with every result ready it stays 0, with none ready it is every
    dispatch but the first after an idle device. No token depends on it."""
    chat = _chat()
    cb = chat._cb
    _run(cb, ["warm up prompt"])
    assert 0 <= cb.stats["dispatched_ahead"] <= (
        cb.stats["decode_steps"] + cb.stats["prefills"] - 1
    )
    sent = cb._sent

    class Running:
        def __init__(self, out):
            self.out = out

        def is_ready(self):
            return False

    def sent_never_ready(done):
        # what the count looks at is the program before: make it look busy
        if cb._out:
            behind = cb._out[-1]
            real = behind.out
            behind.out = Running(real)
            sent(done)
            behind.out = real
        else:
            sent(done)

    cb._sent = sent_never_ready
    before = dict(cb.stats)
    assert _run(cb) == chat._generate_batch(PROMPTS)
    grown = {k: cb.stats[k] - before[k] for k in before}
    assert grown["dispatched_ahead"] == (
        grown["decode_steps"] + grown["prefills"] - 1
    )


def test_the_benchmarks_wrapper_of_admit_sees_every_admitted_request():
    """`bench/pwbench/server.py _log_slots` replaces `_admit` with
    `logged(req, slot, cache)` and hands on what it returns: the loop calls
    it once a request with those three, whatever else travels."""
    sys.path.insert(0, str(BENCH))
    try:
        from pwbench.server import _log_slots
    finally:
        sys.path.remove(str(BENCH))
    chat = _chat()
    cb = chat._cb
    slot_of = _log_slots(cb)
    assert _run(cb) == chat._generate_batch(PROMPTS)
    rows = {tuple(cb.tokenizer.tokenize(p)) for p in PROMPTS}
    assert set(slot_of) == rows and set(slot_of.values()) == {0, 1}


def test_request_ids_count_submissions():
    cb = _chat()._cb
    seen = []
    admit = cb._admit

    def logged(req, slot, cache):  # the benchmark wraps _admit this way
        seen.append((req.id, slot))
        return admit(req, slot, cache)

    cb._admit = logged
    _run(cb)
    assert sorted(i for i, _ in seen) == list(range(1, len(PROMPTS) + 1))
    assert {slot for _, slot in seen} == {0, 1}


# ---------------------------------------------------------------- trace


def test_trace_holds_constant_span_names_and_named_modules(tmp_path):
    sys.path.insert(0, str(BENCH))
    try:
        from pwbench.trace_reduce import program_name
    finally:
        sys.path.remove(str(BENCH))
    cb = _chat()._cb
    _run(cb, ["warm up prompt"])
    events = _traced(tmp_path, lambda: _run(cb))
    spans = [(n, st) for n, st in events if n.startswith("cb.")]
    assert {n for n, _ in spans} == set(PHASES.values())
    # who was admitted rides as metadata, never in a name
    admits = [st for n, st in spans if n in ("cb.admit.dispatch", "cb.admit.wait")]
    assert len(admits) == 2 * len(PROMPTS)
    assert {st["req"] for st in admits} == set(range(2, len(PROMPTS) + 2))
    assert all(st["slot"] in (0, 1) and st["width"] == 16 for st in admits)
    assert not any(re.search(r"\d", n) for n, _ in spans)
    modules = {st["hlo_module"] for _, st in events if "hlo_module" in st}
    programs = {program_name(m) for m in modules}
    assert {"prefill_into_slot", "decode_step_slots"} <= programs
    assert "_unknown" not in programs


def test_wave_embed_and_knn_spans_form_a_closed_set(tmp_path):
    """One `wave <operator>` name per operator, and the constants; the
    metadata (rows) never in a name."""
    from pathway_tpu.models import embedder_config
    from pathway_tpu.stdlib.indexing.host_indexes import VectorSlabIndex
    from pathway_tpu.xpacks.llm.embedders import JaxEmbedder

    emb = JaxEmbedder(config=embedder_config(
        vocab_size=512, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_len=16, embed_dim=32,
    ))
    index = VectorSlabIndex(dimensions=32)

    def work():
        vecs = emb.encode_many(["alpha beta", "gamma", "delta epsilon"])
        for i, v in enumerate(vecs):
            index.add(Key(i + 1), v)
        index.search(vecs[0], 2)
        t = pw.debug.table_from_markdown("a\n1\n2\n3")
        pw.debug.compute_and_print(t.select(b=pw.this.a + 1))

    events = _traced(tmp_path, work)
    names = {n for n, _ in events}
    ours = {n for n in names if n.startswith(("wave ", "embed.", "knn.", "cb."))}
    waves = {n for n in ours if n.startswith(obs.SPAN_WAVE)}
    assert waves and all(
        re.fullmatch(r"wave \w+(\[.*\])?(@.*)?#\d+", n) for n in waves
    )
    assert ours - waves == {
        obs.SPAN_EMBED_ENCODE_BATCH, obs.SPAN_KNN_REFRESH, obs.SPAN_KNN_SEARCH,
    }
    (rows,) = {st["rows"] for n, st in events if n == obs.SPAN_EMBED_ENCODE_BATCH}
    assert rows == 3
    # the set is closed in the module too: the constants and nothing else
    # (a request's clock adds no span: test_the_clock_writes_no_span_...)
    assert {v for k, v in vars(obs).items() if k.startswith("SPAN_")} == (
        set(PHASES.values()) | (ours - waves) | {obs.SPAN_WAVE}
    )


# ------------------------------------------------- the observability plane


def test_plane_on_observes_each_finished_request():
    plane = obs.enable()
    cb = _chat()._cb
    _run(cb)
    n = len(PROMPTS)
    labels = {"pool": cb.pool.name}
    sums = {}
    for name, clock in zip(
        ("pathway_serving_queue_wait_seconds",
         "pathway_serving_first_token_seconds",
         "pathway_serving_request_seconds"), REQUEST_CLOCKS,
    ):
        count, sums[clock] = plane.metrics.histogram_stats(name, labels)
        assert count == n
        assert sums[clock] == pytest.approx(cb.stats[clock])
    ring = [e for e in plane.recorder.snapshot() if e["k"] == "serving.request"]
    assert sorted(e["req"] for e in ring) == list(range(1, n + 1))
    for e in ring:
        assert e["slot"] in (0, 1) and e["width"] == 16
        assert 0 <= e["queue_us"] <= e["first_us"] <= e["total_us"]


def test_plane_off_receives_nothing_from_the_clock():
    cb = _chat()._cb
    _run(cb)
    assert obs.PLANE is None and cb.stats["residence_s"] > 0
    plane = obs.enable()  # switched on after the fact: still empty
    assert not any(n.startswith("pathway_serving") for n in plane.metrics.snapshot())
    assert plane.recorder.snapshot() == []


def test_statistics_and_metrics_show_the_batcher_beside_its_pool():
    from pathway_tpu.internals.metrics import _render_metrics, render_statistics

    chat = _chat()  # held: its finalizer drops the pool from the plane
    cb = chat._cb
    _run(cb)
    shown = render_statistics(None, time.time())["device_plane"]
    assert shown["batchers"][cb.pool.name] == cb.stats
    assert cb.pool.name in shown["slot_pools"]
    text = _render_metrics(None, time.time())
    line = f'pathway_serving_batcher{{pool="{cb.pool.name}",stat="prefills"}}'
    assert f"{line} {len(PROMPTS)}" in text
    cb.close()  # the stats go with the pool
    assert cb.pool.name not in render_statistics(None, time.time()).get(
        "device_plane", {}
    ).get("batchers", {})


def test_rest_route_sums_the_residence_of_its_200s():
    import threading

    import requests
    from conftest import free_port_base

    from pathway_tpu.internals import run as run_mod

    port = free_port_base()
    ws = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    queries, writer = pw.io.http.rest_connector(
        webserver=ws, route="/clock",
        schema=pw.schema_from_types(query=str, user=str),
    )
    writer(queries.select(result=pw.this.query))
    assert pw.io.http.route_stats()["/clock"]["residence_s"] == 0.0
    runner = threading.Thread(target=pw.run, daemon=True)
    runner.start()
    url = f"http://127.0.0.1:{port}/clock"
    try:
        at_client, deadline = 0.0, time.monotonic() + 20
        answered = 0
        while answered < 3 and time.monotonic() < deadline:
            t0 = time.monotonic()
            try:
                r = requests.post(url, json={"query": "q", "user": "u"}, timeout=10)
            except requests.ConnectionError:
                time.sleep(0.05)
                continue
            if r.status_code == 200:
                answered += 1
                at_client += time.monotonic() - t0
    finally:
        run_mod.stop_current_run()
        ws.stop()
        runner.join(timeout=20)
    stats = pw.io.http.route_stats()["/clock"]
    assert answered == 3 and stats["responses"] == 3
    # handler entry to reply lies inside what the client waited
    assert 0 < stats["residence_s"] <= at_client
    # no async node, no index and no batcher on this route: a clock holds
    # the three stages the handler itself stamps, and is gone at the reply
    assert obs.CLOCKS == {}
    assert {k for k, v in stats["stage_s"].items() if v} == {
        obs.STAGE_IN, obs.STAGE_EGRESS, obs.STAGE_REPLY,
    }
    assert sum(stats["stage_s"].values()) == pytest.approx(
        stats["residence_s"], abs=1e-3
    )
    assert len(stats["recent"]) == 3


# ------------------------------------------------------ a request's clock


def _post(port, route, payload, timeout=60):
    import requests

    return requests.post(
        f"http://127.0.0.1:{port}{route}", json=payload, timeout=timeout
    )


@pytest.fixture(scope="module")
def edge_run(tmp_path_factory):
    """A tiny RAG server (documents -> `DocumentStore` -> `JaxEmbedder` ->
    the exact index -> `BaseRAGQuestionAnswerer` over a `JaxLMChat`, so a
    `ContinuousBatcher`) answers `/v2/answer` twice with `PLANE` off, then
    twice with it on and a profiler session open, with one `/v1/retrieve`
    and one `/v2/summarize` (the batcher behind a single async node, no
    embedder and no index); what each layer left behind is what the tests
    below look at."""
    from conftest import free_port_base

    from pathway_tpu.internals import run as run_mod
    from pathway_tpu.internals.metrics import render_statistics
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.models import embedder_config
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import JaxEmbedder
    from pathway_tpu.xpacks.llm.question_answering import (
        BaseRAGQuestionAnswerer,
    )
    from pathway_tpu.xpacks.llm.servers import QASummaryRestServer

    G.clear()
    obs.disable()
    docs = [f"passage {i} about topic{i} and thing{i % 3}" for i in range(8)]
    embedder = JaxEmbedder(config=embedder_config(
        vocab_size=512, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_len=16, embed_dim=32,
    ))
    chat = _chat(config=lm_config(**{**TINY, "max_len": 128}))
    table = pw.debug.table_from_rows(
        pw.schema_from_types(data=bytes, _metadata=object),
        [(d.encode(), {"path": f"doc{i}.txt"}) for i, d in enumerate(docs)],
    )
    store = DocumentStore(table, retriever_factory=BruteForceKnnFactory(
        dimensions=32, embedder=embedder,
    ))
    qa = BaseRAGQuestionAnswerer(chat, store, search_topk=2)
    port = free_port_base()
    # every stamp of every clock, in the order written: (key, stage)
    written = []
    stamp = obs.RequestClock.stamp

    def counted(self, stage, at=None):
        written.append((self.key, stage))
        stamp(self, stage, at)

    obs.RequestClock.stamp = counted
    qa.server = QASummaryRestServer("127.0.0.1", port, qa)  # `/v2/summarize` too
    thread = qa.run_server(
        threaded=True, with_cache=False, terminate_on_error=True,
    )
    out = {"thread_name": thread.name, "written": written, "batcher": chat._cb}
    ask = {"prompt": "what about topic3", "return_context_docs": True}
    try:
        deadline = time.monotonic() + 120
        while True:  # until the index holds the documents
            try:
                r = _post(port, "/v1/retrieve", {"query": "topic3", "k": 2})
                if r.status_code == 200 and len(r.json()) == 2:
                    break
            except OSError:
                pass
            assert time.monotonic() < deadline, "the server never answered"
            time.sleep(0.1)
        del written[:]
        before = pw.io.http.route_stats()
        stats_before = dict(chat._cb.stats)
        out["off"] = [_post(port, "/v2/answer", ask) for _ in range(2)]
        plane = obs.enable()

        def work():
            out["on"] = [_post(port, "/v2/answer", ask) for _ in range(2)]
            out["retrieve"] = _post(port, "/v1/retrieve", {"query": "topic5", "k": 2})
            out["summarize"] = _post(
                port, "/v2/summarize", {"text_list": ["topic1 is one", "thing2"]}
            )

        out["events"] = _traced(tmp_path_factory.mktemp("edge"), work)
        out["ring"] = plane.recorder.snapshot()
        out["statistics"] = render_statistics(None, time.time())
        after = pw.io.http.route_stats()
        out["clocks_left"] = dict(obs.CLOCKS)
        out["grown"] = {
            k: v - stats_before[k] for k, v in chat._cb.stats.items()
        }
    finally:
        obs.RequestClock.stamp = stamp
        obs.disable()
        run_mod.stop_current_run()
        qa.server.webserver.stop()
        thread.join(timeout=60)
        G.clear()
    for route in ("/v2/answer", "/v1/retrieve", "/v2/summarize"):
        a, b = after[route], before[route]
        out[route] = {
            "responses": a["responses"] - b["responses"],
            "residence_s": a["residence_s"] - b["residence_s"],
            "stage_s": {k: v - b["stage_s"][k] for k, v in a["stage_s"].items()},
            "recent": a["recent"][len(b["recent"]):],
        }
    return out


def test_a_request_stamps_every_stage_once_in_order_and_they_sum_to_its_residence(
    edge_run,
):
    assert [r.status_code for r in edge_run["off"] + edge_run["on"]] == [200] * 4
    assert all(len(r.json()["context_docs"]) == 2 for r in edge_run["off"])
    answers = edge_run["/v2/answer"]
    assert answers["responses"] == 4 and len(answers["recent"]) == 4
    # written once each, in the order of the closed set
    by_key = {}
    for key, stage in edge_run["written"]:
        by_key.setdefault(key, []).append(stage)
    whole = [stages for stages in by_key.values() if len(stages) == len(obs.STAGES)]
    assert len(whole) == 4 and all(s == list(obs.STAGES) for s in whole)
    # contiguous: 13 instants in order, no stage of no length
    for stamps in answers["recent"]:
        assert len(stamps) == len(obs.STAGES) + 1
        assert all(end > start for start, end in zip(stamps, stamps[1:]))
    assert all(v > 0 for v in answers["stage_s"].values())
    assert sum(answers["stage_s"].values()) == pytest.approx(
        answers["residence_s"], abs=1e-3
    )
    assert sum(s[-1] - s[0] for s in answers["recent"]) == pytest.approx(
        answers["residence_s"], abs=1e-3
    )
    # the batcher's own stages are its own clocks of the same requests
    # (with the one `/v2/summarize`, which passes the batcher too)
    grown = edge_run["grown"]
    summed = edge_run["/v2/summarize"]["stage_s"]
    in_batcher = sum(
        answers["stage_s"][k] + summed[k]
        for k in (obs.STAGE_TOKENIZE, obs.STAGE_QUEUE, obs.STAGE_FIRST, obs.STAGE_DECODE)
    )
    assert grown["completed"] == 5
    assert in_batcher == pytest.approx(grown["residence_s"], abs=1e-3)
    assert answers["stage_s"][obs.STAGE_TOKENIZE] + summed[
        obs.STAGE_TOKENIZE
    ] == pytest.approx(grown["tokenize_s"], abs=1e-6)
    assert 0 < grown["tokenize_s"] < grown["queue_wait_s"] + grown["tokenize_s"]


def test_a_route_off_the_batchers_path_stamps_only_the_stages_it_passes(edge_run):
    """`/v1/retrieve` has the embedder and the index on its path and no
    answering UDF: its clock holds no stage of the batcher's, and the
    stages it has still sum to its residence."""
    assert edge_run["retrieve"].status_code == 200
    retrieve = edge_run["/v1/retrieve"]
    assert retrieve["responses"] == 1
    passed = {k for k, v in retrieve["stage_s"].items() if v > 0}
    assert passed == {
        obs.STAGE_IN, obs.STAGE_INGRESS, obs.STAGE_EMBED, obs.STAGE_SEARCH,
        obs.STAGE_EGRESS, obs.STAGE_REPLY,
    }
    assert sum(retrieve["stage_s"].values()) == pytest.approx(
        retrieve["residence_s"], abs=1e-3
    )


def test_no_clock_outlives_its_request(edge_run):
    assert edge_run["clocks_left"] == {} and obs.CLOCKS == {}


def test_a_route_with_the_batcher_behind_one_async_node_has_no_stage_below_zero(
    edge_run,
):
    """`/v2/summarize` has no embedder and no index: its one async node
    calls `ContinuousBatcher.submit`. Each stage is stamped by the layer
    that does its work, so the stages nobody does have no length, those of
    the batcher hold the LLM's time, and none is negative."""
    assert edge_run["summarize"].status_code == 200
    assert "response" in edge_run["summarize"].json()
    summarize = edge_run["/v2/summarize"]
    assert summarize["responses"] == 1
    (stamps,) = summarize["recent"]
    assert all(end >= start for start, end in zip(stamps, stamps[1:]))
    stage_s = summarize["stage_s"]
    assert all(v >= 0 for v in stage_s.values())
    assert {k for k, v in stage_s.items() if v > 0} == set(obs.STAGES) - {
        obs.STAGE_EMBED, obs.STAGE_SEARCH, obs.STAGE_PAYLOAD,
    }
    # the answer's time is the batcher's, not the edge's
    batcher = sum(
        stage_s[k]
        for k in (obs.STAGE_TOKENIZE, obs.STAGE_QUEUE, obs.STAGE_FIRST, obs.STAGE_DECODE)
    )
    assert batcher > stage_s[obs.STAGE_PROMPT] + stage_s[obs.STAGE_EGRESS]
    assert sum(stage_s.values()) == pytest.approx(
        summarize["residence_s"], abs=1e-3
    )
    # written once each, in the order of the closed set
    by_key = {}
    for key, stage in edge_run["written"]:
        by_key.setdefault(key, []).append(stage)
    want = [s for s in obs.STAGES if stage_s[s] > 0]
    assert sum(stages == want for stages in by_key.values()) == 1


def test_the_clock_writes_no_span_and_no_event_of_the_plane(edge_run):
    """The clock's sinks are the route's `stage_s` and `recent` and nothing
    else: with `PLANE` on and a profiler session open, three routes' 200s
    leave no ring event and no span of their own (the reduced trace keeps
    no per-name host totals, so nothing could read one: PERF.md, Open
    questions)."""
    assert [e for e in edge_run["ring"] if e["k"].startswith("edge.")] == []
    kinds = {e["k"] for e in edge_run["ring"]}
    assert "serving.request" in kinds  # the batcher's, as before
    ours = {
        n for n, _ in edge_run["events"]
        if n.startswith(("qa.", "edge.", "cb.", "embed.", "knn."))
    }
    assert ours <= {v for k, v in vars(obs).items() if k.startswith("SPAN_")}
    assert not any(n.startswith(("qa.", "edge.")) for n in ours)
    assert "cb.submit" not in ours


def test_statistics_show_the_routes_stages_and_the_cpu_by_role(edge_run):
    shown = edge_run["statistics"]
    route = shown["routes"]["/v2/answer"]
    assert route["responses"] >= 4
    assert set(route["stage_ms"]) == set(obs.STAGES)
    assert sum(route["stage_ms"].values()) == pytest.approx(route["residence_ms"])
    assert set(shown["thread_cpu"]) == set(obs.CPU_ROLES) | {"native"}
    # the server's pump ran in a thread of that name
    assert edge_run["thread_name"] == "pw-engine"
    assert shown["thread_cpu"]["engine"] > 0 and shown["thread_cpu"]["edge"] > 0
    assert shown["thread_cpu"]["udf"] > 0


def test_the_loop_mirrors_the_cpu_by_role_into_stats(edge_run):
    grown = edge_run["grown"]
    assert grown["loop_s"] > 0
    assert all(grown[k] >= 0 for k in CPU_MIRROR)
    # the engine polled while it looped (this test's own thread, `foreign`,
    # slept on its socket: tests/test_bench_edge.py has clients that work)
    assert grown["cpu_engine_s"] > 0


def _edge(route, timeout_s=120.0):
    from conftest import free_port_base

    port = free_port_base()
    ws = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    queries, writer = pw.io.http.rest_connector(
        webserver=ws, route=route, timeout_s=timeout_s,
        schema=pw.schema_from_types(query=str),
    )
    return port, ws, queries, writer


def test_the_clock_dict_is_empty_after_a_503():
    """No pipeline runs behind the route: the handler has made the row's
    key and its clock by then, and drops the clock with the refusal."""
    port, ws, _queries, _writer = _edge("/nobody")
    ws.start()
    try:
        r = _post(port, "/nobody", {"query": "q"})
    finally:
        ws.stop()
    assert r.status_code == 503 and obs.CLOCKS == {}
    stats = pw.io.http.route_stats()["/nobody"]
    assert stats["responses"] == 0 and stats["residence_s"] == 0.0
    assert not stats["recent"] and not any(stats["stage_s"].values())


def test_the_clock_dict_is_empty_after_a_504():
    import threading

    from pathway_tpu.internals import run as run_mod

    port, ws, queries, writer = _edge("/never", timeout_s=0.3)
    writer(queries.filter(pw.this.query == "nobody asks this").select(
        result=pw.this.query
    ))
    runner = threading.Thread(target=pw.run, daemon=True)
    runner.start()
    try:
        deadline = time.monotonic() + 20
        while True:  # until the server listens and the pipeline runs
            try:
                r = _post(port, "/never", {"query": "q"})
                if r.status_code != 503:
                    break
            except OSError:
                pass
            assert time.monotonic() < deadline
            time.sleep(0.05)
    finally:
        run_mod.stop_current_run()
        ws.stop()
        runner.join(timeout=20)
    assert r.status_code == 504 and obs.CLOCKS == {}
    stats = pw.io.http.route_stats()["/never"]
    assert stats["timeouts"] == 1 and not stats["recent"]
    assert stats["residence_s"] == 0.0  # the 200s' only


def _stamped(clock):
    """The stages some layer has stamped on the clock, in the set's order."""
    return [s for s, at in zip(obs.STAGES, clock.t[1:]) if at]


class _CountingDict(dict):
    """`CLOCKS` with its truth tests and look-ups counted."""

    def __init__(self, *a):
        super().__init__(*a)
        self.tests = self.gets = 0

    def __bool__(self):
        self.tests += 1
        return len(self) > 0

    def get(self, *a):
        self.gets += 1
        return super().get(*a)


def test_a_wave_over_an_empty_dict_tests_it_once_and_looks_nothing_up(monkeypatch):
    """An ingest's rows come from no REST route: while no request is in
    flight, a wave of them through an async node pays one truth test of
    `CLOCKS` and no look-up a row; with a request in flight, a row whose
    key has no clock looks once and stamps nothing."""
    from pathway_tpu.engine.runtime import _run_async_batch

    clocks = _CountingDict()
    monkeypatch.setattr(obs, "CLOCKS", clocks)
    rows = [(Key(i + 1), (i,)) for i in range(100)]
    graph = types.SimpleNamespace(log_error=lambda msg: None)

    async def double(k, r):
        assert obs.current_clock() is None
        return 2 * r[0]

    got = _run_async_batch(double, rows, graph)
    assert len(got) == 100 and (clocks.tests, clocks.gets) == (1, 0)
    # a request in flight, of another key
    other = obs.RequestClock()
    clocks[10**9] = other
    _run_async_batch(double, rows, graph)
    assert (clocks.tests, clocks.gets) == (2, 100)
    obs.stamp(5, obs.STAGE_SEARCH)  # a key without a clock
    assert _stamped(other) == []
    assert other.stamps() == (other.t[0],) * (len(obs.STAGES) + 1)


def test_an_async_node_hands_its_rows_clock_to_the_function_it_calls(monkeypatch):
    """The first async node a clocked row reaches ends `ingress`; every
    one makes the clock the `current_clock()` of its call, and of nothing
    else, and stamps no stage whose work is the function's: the function
    does that (`stamp_current`)."""
    from pathway_tpu.engine.runtime import _run_async_batch

    monkeypatch.setattr(obs, "CLOCKS", {})
    clock = obs.RequestClock()
    clock.key = 7
    obs.CLOCKS[7] = clock
    seen = []

    async def fn(k, r):
        seen.append((k.value, obs.current_clock()))
        return r[0]

    async def embeds(k, r):
        obs.stamp_current(obs.STAGE_EMBED)  # a row without a clock: nothing
        return r[0]

    graph = types.SimpleNamespace(log_error=lambda msg: None)
    rows = [(Key(7), ("mine",)), (Key(8), ("no clock",))]
    _run_async_batch(fn, rows, graph)
    assert sorted(seen, key=lambda x: x[0]) == [(7, clock), (8, None)]
    assert _stamped(clock) == [obs.STAGE_INGRESS]
    ingress = clock.t[2]
    _run_async_batch(embeds, rows, graph)
    assert _stamped(clock) == [
        obs.STAGE_INGRESS, obs.STAGE_EMBED,
    ]
    # a later node does not move `ingress`; nor does the first one a row
    # reaches behind a stage that a synchronous operator stamped
    assert clock.t[2] == ingress
    late = obs.RequestClock()
    late.stamp(obs.STAGE_SEARCH)
    obs.CLOCKS[8] = late
    _run_async_batch(fn, rows, graph)
    assert _stamped(late) == [obs.STAGE_SEARCH]
    assert obs.current_clock() is None


def test_submit_stamps_the_callers_clock_and_finish_copies_the_requests():
    """`submit` on behalf of a REST request (`current_clock()`): `prompt`
    ends where `t_submit` is taken and `tokenize` where the request is
    queued; `_finish` writes `queue`, `first` and `decode` from the
    request's own instants before the future resolves."""
    import contextvars

    cb = _chat()._cb
    clock = obs.RequestClock()
    before = dict(cb.stats)

    def submit():
        obs.clocked(clock)
        return cb.submit("a b c")

    fut = contextvars.copy_context().run(submit)
    fut.result(timeout=60)
    at = dict(zip(obs.STAGES, clock.t[1:]))
    assert _stamped(clock) == [
        obs.STAGE_PROMPT, obs.STAGE_TOKENIZE, obs.STAGE_QUEUE, obs.STAGE_FIRST,
        obs.STAGE_DECODE,
    ]
    assert clock.t[0] <= at["prompt"] < at["tokenize"] <= at["queue"]
    assert at["queue"] < at["first"] < at["decode"]
    cb.drain()
    grown = {k: cb.stats[k] - before[k] for k in before}
    assert grown["tokenize_s"] == pytest.approx(at["tokenize"] - at["prompt"])
    assert grown["queue_wait_s"] == pytest.approx(at["queue"] - at["prompt"])
    assert grown["residence_s"] == pytest.approx(at["decode"] - at["prompt"])
    # a caller with no clock stamps nothing and still counts its tokenising
    _run(cb, ["d e f"])
    assert cb.stats["tokenize_s"] > before["tokenize_s"] + grown["tokenize_s"]
    assert obs.current_clock() is None


# ------------------------------------------------------ CPU by thread role


def test_thread_cpu_names_a_busy_thread_by_the_role_its_name_gives_it():
    import threading

    stop = threading.Event()

    def burn():
        while not stop.is_set():
            sum(range(1000))

    threads = [
        threading.Thread(target=burn, name=name, daemon=True)
        for name in ("bench-client-0", "pw-engine", "pw-cb-cb#9", "pw-worker_3")
    ]
    before = obs.thread_cpu()
    for t in threads:
        t.start()
    time.sleep(0.4)
    during = obs.thread_cpu()
    process = time.process_time()
    stop.set()
    for t in threads:
        t.join()
    grown = {k: during[k] - before[k] for k in during}
    for role in ("foreign", "engine", "batcher", "pool"):
        assert grown[role] > 0.02, grown
    assert set(during) == set(obs.CPU_ROLES) | {"native"}
    # the roles and `native` are the process's CPU time (the two were read
    # a few microseconds apart)
    assert sum(during.values()) == pytest.approx(process, abs=0.05)
    assert during["native"] >= 0
    # the threads have ended: what they burnt is `native`'s now
    after = obs.thread_cpu()
    assert after["engine"] < during["engine"] and after["native"] > during["native"]


def test_the_cpu_clock_of_a_thread_is_the_one_its_native_id_names():
    """`thread_cpu` makes a thread's CPU clock from its kernel id and not
    from its `pthread_t` (which is freed when the thread ends): the two
    name the same clock while the thread lives, and a role is matched by
    the thread's name (once a name: the match is cached)."""
    import threading

    me = threading.current_thread()
    clock_id = obs._cpu_clock_id(me.native_id)
    assert clock_id == time.pthread_getcpuclockid(me.ident)
    assert time.clock_gettime(clock_id) == pytest.approx(
        time.thread_time(), abs=0.05
    )
    assert obs._role_of(me.name) == "foreign"  # pytest's main thread is nobody's
    # the thread that runs the benchmark's profiler is the instrument's, not
    # the load's: a traced run's `foreign` reads as an untraced one's
    assert obs._role_of("bench-tracer") == "tracer"
    assert obs._role_of("bench-client-3") == obs._role_of("bench-load") == "foreign"
    assert obs._role_of.cache_info().currsize >= 3


# ------------------------------------------------------ program names


def _module_name(prog, *args, **kwargs):
    return re.search(r"module @(\S+)", prog.lowered_text(*args, **kwargs)).group(1)


def test_device_programs_carry_their_function_names():
    import jax.numpy as jnp

    def scale(x, *, by, k=1):
        return x * by + k

    plane = DevicePlane()
    x = jnp.ones(3)
    partial = plane.program(
        "cb#1/scale", functools.partial(functools.partial(scale, by=2.0)),
        static_argnames=("k",),
    )
    assert _module_name(partial, x, k=3) == "jit_scale"
    assert partial(x, k=3, bucket=3).tolist() == [5.0, 5.0, 5.0]
    lam = plane.program("cb#1/slab update", lambda a: a + 1)
    assert _module_name(lam, x) == "jit_cb_1_slab_update"
    named = plane.program("other", scale, static_argnames=("by", "k"))
    assert _module_name(named, x, by=2.0) == "jit_scale"
    assert named._jit.__wrapped__ is scale  # a named function is not wrapped


# ------------------------------------------------------- the pump's idle


def test_drain_records_the_idle_time_it_measured():
    from pathway_tpu.engine.runtime import Runtime

    plane = obs.enable()
    polls = iter([False] * 4 + [True])
    sched = types.SimpleNamespace(
        fully_drained=lambda: next(polls), pump=lambda: 0,
        has_async=lambda: True,
    )
    t0 = time.perf_counter()
    Runtime._drain(types.SimpleNamespace(_ASYNC_STALL_S=900.0), sched, "test")
    wall = time.perf_counter() - t0
    idle = plane.metrics.counter_value(
        "pathway_runtime_stage_seconds_total", {"stage": "idle"}
    )
    # four sleeps of at least 0.5 ms each, measured and not assumed
    assert 4 * 0.0005 <= idle <= wall
    assert idle != pytest.approx(4 * 0.0005, abs=1e-9)


# ------------------------------------------------------------ the readers


def _reader(name):
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"_reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(batcher, records=()):
    return {
        "counters": {"batcher": batcher}, "records": list(records),
        "mix": {"route": WORKED_ROUTE},
    }


def _rec(sent, done, status=200):
    return types.SimpleNamespace(sent=sent, done=done, status=status)


# 51 s of loop, 40 + 6 s of it waiting for the device: 5 s of host time
WORKED = {
    "decode_steps": 900, "prefills": 100, "completed": 100,
    "loop_s": 51.0, "admit_wait_s": 6.0, "step_wait_s": 40.0,
    "host_cpu_s": 4.0, "queue_wait_s": 150.0, "residence_s": 330.0,
    # 100 prompts of 1,242 tokens, each at the 1280 rung
    "prompt_tokens": 124_200, "padded_tokens": 128_000,
}
RECORDS = [_rec(0.0, 3.5), _rec(1.0, 4.7), _rec(2.0, 9.0, status=500)]
# the route whose finished clocks the table's clock readers window, and two
# clocks whose handler entries (0.1, 1.1) lie between the first and the last
# `sent` (0.0, 2.0), as `RequestClock.stamps()` gives them. Seconds a stage:
#   in .01, ingress .02, embed .03 / .05, search .04 / .06, prompt .01,
#   tokenize .02, queue 1.5, first .5, decode 1.0 / 1.1, payload .03,
#   egress .02 / .04, reply .05
# and one whose entry (2.5) lies after the last `sent`: in no mean
WORKED_ROUTE = "/worked"


def _stamps(t0, seconds):
    out = [t0]
    for s in seconds:
        out.append(out[-1] + s)
    return tuple(out)


WORKED_CLOCKS = [
    _stamps(0.1, (.01, .02, .03, .04, .01, .02, 1.5, .5, 1.0, .03, .02, .05)),
    _stamps(1.1, (.01, .02, .05, .06, .01, .02, 1.5, .5, 1.1, .03, .04, .05)),
    _stamps(2.5, (1.0,) * 12),
]
# the cumulative mirror of observability.thread_cpu over 51 s of loop
WORKED_CPU = {
    **WORKED, "submitted": 100, "tokenize_s": 0.4,
    "cpu_engine_s": 5.1, "cpu_udf_s": 2.55, "cpu_edge_s": 1.02,
    "cpu_pool_s": 1.53, "cpu_foreign_s": 7.65,
}


@pytest.mark.parametrize("name,batcher,records,want", [
    ("host_per_dispatch_ms", WORKED, (), 5.0),  # 5 s over 1,000 dispatches
    ("host_per_dispatch_ms", {"loop_s": 1.0}, (), None),  # no dispatch
    ("host_per_dispatch_ms", {"decode_steps": 9, "prefills": 1}, (), None),
    ("host_stall_pct", WORKED, (), 20.0),  # 4 s of CPU in 5 s of host time
    ("host_stall_pct", {**WORKED, "host_cpu_s": 5.2}, (), 0.0),  # not below 0
    ("host_stall_pct", {**WORKED, "loop_s": 46.0}, (), None),  # no host time
    ("host_stall_pct", {"decode_steps": 9}, (), None),
    ("queue_wait_ms", WORKED, (), 1500.0),
    ("queue_wait_ms", {**WORKED, "prefills": 0}, (), None),
    ("queue_wait_ms", {"prefills": 10}, (), None),
    # the 200s took 3.5 and 3.7 s at the client, 3.3 s of it in the batcher
    ("outside_batcher_ms", WORKED, RECORDS, 300.0),
    ("outside_batcher_ms", {**WORKED, "completed": 0}, RECORDS, None),
    ("outside_batcher_ms", WORKED, RECORDS[2:], None),  # no 200 to average
    ("outside_batcher_ms", {"completed": 5}, RECORDS, None),
    ("prefill_pad_pct", WORKED, (), 2.96875),  # 3,800 of 128,000
    # the same prompts at the cap's width, 2016: what the ladder removed
    ("prefill_pad_pct", {**WORKED, "padded_tokens": 201_600}, (), 38.3928571),
    ("prefill_pad_pct", {**WORKED, "padded_tokens": 124_200}, (), 0.0),
    ("prefill_pad_pct", {**WORKED, "padded_tokens": 0}, (), None),
    ("prefill_pad_pct", {"prefills": 10, "padded_tokens": 20_160}, (), None),
    ("prefill_pad_pct", {"prefills": 10}, (), None),  # the parent commit
    # ISSUE 40's seven. The two clocks in the window: in .01 + ingress .02
    # + embed .04 + search .05 + prompt .01
    ("edge_inbound_ms", WORKED, RECORDS, 130.0),
    ("edge_inbound_ms", WORKED, (), None),  # no record: no window
    # records sent before any clock's entry: no matching clock
    ("edge_inbound_ms", WORKED, [_rec(-2.0, 3.5), _rec(-1.0, 4.7)], None),
    ("retrieve_wait_ms", WORKED, RECORDS, 90.0),  # embed .04 + search .05
    ("retrieve_wait_ms", WORKED, RECORDS[:1], None),  # a window of no width
    ("edge_outbound_ms", WORKED, RECORDS, 110.0),  # .03 + .03 + .05
    ("edge_outbound_ms", WORKED, (), None),
    # 3.5 and 3.7 s at the client, 3.23 and 3.39 s of them in the handler
    ("client_side_ms", WORKED, RECORDS, 290.0),
    # the second reply was built (4.49) after its client says it was done:
    # that clock finds no request of its own and is in no mean
    ("client_side_ms", WORKED, [_rec(0.0, 3.5), _rec(1.0, 4.0)], 270.0),
    ("client_side_ms", WORKED, RECORDS[2:], None),  # no 200 to average
    ("client_side_ms", WORKED, [_rec(-2.0, 3.5), _rec(-1.0, 4.7)], None),
    ("tokenize_ms", WORKED_CPU, (), 4.0),  # 0.4 s over 100 prompts
    ("tokenize_ms", {**WORKED_CPU, "submitted": 0}, (), None),
    ("tokenize_ms", WORKED, (), None),  # the parent commit: no such key
    # 5.1 + 2.55 + 1.02 + 1.53 = 10.2 s of 51 s
    ("program_threads_cpu_pct", WORKED_CPU, (), 20.0),
    ("program_threads_cpu_pct", {"cpu_engine_s": 5.1, "loop_s": 51.0}, (), 10.0),
    ("program_threads_cpu_pct", {**WORKED_CPU, "loop_s": 0.0}, (), None),
    ("program_threads_cpu_pct", WORKED, (), None),  # the parent commit
    ("harness_threads_cpu_pct", WORKED_CPU, (), 15.0),  # 7.65 s of 51 s
    ("harness_threads_cpu_pct", {"cpu_foreign_s": 1.0}, (), None),  # no loop
    ("harness_threads_cpu_pct", WORKED, (), None),  # the parent commit
])
def test_reader_worked_numbers_and_empty_divisors(
    monkeypatch, name, batcher, records, want,
):
    """A divisor of 0 and a program without the counters (the parent
    commit: only the counts) both read None, and never raise; so does a
    reader of the request clocks that finds no clock in its window."""
    from pathway_tpu.io import http

    monkeypatch.setitem(
        http._ROUTE_STATS, WORKED_ROUTE,
        {"stage_s": {}, "recent": list(WORKED_CLOCKS)},
    )
    got = _reader(name)(_ctx(batcher, records))
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_clock_readers_read_nothing_from_a_program_without_the_clock(
    monkeypatch,
):
    """The parent commit's `route_stats()` has no `recent`, and its
    observability no `STAGES`: the benchmark lays these readers over it."""
    from pathway_tpu.io import http

    monkeypatch.setitem(http._ROUTE_STATS, WORKED_ROUTE, {"residence_s": 6.6})
    monkeypatch.setattr(
        http, "route_stats", lambda: {r: dict(s) for r, s in http._ROUTE_STATS.items()}
    )
    names = ("edge_inbound_ms", "retrieve_wait_ms", "edge_outbound_ms",
             "client_side_ms")
    assert [_reader(n)(_ctx(WORKED, RECORDS)) for n in names] == [None] * 4
    monkeypatch.delattr(obs, "STAGES")
    assert [_reader(n)(_ctx(WORKED, RECORDS)) for n in names] == [None] * 4


def test_a_clock_reader_reads_nothing_where_a_full_recent_may_have_dropped_a_clock(
    monkeypatch,
):
    """`recent` keeps the `RECENT_CLOCKS` that finished last. Full, and its
    oldest replied inside the window: one dropped before it may have been
    entered inside the window too, so the mean would lean to the late ones;
    the readers say nothing instead. Full, and its oldest replied before the
    window opened: whatever was dropped lies outside it."""
    from pathway_tpu.io import http

    names = ("edge_inbound_ms", "retrieve_wait_ms", "edge_outbound_ms",
             "client_side_ms")
    monkeypatch.setitem(
        http._ROUTE_STATS, WORKED_ROUTE,
        {"stage_s": {}, "recent": list(WORKED_CLOCKS)},
    )
    monkeypatch.setattr(http, "RECENT_CLOCKS", len(WORKED_CLOCKS))
    ctx = _ctx(WORKED, RECORDS)  # opens at 0.0; the oldest replied at 3.33
    assert [_reader(n)(ctx) for n in names] == [None] * 4
    # a window that opens at 4.0, behind that reply: its one clock is read
    late = _stamps(4.5, (.01, .02, .03, .04, .01, .02, 1.5, .5, 1.0, .03, .02, .05))
    monkeypatch.setitem(
        http._ROUTE_STATS, WORKED_ROUTE,
        {"stage_s": {}, "recent": [*WORKED_CLOCKS[:2], late]},
    )
    ctx = _ctx(WORKED, [_rec(4.0, 8.0), _rec(5.0, 9.0)])
    assert _reader("edge_inbound_ms")(ctx) == pytest.approx(110.0)
    assert _reader("client_side_ms")(ctx) == pytest.approx(1e3 * (4.0 - 3.23))
    # and one entry short of full, the first window is read as it was
    monkeypatch.setattr(http, "RECENT_CLOCKS", len(WORKED_CLOCKS) + 1)
    assert _reader("edge_inbound_ms")(_ctx(WORKED, RECORDS)) == pytest.approx(130.0)


def test_the_readers_identity_adds_up_to_the_clients_time():
    """inbound + (tokenize + queue + first + decode) + outbound + the
    client's side = the mean of `done - sent`: the three parts outside the
    batcher are `outside_batcher_ms` where the batcher's counters and the
    clocks cover the same requests."""
    from pathway_tpu.io import http

    http._ROUTE_STATS[WORKED_ROUTE] = {"stage_s": {}, "recent": list(WORKED_CLOCKS)}
    try:
        # the batcher's residence of the same two requests: 3.02 + 3.12 s
        batcher = {**WORKED, "completed": 2, "residence_s": 6.14}
        ctx = _ctx(batcher, RECORDS)
        parts = sum(_reader(n)(ctx) for n in (
            "edge_inbound_ms", "edge_outbound_ms", "client_side_ms"))
        assert parts == pytest.approx(_reader("outside_batcher_ms")(ctx))
        assert parts + 1e3 * 6.14 / 2 == pytest.approx(1e3 * (3.5 + 3.7) / 2)
    finally:
        del http._ROUTE_STATS[WORKED_ROUTE]


def _listed(names):
    import json

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in names:
        m = entries[f"{name}.tput"]
        assert "rag-cerebras-6b7.backlog" in m["workloads"]
        assert m["moves"] == "answers_per_s"
        # the client's clock less a counter of the program's: the former's
        assert m["source"] == (
            "host_clock" if name == "client_side_ms" else "program_counter"
        )
        assert m["better"] == "lower"
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    return entries


def test_benchmark_lists_the_seven_edge_and_cpu_metrics_for_the_three_cells():
    """ISSUE 40: appended to `per_layer`, each listing the three accepted
    cells, in the layers `PERF.md` section 3 names."""
    import json

    names = ("edge_inbound_ms", "retrieve_wait_ms", "edge_outbound_ms",
             "client_side_ms", "tokenize_ms", "program_threads_cpu_pct",
             "harness_threads_cpu_pct")
    entries = _listed(names)
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    listed = [m["name"] for m in bench["per_layer"]]
    first = listed.index(f"{names[0]}.tput")  # (later PRs append behind them)
    assert listed[first:first + 7] == [f"{n}.tput" for n in names]
    layers = {n: entries[f"{n}.tput"]["layer"] for n in names}
    edge = entries["outside_batcher_ms.tput"]["layer"]
    batching = entries["queue_wait_ms.tput"]["layer"]
    generator = "load generator (bench/pwbench/loadgen.py)"
    assert layers == {
        "edge_inbound_ms": edge, "retrieve_wait_ms": edge,
        "edge_outbound_ms": edge, "client_side_ms": generator,
        "tokenize_ms": batching, "program_threads_cpu_pct": batching,
        "harness_threads_cpu_pct": generator,
    }
    for n in names:
        assert entries[f"{n}.tput"]["workloads"] == cells
        assert entries[f"{n}.tput"]["unit"] == ("%" if n.endswith("pct") else "ms")


def test_benchmark_lists_the_four_metrics_for_the_backlog_cell():
    """Only the top file: ``bench/rehearsal/BENCHMARK.json`` is an accepted
    benchmark file, so the tiny preset gains the entries in a ``benchmark``
    PR (PERF.md section 7)."""
    _listed(("host_per_dispatch_ms", "host_stall_pct", "queue_wait_ms",
             "outside_batcher_ms"))


def test_benchmark_lists_prefill_pad_pct_in_the_batchers_layer():
    entries = _listed(("prefill_pad_pct",))
    assert entries["prefill_pad_pct.tput"]["layer"] == (
        entries["slot_occupancy_pct.tput"]["layer"]
    )


def test_prefill_pad_pct_reads_the_batcher_it_was_written_for():
    """The reader over a real batcher's counters, as the harness takes
    them: the difference of two copies of `stats`."""
    cb = _chat()._cb
    before = dict(cb.stats)
    _run(cb)
    grown = {k: v - before[k] for k, v in cb.stats.items() if v != before[k]}
    tokens = sum(len(cb.tokenizer.tokenize(p)) for p in PROMPTS)
    assert grown["prompt_tokens"] == tokens
    assert grown["padded_tokens"] == 16 * len(PROMPTS)
    assert _reader("prefill_pad_pct")(_ctx(grown)) == pytest.approx(
        100.0 * (1 - tokens / (16 * len(PROMPTS)))
    )
