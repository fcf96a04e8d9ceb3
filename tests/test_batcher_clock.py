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
        "dispatched_ahead",
        "prompt_tokens", "padded_tokens", "kernel_prefills", "kernel_steps",
        "kernel_expert_prefills", "kernel_linear_prefills",
        "kernel_sparse_prefills", "kernel_sparse_steps",
        # an experts decoder's device counters (0 for this block)
        "routed_pairs", "expert_load_max", "experts_touched", "moe_layers_run",
        # and those of a decoder with sparse or linear layers
        "sparse_blocks_read", "sparse_blocks_visible", "linear_tokens",
    }
    clocks = (
        set(PHASES) | {"loop_s", "host_cpu_s", "preload_s"}
        | set(REQUEST_CLOCKS)
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
    return {"counters": {"batcher": batcher}, "records": list(records)}


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
])
def test_reader_worked_numbers_and_empty_divisors(name, batcher, records, want):
    """A divisor of 0 and a program without the counters (the parent
    commit: only the counts) both read None, and never raise."""
    got = _reader(name)(_ctx(batcher, records))
    assert got == (pytest.approx(want) if want is not None else None)


def _listed(names):
    import json

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in names:
        m = entries[f"{name}.tput"]
        assert "rag-cerebras-6b7.backlog" in m["workloads"]
        assert m["moves"] == "answers_per_s"
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    return entries


def test_benchmark_lists_the_four_metrics_for_the_backlog_cell():
    """Only the top file: ``bench/rehearsal/BENCHMARK.json`` is an accepted
    benchmark file, so the tiny preset gains the entries in a ``benchmark``
    PR (PERF.md section 7)."""
    _listed(("host_per_dispatch_ms", "host_stall_pct", "queue_wait_ms",
             "outside_batcher_ms"))


@pytest.mark.parametrize("engages", [True, False])
@pytest.mark.parametrize("stat, predicate", [
    ("kernel_prefills", "prefill_uses_kernel"),
    ("kernel_expert_prefills", "prefill_experts_use_kernel"),
])
def test_kernel_prefills_counts_what_the_predicate_says(
    monkeypatch, engages, stat, predicate
):
    """`kernel_prefills` and `kernel_expert_prefills` are the model module's
    own predicates of the width a prompt ran at, the ones `_prefill` and
    (through `experts_use_kernel`) `_experts` branch on: with one patched
    true (after the programs are traced, so that the CPU still runs them)
    its count equals `prefills`; as it is off the TPU it stays 0."""
    cb = _chat()._cb
    _run(cb, ["warm up prompt"])
    seen = []
    if engages:
        monkeypatch.setattr(
            cb._model, predicate,
            lambda cfg, width: seen.append((cfg, width)) or True,
        )
    before = dict(cb.stats)
    _run(cb)
    grown = cb.stats["prefills"] - before["prefills"]
    assert grown == len(PROMPTS)
    assert cb.stats[stat] == (grown if engages else 0)
    if engages:
        assert seen == [(cb.cfg, 16)] * len(PROMPTS)


@pytest.mark.parametrize("engages", [True, False])
def test_kernel_steps_counts_what_the_predicate_says(monkeypatch, engages):
    """`kernel_steps` is the model module's own predicate, the one
    `_step_rows` branches on: with it patched true (after the step program
    is traced, so that the CPU still runs it) the count equals
    `decode_steps`; as it is off the TPU it stays 0."""
    cb = _chat()._cb
    _run(cb, ["warm up prompt"])
    seen = []
    if engages:
        monkeypatch.setattr(
            cb._model, "step_uses_kernel", lambda cfg: seen.append(cfg) or True
        )
    before = dict(cb.stats)
    _run(cb)
    grown = cb.stats["decode_steps"] - before["decode_steps"]
    assert grown > 0
    assert cb.stats["kernel_steps"] - before["kernel_steps"] == (
        grown if engages else 0
    )
    if engages:
        assert seen == [cb.cfg] * grown


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
