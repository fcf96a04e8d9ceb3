"""Continuous batching for LLM decode (serving/continuous_batching.py).

Pins the acceptance contract of the slot scheduler:

  * a request admitted MID-GENERATION joins the in-flight decode batch at
    a step boundary — the device-plane compile ledger shows zero new XLA
    compilations for the join, and the slot counters (pool + metrics
    registry) prove the freed-slot re-fill happened;
  * the scheduler is a function of `temperature` alone, and the slot
    path's per-row math equals the scanned `generate_serving` path BYTE
    for byte — `chat._generate_batch` is the oracle on any chat;
  * slot-pool bookkeeping: acquire/release, refill + joined-in-flight
    counters, exhaustion, namespace cleanup.
"""

from __future__ import annotations

import time as _time

import pytest

from pathway_tpu.engine.device_plane import DevicePlane, SlotPool
from pathway_tpu.internals import observability as obs
from pathway_tpu.models import lm_config
from pathway_tpu.serving.continuous_batching import _AHEAD


@pytest.fixture(autouse=True)
def _plane_off():
    yield
    obs.disable()


TINY = dict(
    vocab_size=256, d_model=16, n_heads=2, n_layers=1, d_ff=32, max_len=64
)


def _chat(**kw):
    from pathway_tpu.xpacks.llm.llms import JaxLMChat

    kw.setdefault("config", lm_config(**TINY))
    kw.setdefault("max_new_tokens", 4)
    return JaxLMChat(**kw)


# ------------------------------------------------------------ slot pool


def test_slot_pool_acquire_release_and_counters():
    pool = SlotPool("t", 2)
    a = pool.acquire()
    b = pool.acquire()
    assert {a, b} == {0, 1}
    assert pool.acquire() is None  # exhausted: request stays queued
    assert pool.joined_inflight == 1  # b acquired while a was in flight
    assert pool.refills == 0
    pool.release(a)
    c = pool.acquire()
    assert c == a
    assert pool.refills == 1  # a freed row re-filled
    assert pool.joined_inflight == 2
    assert pool.high_water == 2
    with pytest.raises(ValueError):
        pool.release(b)
        pool.release(b)  # double release fails loudly


def test_plane_slot_pool_registry_and_namespace_drop():
    plane = DevicePlane()
    pool = plane.slot_pool("cb#1/slots", 4)
    assert plane.slot_pool("cb#1/slots", 4) is pool
    with pytest.raises(ValueError):
        plane.slot_pool("cb#1/slots", 8)  # size conflict fails loudly
    plane.program("cb#1/prefill", lambda x: x)
    plane.program("cb#10/prefill", lambda x: x)  # prefix sibling
    plane.restore(("cb_kv_cache", "cb#1", 4), {"k": 0})
    plane.drop_namespace("cb#1")
    assert "cb#1/prefill" not in plane.programs
    assert "cb#10/prefill" in plane.programs  # delimiter-aware match
    assert "cb#1/slots" not in plane._slot_pools
    assert not any(
        isinstance(k, tuple) and "cb#1" in k for k in plane._leases
    )


# ------------------------------------------- one selector: the temperature


def test_continuous_batching_matches_wave_aligned_byte_identically():
    """The central equivalence: the slot scheduler's output equals the
    wave-aligned generate dispatch byte for byte, per request."""
    chat = _chat(decode_slots=4)
    prompts = ["a b c", "d", "hello world longer prompt", "x y", "q", "z z z"]
    futs = [chat._cb.submit(p) for p in prompts]
    got_cb = [f.result(timeout=60) for f in futs]
    assert got_cb == chat._generate_batch(prompts)
    chat._cb.drain()


def _lm_generate_programs(plane) -> set:
    return {n for n in plane.programs if n.startswith("lm_generate")}


def test_greedy_chat_makes_the_oracle_program_on_first_use_only():
    """At temperature 0 only the batcher is built: no `lm_generate`
    program and no coalescer until `_generate_batch` is called, and the
    finalizer then drops the batcher's namespace and that program."""
    plane = _chat()._plane
    before = _lm_generate_programs(plane)
    chat = _chat(decode_slots=2)
    assert chat._cb is not None and chat._batcher is None
    assert chat._gen is None and _lm_generate_programs(plane) == before
    chat._cb.submit("a b").result(timeout=60)  # served without it, too
    chat._cb.drain()
    assert chat._gen is None and _lm_generate_programs(plane) == before
    chat._generate_batch(["a b"])
    name, cb_name = chat._gen.name, chat._cb.name
    assert _lm_generate_programs(plane) == before | {name}
    assert chat._generate_batch(["c"]) and chat._gen.name == name  # made once
    assert any(isinstance(k, tuple) and name in k for k in plane._leases)
    chat._finalizer()  # what gc runs when the instance dies
    assert name not in plane.programs
    assert f"{cb_name}/step" not in plane.programs
    assert f"{cb_name}/slots" not in plane._slot_pools
    assert not any(
        isinstance(k, tuple) and (name in k or cb_name in k)
        for k in plane._leases
    )


def test_sampled_generation_keeps_wave_aligned_path():
    plane = _chat()._plane
    programs, pools = set(plane.programs), set(plane._slot_pools)
    chat = _chat(temperature=0.7)
    assert chat._cb is None  # per-request rng in a shared step: future work
    assert chat._batcher is not None  # the coalescer is its scheduler
    # no batcher, so none of its programs and no slot pool
    assert set(plane.programs) == programs
    assert set(plane._slot_pools) == pools
    out = chat._generate_batch(["a b c", "d"])
    assert [len(o.split()) for o in out] == [4, 4]
    assert set(plane._slot_pools) == pools
    assert set(plane.programs) - programs == {chat._gen.name}


# ------------------------------------------- mid-generation join acceptance


def test_mid_generation_join_refills_slot_without_new_compile():
    """A request admitted while another is mid-generation joins the
    in-flight decode batch: the compile ledger gains NOTHING (the step
    program and the prompt bucket are warm) and the slot counters — on
    the pool and in the metrics registry — record the join/re-fill."""
    obs.enable()
    chat = _chat(max_new_tokens=24, decode_slots=2)
    cb = chat._cb
    assert cb is not None
    # warm both programs and the prompt bucket with one full generation
    cb.submit("warm up prompt").result(timeout=60)
    cb.drain()
    warmed = (dict(cb._step.compile_counts), dict(cb._prefill.compile_counts))
    pool_before = cb.pool.snapshot()

    first = cb.submit("first long running request")
    # wait until the first request is provably mid-generation
    deadline = _time.monotonic() + 30
    while cb.stats["decode_steps"] < 3 and _time.monotonic() < deadline:
        _time.sleep(0.005)
    assert cb.stats["decode_steps"] >= 3, "first request never started decoding"
    second = cb.submit("second joins the flight")
    r1 = first.result(timeout=60)
    r2 = second.result(timeout=60)
    cb.drain()
    # outputs still equal the wave-aligned path (no cross-slot bleed)
    assert [r1, r2] == chat._generate_batch(
        ["first long running request", "second joins the flight"]
    )
    # zero new compiles for the join
    after = (dict(cb._step.compile_counts), dict(cb._prefill.compile_counts))
    assert after == warmed, f"join recompiled: {warmed} -> {after}"
    # slot counters prove the join: pool-side and registry-side
    pool_after = cb.pool.snapshot()
    assert pool_after["joined_inflight"] > pool_before["joined_inflight"]
    assert pool_after["refills"] > pool_before["refills"]
    plane = obs.PLANE
    assert plane is not None
    assert plane.metrics.counter_value(
        "pathway_serving_joined_inflight_total", {"pool": cb.pool.name}
    ) >= 1
    assert plane.metrics.counter_value(
        "pathway_serving_slot_refills_total", {"pool": cb.pool.name}
    ) >= 1
    assert plane.metrics.counter_value(
        "pathway_serving_decode_steps_total", {"pool": cb.pool.name}
    ) >= 23


def test_queue_overflow_waits_for_free_slot():
    """More requests than slots: the excess queues and lands in freed
    slots (refills), every result still byte-equal to wave-aligned."""
    chat = _chat(decode_slots=2)
    cb = chat._cb
    prompts = [f"prompt number {i}" for i in range(7)]
    futs = [cb.submit(p) for p in prompts]
    got = [f.result(timeout=120) for f in futs]
    cb.drain()
    assert got == chat._generate_batch(prompts)
    snap = cb.pool.snapshot()
    assert snap["refills"] >= 5  # 7 requests over 2 slots
    assert snap["active"] == 0  # fully drained


def test_chat_finalizer_releases_cb_namespace():
    chat = _chat(decode_slots=2)
    cb = chat._cb
    cb.submit("a b").result(timeout=60)
    cb.drain()
    plane = chat._plane
    name = cb.name
    assert f"{name}/prefill" in plane.programs
    assert f"{name}/step" in plane.programs
    assert f"{name}/slots" in plane._slot_pools
    assert any(isinstance(k, tuple) and name in k for k in plane._leases)
    chat._finalizer()  # what gc runs when the instance dies
    assert f"{name}/prefill" not in plane.programs
    assert f"{name}/step" not in plane.programs
    assert f"{name}/slots" not in plane._slot_pools
    assert not any(isinstance(k, tuple) and name in k for k in plane._leases)


def test_cb_chat_through_a_pipeline():
    """JaxLMChat rides the UDF machinery with continuous batching on:
    a table of questions answers identically to the wave-aligned oracle."""
    import pathway_tpu as pw
    from pathway_tpu.xpacks.llm.llms import prompt_chat_single_qa

    chat = _chat(decode_slots=2)
    questions = ["what is a", "what is b", "what is c"]
    t = pw.debug.table_from_rows(
        pw.schema_from_types(q=str), [(q,) for q in questions]
    )
    r = t.select(
        q=pw.this.q, a=chat(pw.apply(prompt_chat_single_qa, pw.this.q))
    )
    rows = {}
    pw.io.subscribe(
        r,
        on_change=lambda key, row, time, is_addition: rows.__setitem__(
            row["q"], row["a"]
        ),
    )
    pw.run()
    pw.internals.parse_graph.G.clear()
    assert chat._cb.stats["completed"] == len(questions)  # the batcher served
    # a single-turn message's prompt is its content: the question itself
    assert [rows[q] for q in questions] == chat._generate_batch(questions)


# ------------------------------------------------- the prompt-length ladder

# wide enough in positions for a rung above 512, tiny in everything else
LONG = dict(
    vocab_size=256, d_model=16, n_heads=2, n_layers=1, d_ff=32, max_len=1100
)


def _cb_chat(**kw):
    return _chat(decode_slots=2, **kw)


def _words(n_tokens: int, salt: str = "w") -> str:
    """A prompt the hash tokenizer turns into `n_tokens` ids (one leads)."""
    return " ".join(f"{salt}{j}" for j in range(n_tokens - 1))


def test_long_prompt_runs_at_its_rung_and_matches_the_cap_width(monkeypatch):
    """A 600-token prompt runs at width 640, not at the cap's 1,096, and
    gives the tokens the cap's width gives: the left padding and
    `pad_len` are carried through the prefill and every step."""
    from pathway_tpu.engine import device_plane

    prompt = _words(600)
    widths = []

    def run():
        cb = _cb_chat(config=lm_config(**LONG))._cb
        admit = cb._admit

        def logged(req, slot, cache):
            out = admit(req, slot, cache)
            widths.append((req.length, req.width, req.pad_len))
            return out

        cb._admit = logged
        got = cb.submit(prompt).result(timeout=120)
        cb.drain()
        return got, cb

    at_rung, cb = run()
    assert widths == [(600, 640, 40)]
    assert cb._prefill.compile_counts == {(1, 640): 1}
    assert cb.stats["prompt_tokens"] == 600
    assert cb.stats["padded_tokens"] == 640
    monkeypatch.setattr(device_plane, "bucket_len", lambda longest, cap: cap)
    at_cap, cb = run()
    assert widths[1:] == [(600, 1096, 496)]
    assert cb._prefill.compile_counts == {(1, 1096): 1}
    assert at_rung == at_cap and len(at_rung.split()) == 4


def test_prompts_inside_one_rung_cost_one_compile():
    cb = _cb_chat(config=lm_config(**LONG))._cb
    lengths = [513, 600, 640, 577]
    futs = [cb.submit(_words(n, salt=f"p{n}x")) for n in lengths]
    assert all(len(f.result(timeout=120).split()) == 4 for f in futs)
    cb.drain()
    assert cb._prefill.compile_counts == {(1, 640): 1}
    assert cb._step.compile_counts == {2: 1}
    assert cb.stats["prompt_tokens"] == sum(lengths)
    assert cb.stats["padded_tokens"] == 640 * len(lengths)
    # the next rung is a program of its own, loaded when first asked for
    cb.submit(_words(641)).result(timeout=120)
    cb.drain()
    assert cb._prefill.compile_counts == {(1, 640): 1, (1, 768): 1}


# ------------------------------------- the step program at construction


def test_construction_loads_the_step_program_and_counts_no_step():
    chat = _cb_chat()
    cb = chat._cb
    cb.drain()  # the construction's own pass of the thread
    assert not cb._thread.is_alive() and not cb._running
    assert cb._step.compile_counts == {2: 1}
    assert cb._prefill.compile_counts == {}  # no rung nobody asked for
    assert cb.stats["preload_s"] > 0
    assert all(v == 0 for k, v in cb.stats.items() if k != "preload_s")
    assert cb.pool.snapshot()["active"] == 0
    # the lease is back: one slot cache, which the first request takes
    assert len(chat._plane._leases[cb._cache_key]) == 1
    preload_s = cb.stats["preload_s"]
    assert cb.submit("a b c").result(timeout=60)
    cb.drain()
    assert cb._step.compile_counts == {2: 1}  # the step was loaded already
    assert cb.stats["preload_s"] == preload_s
    assert cb.stats["decode_steps"] == 3 and cb.stats["loop_s"] > 0
    assert len(chat._plane._leases[cb._cache_key]) == 1


def test_submit_racing_the_construction_is_answered_by_the_same_thread():
    """No second thread and no second lease, wherever the construction's
    pass stands when the request comes in."""
    wa = _chat()._generate_batch(["a b c"])
    for _ in range(4):
        chat = _cb_chat()
        cb = chat._cb
        first = cb._thread
        fut = cb.submit("a b c")
        assert [fut.result(timeout=60)] == wa
        cb.drain()
        if cb._thread is first:
            break
    assert cb._thread is first, "the construction's pass never saw a submit"
    assert cb._step.compile_counts == {2: 1}
    assert len(chat._plane._leases[cb._cache_key]) == 1
    assert cb.stats["preload_s"] > 0 and cb.stats["decode_steps"] == 3


def test_second_thread_waits_for_the_lease_of_the_first(monkeypatch):
    """A thread started while its predecessor still hands the cache back
    joins it first: one slot cache on the device, never two."""
    import threading

    chat = _cb_chat()
    cb = chat._cb
    cb.drain()
    made = []
    init = cb._init_cache
    cb._init_cache = lambda: made.append(1) or init()
    plane = chat._plane
    restore = plane.restore
    at_gate, gate = threading.Event(), threading.Event()

    def held(key, buf):
        if key == cb._cache_key:
            at_gate.set()
            assert gate.wait(30)
        restore(key, buf)

    monkeypatch.setattr(plane, "restore", held)
    assert cb.submit("a b c").result(timeout=60)
    assert at_gate.wait(30)  # the first thread is out of its loop
    assert not cb._running
    fut = cb.submit("d e f")  # starts the second
    _time.sleep(0.2)
    assert made == [] and not fut.done()
    gate.set()
    assert fut.result(timeout=60)
    cb.drain()
    assert made == []
    assert len(plane._leases[cb._cache_key]) == 1


def test_failed_step_load_is_logged_and_fails_the_first_request(monkeypatch):
    """A first compile that fails at construction follows the plane's
    rule: it is written to the global error log, the batcher keeps no
    state of it, and the first request's step is a first compile again,
    which fails that request's future."""
    from pathway_tpu.internals.errors import global_error_log
    from pathway_tpu.models import transformer

    real, broken = transformer.decode_step_slots, [True]

    def refused(*a, **kw):
        if broken[0]:
            raise ValueError("step does not fit")
        return real(*a, **kw)

    monkeypatch.setattr(transformer, "decode_step_slots", refused)
    log = global_error_log().entries
    before = len(log)
    chat = _cb_chat()
    cb = chat._cb
    cb.drain()
    assert not cb._running and cb.stats["preload_s"] == 0
    assert cb._step.compile_counts == {} and cb._step.host_fallbacks == 0
    assert "first compile failed" in log[before] and cb._step.name in log[before]
    assert len(chat._plane._leases[cb._cache_key]) == 1  # the lease is back
    fut = cb.submit("a b c")
    with pytest.raises(ValueError, match="step does not fit"):
        fut.result(timeout=60)
    cb.drain()
    assert cb.stats["submitted"] == 1 and cb.stats["completed"] == 0
    assert cb.pool.snapshot()["active"] == 0
    assert len(chat._plane._leases[cb._cache_key]) == 1
    # the program repaired, the same batcher serves: nothing was latched
    broken[0] = False
    assert cb.submit("a b c").result(timeout=60)
    cb.drain()
    assert cb._step.compile_counts == {2: 1}


# --------------------------------------------- one prefill a step boundary


def _held_back(cb, prompts):
    """Queue every prompt before the thread looks: what the loop does with
    a standing queue is then the same in every run."""
    with cb._lock:
        cb._running = True
    futs = [cb.submit(p) for p in prompts]
    with cb._lock:
        cb._start_thread()
    return futs


def _logged(cb):
    """The loop's programs in the order the host handled them: `P<slot>`
    and `S` where one is dispatched, `p` and `s` where its result is read,
    and for each request finished the number of steps read by then."""
    events: list[str] = []
    done_at: list[int] = []
    admit, step, read, finish = cb._admit, cb._step, cb._read, cb._finish

    def admit_logged(req, slot, cache):
        events.append(f"P{slot}")
        return admit(req, slot, cache)

    def step_logged(*a, **kw):
        events.append("S")
        return step(*a, **kw)

    def read_logged(done):
        events.append("p" if done.batch is None else "s")
        return read(done)

    def finish_logged(slot, req):
        done_at.append(events.count("s"))
        return finish(slot, req)

    cb._admit, cb._step = admit_logged, step_logged
    cb._read, cb._finish = read_logged, finish_logged
    return events, done_at


@pytest.mark.parametrize("slots,requests", [(2, 2), (3, 3), (2, 5), (4, 3)])
def test_a_boundary_runs_one_prefill_and_slots_finish_a_step_apart(
    slots, requests
):
    """A queue behind several free slots is admitted prefill, step,
    prefill, step: no decoding answer waits for two prefills in a row,
    slots filled from one queue finish at different steps, and the tokens
    are those of the wave-aligned path."""
    new = 6
    chat = _chat(decode_slots=slots, max_new_tokens=new)
    cb = chat._cb
    cb.drain()  # the construction's own pass
    events, done_at = _logged(cb)
    prompts = [f"question number {i} of the queue" for i in range(requests)]
    got = [f.result(timeout=120) for f in _held_back(cb, prompts)]
    cb.drain()
    assert got == chat._generate_batch(prompts)
    seq = "".join(e[0] for e in events if e[0] in "PS")
    assert seq.count("P") == requests and "PP" not in seq, seq
    first = min(slots, requests)
    assert seq.startswith("PS" * first), seq
    # no two answers of one filling finish at the same step
    assert len(set(done_at)) == requests, (done_at, events)
    if requests <= slots:
        # the last of a burst into an idle pool pays k - 1 steps more
        assert cb.stats["decode_steps"] == (new - 1) + (requests - 1)


# ------------------------------------------ programs dispatched ahead of the read


def _replayed(events):
    """Replays a `_logged` order: the programs out (oldest first, "P" or
    "S") just before each dispatch and just before each read, as strings,
    and whether a dispatch came after that read. Asserts that results are
    read in the order their programs went out."""
    out, at_dispatch, at_read = "", [], []
    for e in events:
        if e[0] in "PS":
            at_dispatch.append(out)
            out += e[0]
        else:
            assert out[:1] == e.upper(), events
            at_read.append(out)
            out = out[1:]
    n_sent = [i for i, e in enumerate(events) if e[0] in "PS"]
    last_sent = n_sent[-1] if n_sent else -1
    reads = [i for i, e in enumerate(events) if e in "ps"]
    dispatch_after = [i < last_sent for i in reads]
    assert out == "", events  # everything out is read
    return at_dispatch, at_read, dispatch_after


def _assert_the_rule(events):
    """The read rule, both ways. Before every dispatch the programs out are
    at most `_AHEAD`, or a prefill with only steps behind it; a read with
    more dispatches after it is of a step with more than `_AHEAD` out, or
    of a prefill with more than `_AHEAD` out and a later prefill among
    them. Returns the programs out before each dispatch."""
    at_dispatch, at_read, dispatch_after = _replayed(events)
    for out in at_dispatch:
        assert len(out) <= _AHEAD or (
            out[0] == "P" and "P" not in out[1:]
        ), (out, events)
    for out, more in zip(at_read, dispatch_after):
        if more:
            assert len(out) > _AHEAD, (out, events)
            assert out[0] == "S" or "P" in out[1:], (out, events)
    return at_dispatch


def _read_at_depth(cb):
    """The reader that waits for any oldest program, a prefill too, once
    more than `_AHEAD` are out: what the rule departs from."""

    def read_behind(keep=_AHEAD):
        while len(cb._out) > keep:
            cb._read(cb._out.popleft())

    cb._read_behind = read_behind


def test_programs_are_dispatched_ahead_of_the_read():
    """Three requests of five tokens over two slots, the whole order: a
    step is read after the next two programs went out; the first prefill
    is read at the second's dispatch, the second at the third's, and the
    third, with only steps behind it and nothing queued, when nothing is
    left to dispatch; the slot whose last step is out takes the next
    prefill at that very boundary, before that step is read; and only
    then does the thread leave."""
    chat = _chat(decode_slots=2, max_new_tokens=5)
    cb = chat._cb
    cb.drain()
    events, done_at = _logged(cb)
    prompts = ["first of three", "second", "the third one"]
    got = [f.result(timeout=120) for f in _held_back(cb, prompts)]
    cb.drain()
    assert got == chat._generate_batch(prompts)
    assert " ".join(events) == (
        "P0 S P1 p S s S S P0 p s s S s S S S p s s s s"
    )
    _assert_the_rule(events)
    assert done_at == [4, 5, 8]
    s = cb.stats
    assert (s["prefills"], s["decode_steps"], s["completed"]) == (3, 8, 3)
    assert 0 <= s["dispatched_ahead"] <= 10  # all but the first may be
    assert not cb._out and not cb._leaving | set(cb._active)
    assert cb.pool.snapshot()["active"] == 0 and cb.queue_depth() == 0


@pytest.mark.parametrize("new", [1, 2, 5])
@pytest.mark.parametrize("slots", [1, 2])
def test_reading_late_keeps_the_tokens_at_the_edges(new, slots):
    """A queue deeper than the pool, and the edges of reading late: an
    answer that is its prefill's token alone (no step is ever dispatched,
    and its slot is free as soon as the prefill is out), and one whose
    only step is its last."""
    chat = _chat(decode_slots=slots, max_new_tokens=new)
    cb = chat._cb
    cb.drain()
    events, _ = _logged(cb)
    prompts = [f"prompt {i} of a queue deeper than the pool" for i in range(5)]
    got = [f.result(timeout=120) for f in _held_back(cb, prompts)]
    cb.drain()
    assert got == chat._generate_batch(prompts)
    assert all(len(g.split()) == new for g in got)
    if new == 1:
        # prefills alone, each ahead of the reads, read `_AHEAD` late
        assert "".join(e[0] for e in events) == "PPPpPpPppp"
        assert cb.stats["decode_steps"] == 0
    # results are read in the order their programs went out (`_replayed`),
    # none before `_AHEAD` more dispatches (the last ones have none behind
    # them), and a prefill with only steps behind it not until a later
    # prefill is out or nothing is left to dispatch
    sent_at = [i for i, e in enumerate(events) if e[0] in "PS"]
    read_at = [i for i, e in enumerate(events) if e in "ps"]
    assert all(n < read for n, read in zip(sent_at[_AHEAD:], read_at)), events
    _assert_the_rule(events)
    assert cb.stats["completed"] == cb.stats["prefills"] == 5
    assert cb.pool.snapshot()["active"] == 0 and cb.queue_depth() == 0


@pytest.mark.parametrize("requests,new", [(4, 4), (5, 6), (6, 3)])
def test_a_prefill_is_read_once_a_later_prefill_is_out(requests, new):
    """A backlog over two slots: each prefill but the last is read only
    after the next prefill went out behind it, the last when nothing is
    left to dispatch, and never are more than two prefills out at once."""
    chat = _chat(decode_slots=2, max_new_tokens=new)
    cb = chat._cb
    cb.drain()
    events, _ = _logged(cb)
    prompts = [f"backlog prompt {i} over two slots" for i in range(requests)]
    got = [f.result(timeout=120) for f in _held_back(cb, prompts)]
    cb.drain()
    assert got == chat._generate_batch(prompts)
    _assert_the_rule(events)
    sent = [i for i, e in enumerate(events) if e[0] == "P"]
    read = [i for i, e in enumerate(events) if e == "p"]
    assert len(sent) == len(read) == requests
    for k in range(requests - 1):
        assert sent[k + 1] < read[k], events
    last_dispatch = max(i for i, e in enumerate(events) if e[0] in "PS")
    assert read[-1] > last_dispatch, events
    out = most = 0
    for e in events:
        out += (e[0] == "P") - (e == "p")
        most = max(most, out)
    assert most == 2, events


@pytest.mark.parametrize("requests", [1, 2])
def test_with_no_queue_the_steps_behind_a_prefill_stop_at_the_last_steps(
    requests,
):
    """Every request holds a slot and none waits: the loop dispatches the
    steps behind the unread prefill up to the requests' last steps, no
    further, and reads everything out before the thread leaves."""
    new = 6
    chat = _chat(decode_slots=2, max_new_tokens=new)
    cb = chat._cb
    cb.drain()
    events, _ = _logged(cb)
    running = []
    read = cb._read

    def read_running(done):
        running.append(cb._running)
        return read(done)

    cb._read = read_running
    prompts = [f"no queue behind prompt {i}" for i in range(requests)]
    got = [f.result(timeout=120) for f in _held_back(cb, prompts)]
    cb.drain()
    assert got == chat._generate_batch(prompts)
    assert " ".join(events) == {
        1: "P0 S S S S S p s s s s s",
        2: "P0 S P1 p S s S S S S p s s s s s",
    }[requests]
    at_dispatch = _assert_the_rule(events)
    # the last request's n_steps - 1 steps, and its prefill
    assert max(map(len, at_dispatch)) + 1 == new
    # each prefill, and the steps: the last request's after its neighbour's
    assert len(running) == requests + (new - 1) + (requests - 1)
    assert all(running)  # every read before the thread gave up the loop
    assert not cb._thread.is_alive() and not cb._out
    assert cb.stats["completed"] == requests


@pytest.mark.parametrize(
    "slots,requests,new,past", [(2, 3, 5, 4), (1, 5, 5, 14), (2, 1, 6, 3),
                                (2, 5, 1, 0)],
)
def test_dispatched_past_prefill_counts_where_the_old_depth_would_wait(
    slots, requests, new, past
):
    """The counter is the dispatches made with more than `_AHEAD` programs
    out behind an unread prefill: the reader that waits at that depth for
    a prefill too makes the same dispatches in the same order with the
    same tokens, and never one of those."""
    prompts = [f"counted prompt {i}" for i in range(requests)]
    orders, counts = [], []
    for depth in (False, True):
        chat = _chat(decode_slots=slots, max_new_tokens=new)
        cb = chat._cb
        cb.drain()
        if depth:
            _read_at_depth(cb)
        events, _ = _logged(cb)
        got = [f.result(timeout=120) for f in _held_back(cb, prompts)]
        cb.drain()
        assert got == chat._generate_batch(prompts)
        at_dispatch = _replayed(events)[0]
        past_here = [
            out for out in at_dispatch if len(out) > _AHEAD and out[0] == "P"
        ]
        assert cb.stats["dispatched_past_prefill"] == len(past_here)
        orders.append([e for e in events if e[0] in "PS"])
        counts.append(len(past_here))
    assert orders[0] == orders[1]
    assert counts == [past, 0]


def test_queue_depth_counts_a_request_until_its_reply_leaves():
    """The slot goes back when the last step is dispatched; the request is
    still the batcher's until that step is read."""
    chat = _chat(decode_slots=1, max_new_tokens=2)
    cb = chat._cb
    cb.drain()
    seen = []
    read = cb._read

    def read_logged(done):
        seen.append(
            (cb.queue_depth(), cb.pool.snapshot()["active"], len(cb._leaving))
        )
        return read(done)

    cb._read = read_logged
    assert cb.submit("a b c").result(timeout=60)
    cb.drain()
    # the prefill is read with the only step out and nothing more to
    # dispatch: no slot held, one request leaving; then that step, the same
    assert seen == [(1, 0, 1), (1, 0, 1)]
    assert cb.queue_depth() == 0


@pytest.mark.parametrize("fails_at", [1, 2, 3])
def test_a_read_that_raises_fails_every_future_and_returns_every_slot(fails_at):
    """The read of a prefill (1, 3) or of a step (2) raises: every request
    fails with it — queued, holding a slot, or with its last step out and
    not read — every slot is back, the lease is back, and the same batcher
    serves again."""
    chat = _chat(decode_slots=2, max_new_tokens=3)
    cb = chat._cb
    cb.drain()
    read, reads = cb._read, []

    def read_failing(done):
        reads.append(done)
        if len(reads) == fails_at:
            raise RuntimeError("result lost")
        return read(done)

    cb._read = read_failing
    futs = _held_back(cb, [f"request {i}" for i in range(4)])
    for fut in futs:
        with pytest.raises(RuntimeError, match="result lost"):
            fut.result(timeout=60)
    cb.drain()
    assert len(reads) == fails_at
    assert cb.pool.snapshot()["active"] == 0 and cb.queue_depth() == 0
    assert not cb._out and not cb._leaving | set(cb._active)
    assert cb.stats["completed"] == 0
    assert len(chat._plane._leases[cb._cache_key]) == 1
    cb._read = read
    prompts = ["served after the failure", "and a second"]
    got = [f.result(timeout=60) for f in [cb.submit(p) for p in prompts]]
    cb.drain()
    assert got == chat._generate_batch(prompts)


def test_submitters_racing_the_loop_lose_no_request():
    """More submitting threads than cores and a short switch interval: the
    loop takes from the queue, frees slots at a dispatch and resolves at
    a read while they add to it; every request is answered with the
    oracle's tokens and nothing is left held."""
    import sys
    import threading

    chat = _chat(decode_slots=2, max_new_tokens=3)
    cb = chat._cb
    prompts = [f"racing prompt {i}" for i in range(6)]
    want = dict(zip(prompts, chat._generate_batch(prompts)))
    got: list = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def client(k):
            for p in prompts[k % 3:] + prompts[:k % 3]:
                got.append((p, cb.submit(p).result(timeout=120)))

        threads = [threading.Thread(target=client, args=(k,)) for k in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    cb.drain()
    assert len(got) == 12 * len(prompts)
    assert all(out == want[p] for p, out in got)
    assert cb.stats["completed"] == cb.stats["prefills"] == len(got)
    assert cb.pool.snapshot()["active"] == 0 and cb.queue_depth() == 0
    assert not cb._out and not cb._leaving | set(cb._active)
