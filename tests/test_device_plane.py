"""Device serving plane (engine/device_plane.py).

Pins the four pillars of the dispatch subsystem:

  * shape-bucketed coalescing: ragged live batches pad to buckets (rows
    to a power of two, sequences to the rungs of one ladder), so the jit cache (and the per-bucket compile ledger) sees a
    bounded set of shapes — the CPU-runnable no-recompile guard;
  * padding hygiene: padded rows never leak into results;
  * donated persistent buffers: the decoder KV cache and the KNN slab
    mirror ride lease/restore cycles instead of per-call allocation;
  * frontier stage overlap: a slow generate wave defers off the pump, so
    embed of later waves proceeds — the pipelined RAG steady state.
"""

from __future__ import annotations

import asyncio
import time as _time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine.device_plane import (
    BucketPolicy,
    DeviceProgram,
    DevicePlane,
    WaveCoalescer,
)


# ------------------------------------------------------------- bucketing


def test_rows_bucket_boundaries():
    b = BucketPolicy(min_rows=8, max_rows=4096)
    assert b.rows_bucket(1) == 8
    assert b.rows_bucket(8) == 8
    assert b.rows_bucket(9) == 16  # boundary rounds UP
    assert b.rows_bucket(16) == 16
    assert b.rows_bucket(17) == 32
    assert b.rows_bucket(4096) == 4096
    with pytest.raises(ValueError):
        b.rows_bucket(4097)  # past the cap: split, don't pad


def test_seq_bucket_boundaries():
    b = BucketPolicy()
    assert b.seq_bucket(1, cap=512) == 16
    assert b.seq_bucket(16, cap=512) == 16
    assert b.seq_bucket(17, cap=512) == 32
    assert b.seq_bucket(100, cap=512) == 128
    assert b.seq_bucket(1000, cap=512) == 512  # cap wins


@pytest.mark.parametrize("longest,cap,want", [
    # up to 512: powers of two from min_seq, as ever
    (1, 2016, 16), (16, 2016, 16), (17, 2016, 32), (100, 2016, 128),
    (512, 2016, 512),
    # above 512: four rungs an octave
    (513, 2016, 640), (640, 2016, 640), (641, 2016, 768), (1024, 2016, 1024),
    (1025, 2016, 1280), (1250, 2016, 1280), (1281, 2016, 1536),
    (1793, 4096, 2048), (2049, 4096, 2560), (3585, 8192, 4096),
    # the cap wins: the cell's budget, and a rung is not a cap
    (1793, 2016, 2016), (2016, 2016, 2016), (5000, 2016, 2016),
    (1000, 512, 512), (600, 600, 600),
])
def test_seq_bucket_rungs(longest, cap, want):
    assert BucketPolicy().seq_bucket(longest, cap) == want


def test_seq_bucket_ladder_is_monotone_tight_and_aligned():
    """Over every length up to 9,000: never under the length, never
    falling as the length grows, under a quarter of padding above 512,
    and every rung above 512 a multiple of 128 unless it is the cap."""
    b = BucketPolicy()
    for cap in (2016, 1 << 20):
        last = 0
        for n in range(1, 9001):
            got = b.seq_bucket(n, cap)
            assert got >= last
            last = got
            if got == cap:
                continue
            assert got >= n
            if n > 512:
                assert got % 128 == 0 and got < 1.25 * n
    rungs = sorted({b.seq_bucket(n, 1 << 20) for n in range(513, 4097)})
    assert rungs == [
        640, 768, 896, 1024, 1280, 1536, 1792, 2048, 2560, 3072, 3584, 4096,
    ]


def test_pad_rows_pads_with_zeros_to_bucket():
    plane = DevicePlane()
    m = np.ones((5, 3), np.float32)
    (p,), bucket = plane.pad_rows([m], 5)
    assert bucket == 8 and p.shape == (8, 3)
    assert np.all(p[5:] == 0.0)


# ------------------------------------------------- compile-count guard


def test_ragged_batches_in_one_bucket_compile_once():
    """The tier-1 regression guard: streaming ragged batch sizes across
    one bucket must cost exactly ONE XLA compilation per (bucket,
    program) pair — asserted against both the plane's ledger and the jit
    cache itself."""
    plane = DevicePlane()
    prog = plane.program("guard_double", lambda x: x * 2.0)
    for n in (3, 5, 7, 8):  # all inside the 8-row bucket
        (x,), bucket = plane.pad_rows([np.ones((n, 4), np.float32)], n)
        out = prog(x, bucket=bucket)
        assert out.shape == (8, 4)
    assert prog.compile_counts == {8: 1}
    # crossing the boundary costs exactly one more
    (x,), bucket = plane.pad_rows([np.ones((9, 4), np.float32)], 9)
    prog(x, bucket=bucket)
    assert prog.compile_counts == {8: 1, 16: 1}
    assert prog.total_compiles == 2
    # the ledger is not self-referential: XLA's own cache agrees
    cache = prog.jit_cache_size()
    assert cache is None or cache == prog.total_compiles


def test_embedder_ragged_waves_hit_one_program():
    """End-to-end guard through the flagship encoder: ragged wave sizes
    within a bucket reuse one compiled program."""
    from pathway_tpu.models import embedder_config
    from pathway_tpu.xpacks.llm.embedders import JaxEmbedder

    emb = JaxEmbedder(
        config=embedder_config(
            vocab_size=256, d_model=16, n_heads=2, n_layers=1, d_ff=32,
            max_len=32, embed_dim=16,
        )
    )
    for texts in (["a"], ["a b", "c"], ["d e f"] * 7, ["x"] * 8):
        emb.encode_many(texts)
    assert emb._encode.total_compiles == 1, emb._encode.compile_counts
    emb.encode_many(["y"] * 9)  # next bucket: exactly one more
    assert emb._encode.total_compiles == 2


# ------------------------------------------------------ padding hygiene


def test_padded_rows_never_leak_into_results():
    from pathway_tpu.models import embedder_config
    from pathway_tpu.xpacks.llm.embedders import JaxEmbedder

    emb = JaxEmbedder(
        config=embedder_config(
            vocab_size=256, d_model=16, n_heads=2, n_layers=1, d_ff=32,
            max_len=32, embed_dim=16,
        )
    )
    texts = ["alpha beta", "gamma", "delta epsilon zeta"]
    got = emb.encode_many(texts)  # padded 3 -> 8 rows internally
    assert len(got) == len(texts)
    # row-by-row singleton encodes (different padding) agree: mask-aware
    # pooling keeps pad rows/columns out of every result
    for t, v in zip(texts, got):
        (solo,) = emb.encode_many([t])
        np.testing.assert_allclose(v, solo, atol=1e-5)


def test_coalescer_length_mismatch_fails_rows_not_silently():
    flushed = []

    def bad_flush(items):
        flushed.append(len(items))
        return [1]  # wrong arity: must error every row, not misalign

    co = WaveCoalescer(bad_flush, pool=None)

    async def drive():
        return await asyncio.gather(
            co.submit("a"), co.submit("b"), return_exceptions=True
        )

    res = asyncio.run(drive())
    assert flushed == [2]
    assert all(isinstance(r, RuntimeError) for r in res)


# -------------------------------------------------- donated buffer leases


def test_lease_restore_cycle():
    plane = DevicePlane()
    made = []

    def make():
        made.append(1)
        return {"buf": np.zeros(4)}

    b1 = plane.lease("k", make)
    assert made == [1]
    plane.restore("k", b1)
    b2 = plane.lease("k", make)
    assert b2 is b1 and made == [1]  # reused, not rebuilt
    # while leased the slot is empty: a concurrent lease builds fresh
    b3 = plane.lease("k", make)
    assert b3 is not b1 and made == [1, 1]


def test_chat_kv_cache_is_a_persistent_lease():
    """The decoder's KV cache survives across dispatches (donated buffer
    reuse), and stale contents from an earlier wave never change later
    results."""
    from pathway_tpu.models import lm_config
    from pathway_tpu.xpacks.llm.llms import JaxLMChat

    chat = JaxLMChat(
        config=lm_config(
            vocab_size=256, d_model=16, n_heads=2, n_layers=1, d_ff=32,
            max_len=64,
        ),
        max_new_tokens=4,
    )
    first = chat._generate_batch(["a b c", "d"])
    key = ("lm_kv_cache", chat._gen.name, 8)
    assert chat._plane._leases.get(key)  # restored after the dispatch
    # a longer wave warms the cache with different rows, then the first
    # wave repeats: identical output despite the recycled cache
    chat._generate_batch(["w x y z " * 8, "q", "r", "s", "t"])
    again = chat._generate_batch(["a b c", "d"])
    assert again == first
    assert chat._gen.donate_argnums == (2,)


def test_knn_slab_incremental_update_matches_host():
    """Small deltas scatter into the persistent device mirror (donated
    update program); results stay equal to a ground-truth host scan."""
    from pathway_tpu.internals.keys import key_for_values
    from pathway_tpu.stdlib.indexing.host_indexes import VectorSlabIndex

    rng = np.random.default_rng(0)
    idx = VectorSlabIndex(dimensions=16)
    keys = [key_for_values(i) for i in range(80)]
    for i, k in enumerate(keys):
        idx.add(k, rng.normal(size=16))
    q = rng.normal(size=16)
    first = idx.search(q, k=5)
    assert len(first) == 5
    mirror = idx._device_docs
    assert mirror is not None and int(mirror.shape[0]) == 128
    # delta: a handful of upserts + one delete — same padded bucket, so
    # the mirror must be PATCHED, not re-uploaded
    for i in (3, 7):
        idx.add(keys[i], rng.normal(size=16))
    idx.remove(keys[11])
    got = idx.search(q, k=5)
    assert idx._device_docs is not None
    from pathway_tpu.engine.device_plane import get_device_plane

    counts = get_device_plane().compile_counts()
    assert any(name == "knn_slab_update" for (name, _b) in counts)
    # ground truth from the host scan
    idx_host = VectorSlabIndex(dimensions=16, device=False)
    for slot in range(idx.n_slots):
        if idx.valid[slot]:
            idx_host.add(idx.key_of[slot], idx.vectors[slot])
    want = idx_host.search(q, k=5)
    assert [k for k, _ in got] == [k for k, _ in want]
    np.testing.assert_allclose(
        [d for _, d in got], [d for _, d in want], atol=2e-2
    )


def test_update_quantized_docs_matches_requantize():
    """In-place donated refresh of the quantized KNN shard equals a full
    re-quantization, including idempotent duplicate-index padding."""
    import jax.numpy as jnp

    from pathway_tpu.ops.topk import quantize_docs, update_quantized_docs

    rng = np.random.default_rng(3)
    base = rng.normal(size=(32, 8)).astype(np.float32)
    base /= np.linalg.norm(base, axis=1, keepdims=True)
    fresh = rng.normal(size=(2, 8)).astype(np.float32)
    fresh /= np.linalg.norm(fresh, axis=1, keepdims=True)

    docs = quantize_docs(jnp.asarray(base))
    # pad the 2-row delta to 4 by repeating the first (idx, row) pair
    idx = jnp.asarray([5, 9, 5, 5], jnp.int32)
    rows = jnp.asarray(np.stack([fresh[0], fresh[1], fresh[0], fresh[0]]))
    got = update_quantized_docs(docs, idx, rows)

    want_host = base.copy()
    want_host[5], want_host[9] = fresh[0], fresh[1]
    want = quantize_docs(jnp.asarray(want_host))
    np.testing.assert_array_equal(np.asarray(got.values), np.asarray(want.values))
    np.testing.assert_allclose(
        np.asarray(got.scale), np.asarray(want.scale), rtol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(got.full, np.float32), np.asarray(want.full, np.float32)
    )


# -------------------------------------------------------- stage overlap


def _overlap_pipeline(events):
    @pw.udf(executor=pw.udfs.async_executor())
    async def embed(x: int) -> int:
        await asyncio.sleep(0.02)
        events.append(("embed", x, _time.perf_counter()))
        return x * 10

    @pw.udf(executor=pw.udfs.async_executor())
    async def generate(x: int) -> int:
        await asyncio.sleep(0.25)  # the slow straggler stage
        events.append(("generate", x, _time.perf_counter()))
        return x + 1

    rows = [(i, 2 * (i // 4) + 2, 1) for i in range(16)]  # 4 waves of 4
    t = pw.debug.table_from_rows(
        pw.schema_from_types(v=int), rows, is_stream=True
    )
    return t.select(e=embed(pw.this.v)).select(g=generate(pw.this.e))


def test_slow_generate_does_not_stall_later_embed_waves():
    """The straggler-isolation contract on the serving path (the
    tests/test_frontier.py harness shape, device-stage edition): a slow
    generate of wave t must not dam up embed of waves t+1..t+3, and the
    pipelined total must beat the serial stage sum."""
    events: list = []
    res = _overlap_pipeline(events)
    seen: list = []
    pw.io.subscribe(
        res, on_change=lambda key, row, time, is_addition: seen.append(row["g"])
    )
    t0 = _time.perf_counter()
    pw.run()
    total = _time.perf_counter() - t0
    assert sorted(seen) == sorted(i * 10 + 1 for i in range(16))
    first_gen_done = min(t for (kind, _x, t) in events if kind == "generate")
    late_embeds = [
        x for (kind, x, t) in events
        if kind == "embed" and x >= 4 and t < first_gen_done
    ]
    # embeds of waves 2..4 completed while generate of wave 1 was still
    # decoding — the overlap the serial chain could never show
    assert late_embeds, events
    serial = 4 * (0.02 + 0.25)
    assert total < 0.8 * serial, f"no pipelining: {total:.2f}s vs {serial:.2f}s"


def test_retraction_behind_inflight_wave_stays_consistent():
    """A retraction-only wave arriving while the insertion's device wave
    is still in flight must chain behind it (emissions stay in time
    order), retracting EXACTLY the value the insertion produced — never
    an ERROR placeholder that would leave a phantom row downstream."""

    from pathway_tpu.internals.table import Table

    @pw.udf(executor=pw.udfs.async_executor())
    async def slow(x: int) -> int:
        await asyncio.sleep(0.1)
        return x * 10

    # same KEY for the insert and its retraction (a real upsert stream)
    t = Table.from_rows(
        pw.schema_from_types(v=int), [(7,), (7,), (8,)],
        keys=["a", "a", "b"], times=[2, 4, 6], diffs=[1, -1, 1],
    )
    r = t.select(s=slow(pw.this.v))
    live: dict = {}

    def on_change(key, row, time, is_addition):
        if is_addition:
            live[key] = row["s"]
        else:
            assert live.pop(key) == row["s"]

    pw.io.subscribe(r, on_change=on_change)
    pw.run()
    assert sorted(live.values()) == [80]  # 7 inserted AND cleanly retracted


# ---------------------------------------------------------- batched UDFs


def test_batched_udf_coalesces_whole_wave():
    calls: list[int] = []

    @pw.udf(batched=True)
    def double(xs: list) -> list[int]:
        calls.append(len(xs))
        return [x * 2 for x in xs]

    t = pw.debug.table_from_rows(
        pw.schema_from_types(v=int), [(i,) for i in range(10)]
    )
    r = t.select(d=double(pw.this.v))
    rows: list = []
    pw.io.subscribe(
        r, on_change=lambda key, row, time, is_addition: rows.append(row["d"])
    )
    pw.run()
    assert sorted(rows) == [i * 2 for i in range(10)]
    assert calls == [10], calls  # one device batch for the whole wave


def test_batched_udf_call_sites_with_different_arity_do_not_mix():
    """Two call sites of one batched UDF with different arity must flush
    through separate coalescers — a shared flush would transpose-truncate
    the wider site's columns."""

    @pw.udf(batched=True)
    def combine(xs: list, ys: list | None = None) -> list[int]:
        if ys is None:
            return [x + 1 for x in xs]
        return [x + y for x, y in zip(xs, ys)]

    t = pw.debug.table_from_rows(
        pw.schema_from_types(a=int, b=int), [(1, 10), (2, 20)]
    )
    one = t.select(r=combine(pw.this.a))
    two = t.select(r=combine(pw.this.a, pw.this.b))
    got_one: list = []
    got_two: list = []
    pw.io.subscribe(
        one, on_change=lambda key, row, time, is_addition: got_one.append(row["r"])
    )
    pw.io.subscribe(
        two, on_change=lambda key, row, time, is_addition: got_two.append(row["r"])
    )
    pw.run()
    assert sorted(got_one) == [2, 3]
    assert sorted(got_two) == [11, 22]


def test_batched_udf_rejects_async_and_cache():
    with pytest.raises(ValueError):
        pw.udf(batched=True, cache_strategy=pw.udfs.InMemoryCache())(
            lambda xs: xs
        )

    @pw.udf(batched=True)
    async def bad(xs: list) -> list:
        return xs

    with pytest.raises(ValueError):
        bad(pw.this.v)


def test_deterministic_batched_udf_retraction_recomputes_through_loop():
    """deterministic=True skips the memo, so a retraction in a later wave
    takes the recompute branch — which for a batched UDF (async per-row
    wrapper) must run through the event loop, not emit a bare coroutine
    that would never match the inserted row downstream."""
    from pathway_tpu.internals.table import Table

    @pw.udf(batched=True, deterministic=True)
    def mul(xs: list) -> list[int]:
        return [x * 10 for x in xs]

    t = Table.from_rows(
        pw.schema_from_types(v=int), [(7,), (7,), (8,)],
        keys=["a", "a", "b"], times=[2, 4, 6], diffs=[1, -1, 1],
    )
    r = t.select(s=mul(pw.this.v))
    live: dict = {}

    def on_change(key, row, time, is_addition):
        assert isinstance(row["s"], int), row["s"]
        if is_addition:
            live[key] = row["s"]
        else:
            assert live.pop(key) == row["s"]

    pw.io.subscribe(r, on_change=on_change)
    pw.run()
    assert sorted(live.values()) == [80]  # "a" inserted AND cleanly retracted


def test_drop_program_releases_program_and_leases():
    plane = DevicePlane()
    name = plane.unique_name("lm_generate")
    plane.program(name, lambda x: x)
    plane.restore(("lm_kv_cache", name, 8), {"buf": np.zeros(4)})
    plane.restore("unrelated", {"buf": np.ones(2)})
    plane.drop_program(name)
    assert name not in plane.programs
    assert not any(
        isinstance(k, tuple) and name in k for k in plane._leases
    )
    assert "unrelated" in plane._leases  # other pools untouched


def test_chat_finalizer_drops_its_program_from_the_plane():
    from pathway_tpu.models import lm_config
    from pathway_tpu.xpacks.llm.llms import JaxLMChat

    chat = JaxLMChat(
        config=lm_config(
            vocab_size=256, d_model=16, n_heads=2, n_layers=1, d_ff=32,
            max_len=64,
        ),
        max_new_tokens=4,
    )
    chat._generate_batch(["a b", "c"])
    name = chat._gen.name
    plane = chat._plane
    assert name in plane.programs
    assert any(isinstance(k, tuple) and name in k for k in plane._leases)
    chat._finalizer()  # what gc runs when the instance dies
    assert name not in plane.programs
    assert not any(isinstance(k, tuple) and name in k for k in plane._leases)


# ------------------------------------------------- quarantine lifecycle


def test_quarantine_reset_is_the_generation_boundary_slate_wipe(monkeypatch):
    """A failed dispatch quarantines its bucket (host fallback until the
    cooldown admits a re-probe); reset_quarantine() drops the record so
    a fresh supervisor generation starts back on the device path instead
    of inheriting a dead process's cooldowns."""
    from pathway_tpu.engine import faults

    plane = DevicePlane()
    prog = plane.program("quar_double", lambda x: x * 2)
    # a cooldown long enough that nothing re-probes behind our back
    monkeypatch.setattr(DeviceProgram, "PROBE_BASE_S", 120.0)
    monkeypatch.setattr(DeviceProgram, "PROBE_CAP_S", 120.0)

    x = np.arange(4)
    monkeypatch.setenv("PATHWAY_FAULTS", "device.dispatch.quar_double@1")
    faults.reset()
    try:
        out = prog(x, bucket=4)  # injected dispatch failure
    finally:
        monkeypatch.setenv("PATHWAY_FAULTS", "0")
        faults.reset()
    # degraded, but the answer still arrived via the host path
    np.testing.assert_array_equal(np.asarray(out), x * 2)
    assert prog.quarantine[4]["failures"] == 1
    assert "injected fault" in prog.quarantine[4]["last_error"]
    assert prog.host_fallbacks == 1

    # cooldown still running: the next call is a host fallback too
    np.testing.assert_array_equal(np.asarray(prog(x, bucket=4)), x * 2)
    assert prog.host_fallbacks == 2

    assert prog.reset_quarantine() == 1
    assert prog.quarantine == {}
    # immediately back on the device path: no new fallback, and the
    # compile ledger is charged by the successful dispatch
    np.testing.assert_array_equal(np.asarray(prog(x, bucket=4)), x * 2)
    assert prog.host_fallbacks == 2
    assert prog.compile_counts.get(4) == 1


def test_first_compile_failure_raises_and_reaches_the_error_log():
    """A program the compiler refuses on its first call is a bug in the
    program, not a device fault: the call raises, nothing is served from
    the host or quarantined, the program, bucket and error reach the
    error log, and the next call is a first compile again."""
    from pathway_tpu.internals.errors import global_error_log

    host_calls = []

    def refuses_wide(x):
        if x.shape[0] > 4:  # a trace-time refusal, as a compiler's would be
            raise ValueError("block does not fit")
        host_calls.append(x.shape)
        return x * 2

    plane = DevicePlane()
    prog = plane.program("refuses_wide", refuses_wide)
    log = global_error_log().entries
    before = len(log)
    with pytest.raises(ValueError, match="block does not fit"):
        prog(np.arange(8), bucket=8)
    assert prog.host_fallbacks == 0 and plane.quarantined() == {}
    assert prog.compile_counts == {}
    (entry,) = log[before:]
    assert "refuses_wide" in entry and "bucket 8" in entry
    assert "first compile failed" in entry and "block does not fit" in entry
    # again: loud, not a host success
    with pytest.raises(ValueError, match="block does not fit"):
        prog(np.arange(8), bucket=8)
    assert prog.host_fallbacks == 0 and len(log) == before + 2
    # the other bucket of the same program is untouched
    host_calls.clear()
    np.testing.assert_array_equal(
        np.asarray(prog(np.arange(4), bucket=4)), np.arange(4) * 2
    )
    assert prog.compile_counts == {4: 1} and prog.host_fallbacks == 0
    assert prog.compile_seconds[4] > 0.0


def test_failed_call_that_consumed_its_donation_is_not_rerun_on_host(
    monkeypatch,
):
    """The host re-run needs the arguments; a failed call that already
    deleted a donated buffer re-raises instead of touching it."""
    import jax.numpy as jnp

    monkeypatch.setattr(DeviceProgram, "PROBE_BASE_S", 120.0)
    plane = DevicePlane()
    prog = plane.program(
        "donating", lambda buf, x: (buf + x, x), donate_argnums=(0,)
    )
    buf, x = jnp.zeros(4), jnp.ones(4)
    buf, _ = prog(buf, x, bucket=4)  # compiles: the signature is now seen

    def lose_device(buf, x):
        buf.delete()  # what a donating executable does before it fails
        raise RuntimeError("device lost")

    monkeypatch.setattr(prog, "_jit", lose_device)
    with pytest.raises(RuntimeError, match="device lost"):
        prog(buf, x, bucket=4)
    assert prog.host_fallbacks == 0
    assert ("donating", 4) in plane.quarantined()


def test_plane_wide_quarantine_reset_spans_programs():
    """The supervisor's generation-boundary hook is the module-level
    reset_quarantines(): it sweeps every registered program on the
    shared plane and reports how many records it dropped."""
    import time as _t

    from pathway_tpu.engine.device_plane import (
        get_device_plane,
        reset_quarantines,
    )

    plane = get_device_plane()
    reset_quarantines()  # start from a clean slate
    p1 = plane.program("quar_sweep_a", lambda x: x + 1)
    p2 = plane.program("quar_sweep_b", lambda x: x - 1)
    try:
        far = _t.monotonic() + 999.0
        with p1._lock:
            p1.quarantine["b8"] = {
                "failures": 3, "reopen_at": far, "last_error": "x"
            }
        with p2._lock:
            p2.quarantine["b16"] = {
                "failures": 1, "reopen_at": far, "last_error": "y"
            }
        assert set(plane.quarantined()) >= {
            ("quar_sweep_a", "b8"), ("quar_sweep_b", "b16")
        }
        assert reset_quarantines() == 2
        assert p1.quarantine == {} and p2.quarantine == {}
        # idempotent on a clean slate — and never constructs a plane
        assert reset_quarantines() == 0
    finally:
        plane.drop_program("quar_sweep_a")
        plane.drop_program("quar_sweep_b")
