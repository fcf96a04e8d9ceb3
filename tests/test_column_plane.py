"""Sharded NativeBatch column plane: the key-hash shuffle as one device
collective (parallel/column_plane.py + exchange_columns_with_respill),
its host byte-identity, routing parity, overflow respill, and the
mesh.device_wire degradation ladder."""

from __future__ import annotations

import collections
import os
import subprocess
import sys

import numpy as np
import pytest

from pathway_tpu.parallel.exchange import (
    exchange_columns_with_respill,
    exchange_with_respill,
    route128,
)
from pathway_tpu.parallel.mesh import default_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device (virtual) mesh")
    return default_mesh(("data",))


# ------------------------------------------------------------ respill


def test_respill_multi_round_overflow_adversarial_skew():
    """Bucket counts far beyond capacity must ship over >= 3 rounds with
    nothing lost and per-destination global arrival order kept — the
    same-key ordering invariant (a retraction never overtakes its
    insert, even across respill rounds)."""
    mesh = _mesh()
    n_shards = mesh.shape["data"]
    n = 1024
    rng = np.random.default_rng(7)
    ids = np.arange(n, dtype=np.uint32)
    pay = rng.normal(size=(n, 3)).astype(np.float32)
    # adversarial skew: 70% of rows hammer shard 1, rest spread
    dests = np.where(
        rng.random(n) < 0.7, 1, rng.integers(0, n_shards, n)
    ).astype(np.int64)
    cap = 16
    max_bucket = max(
        collections.Counter(
            zip(np.arange(n) * n_shards // n, dests)
        ).values()
    )
    assert -(-max_bucket // cap) >= 3, "fixture must force >= 3 rounds"
    keys, pays, srcs = exchange_with_respill(
        ids, pay, dests, mesh, capacity=cap
    )
    for d in range(n_shards):
        idx = np.nonzero(dests == d)[0]
        assert np.array_equal(srcs[d], idx)  # arrival order, no loss
        assert np.array_equal(pays[d], pay[idx])
        assert np.array_equal(keys[d], ids[idx])


def test_respill_all_to_one_destination():
    mesh = _mesh()
    n = 512
    ids = np.arange(n, dtype=np.uint32)
    pay = np.arange(n, dtype=np.float32)[:, None]
    dests = np.zeros(n, np.int64)
    _k, pays, srcs = exchange_with_respill(ids, pay, dests, mesh, capacity=8)
    assert np.array_equal(srcs[0], np.arange(n))
    assert np.array_equal(pays[0][:, 0], np.arange(n, dtype=np.float32))
    for d in range(1, mesh.shape["data"]):
        assert len(pays[d]) == 0


def test_column_exchange_bit_exact_u64_i64():
    """64-bit columns cross as two u32 lanes and come back bit-exact in
    their input dtypes — including values above 2^63 and negative
    diffs."""
    mesh = _mesh()
    n_shards = mesh.shape["data"]
    rng = np.random.default_rng(3)
    n = 700
    lo = (rng.integers(0, 2**63, n).astype(np.uint64) * 2) + 1
    hi = rng.integers(0, 2**63, n).astype(np.uint64) + (1 << 63)
    tok = rng.integers(0, 1 << 40, n).astype(np.uint64)
    diff = rng.choice([-3, -1, 1, 2], n).astype(np.int64)
    dests = rng.integers(0, n_shards, n).astype(np.int64)
    cols, srcs = exchange_columns_with_respill([lo, hi, tok, diff], dests, mesh)
    for d in range(n_shards):
        idx = np.nonzero(dests == d)[0]
        assert np.array_equal(srcs[d], idx)
        for got, src in zip(cols[d], (lo, hi, tok, diff)):
            assert got.dtype == src.dtype
            assert np.array_equal(got, src[idx])


def test_donated_single_round_engages_for_steady_state_waves(monkeypatch):
    """Near-uniform (hash-routed) waves must take the donated
    single-round program — staging buffers aliased as receive buffers —
    while skewed waves must fall back to the undonated multi-round
    respill (aliasing there would corrupt round 2+)."""
    import pathway_tpu.parallel.exchange as ex

    mesh = _mesh()
    n_shards = mesh.shape["data"]
    flags = []
    orig = ex.exchange_by_key

    def spy(*args, **kwargs):
        flags.append(kwargs.get("donate", False))
        return orig(*args, **kwargs)

    monkeypatch.setattr(ex, "exchange_by_key", spy)
    rng = np.random.default_rng(4)
    n = 10_000
    ids = np.arange(n, dtype=np.uint32)
    pay = rng.normal(size=(n, 2)).astype(np.float32)
    hashed = rng.integers(0, n_shards, n).astype(np.int64)
    _k, pays, srcs = exchange_with_respill(ids, pay, hashed, mesh)
    assert flags == [True]  # ONE donated round
    for d in range(n_shards):
        idx = np.nonzero(hashed == d)[0]
        assert np.array_equal(srcs[d], idx)
        assert np.array_equal(pays[d], pay[idx])
    flags.clear()
    skewed = np.where(
        rng.random(n) < 0.8, 0, rng.integers(0, n_shards, n)
    ).astype(np.int64)
    _k, pays, srcs = exchange_with_respill(ids, pay, skewed, mesh)
    assert len(flags) > 1 and not any(flags)  # multi-round, undonated
    for d in range(n_shards):
        idx = np.nonzero(skewed == d)[0]
        assert np.array_equal(srcs[d], idx)
        assert np.array_equal(pays[d], pay[idx])


# ------------------------------------------------------- routing parity


def test_host_device_routing_parity_under_key_skew():
    """dp_route_key (the C 128-bit key % n rule feeding the device
    plane's dests) must agree with the Python _shard_of on adversarial
    keys: dense sequential, high-bit-heavy, and colliding-low-64 keys."""
    from pathway_tpu.engine.native import dataplane as dp

    if not dp.available():
        pytest.skip("native dataplane unavailable")
    from pathway_tpu.engine.workers import _shard_of
    from pathway_tpu.internals.keys import Key

    rng = np.random.default_rng(11)
    lo = np.concatenate([
        np.arange(256, dtype=np.uint64),  # dense sequential
        rng.integers(0, 2**64 - 1, 256, dtype=np.uint64),
        np.full(64, 0xDEADBEEF, np.uint64),  # colliding low words
    ])
    hi = np.concatenate([
        np.zeros(256, np.uint64),
        rng.integers(0, 2**64 - 1, 256, dtype=np.uint64),
        np.arange(64, dtype=np.uint64) << 32,
    ])
    for n_shards in (2, 3, 4, 7, 8):
        via_c = dp.route_key(lo, hi, n_shards)
        via_128 = route128(lo, hi, n_shards)
        assert np.array_equal(via_c, via_128)
        for i in range(0, len(lo), 37):
            key = Key((int(hi[i]) << 64) | int(lo[i]))
            assert _shard_of(key.value, n_shards) == via_c[i]


# --------------------------------------------------- batch split identity


def _native_batch(n, rng):
    from pathway_tpu.engine.native import dataplane as dp

    tab = dp.default_table()
    tok = np.empty(n, np.uint64)
    for i in range(n):
        t = tab.intern_row((f"row{i % 50}", i % 13))
        assert t is not None
        tok[i] = t
    lo = rng.integers(0, 2**63, n).astype(np.uint64)
    hi = rng.integers(0, 2**63, n).astype(np.uint64)
    diff = rng.choice([-1, 1], n).astype(np.int64)
    return dp.NativeBatch(tab, lo, hi, tok, diff)


def test_split_batch_matches_host_select_byte_for_byte(monkeypatch):
    """ColumnExchanger.split_batch == [batch.select(shards == p) ...] on
    every column, in order — the byte-identity the host fallback rests
    on."""
    from pathway_tpu.engine.native import dataplane as dp

    if not dp.available():
        pytest.skip("native dataplane unavailable")
    _mesh()
    monkeypatch.setenv("PATHWAY_DEVICE_EXCHANGE", "1")
    from pathway_tpu.parallel.column_plane import ColumnExchanger

    rng = np.random.default_rng(5)
    batch = _native_batch(400, rng)
    ce = ColumnExchanger()
    n_shards = 4
    shards = np.asarray(
        dp.route_key(batch.key_lo, batch.key_hi, n_shards), np.int64
    )
    subs = ce.split_batch(batch, shards, n_shards)
    assert subs is not None
    for p in range(n_shards):
        ref = batch.select(shards == p)
        got = subs[p]
        assert np.array_equal(got.key_lo, ref.key_lo)
        assert np.array_equal(got.key_hi, ref.key_hi)
        assert np.array_equal(got.token, ref.token)
        assert np.array_equal(got.diff, ref.diff)
        # tokens are process-wide: rows materialize identically
        assert got.materialize() == ref.materialize()


def test_split_batch_gating(monkeypatch):
    """Off mode and auto-on-virtual-mesh must refuse (host path); force
    must engage regardless of batch size."""
    from pathway_tpu.engine.native import dataplane as dp

    if not dp.available():
        pytest.skip("native dataplane unavailable")
    _mesh()
    from pathway_tpu.parallel.column_plane import ColumnExchanger

    rng = np.random.default_rng(6)
    batch = _native_batch(64, rng)
    shards = np.asarray(dp.route_key(batch.key_lo, batch.key_hi, 2), np.int64)
    monkeypatch.setenv("PATHWAY_DEVICE_EXCHANGE", "0")
    assert ColumnExchanger().split_batch(batch, shards, 2) is None
    monkeypatch.delenv("PATHWAY_DEVICE_EXCHANGE", raising=False)
    # auto on a CPU/virtual mesh: measured always slower -> refuse
    assert ColumnExchanger().split_batch(batch, shards, 2) is None
    monkeypatch.setenv("PATHWAY_DEVICE_EXCHANGE", "1")
    assert ColumnExchanger().split_batch(batch, shards, 2) is not None


def test_device_wire_fault_degrades_to_host(monkeypatch):
    """mesh.device_wire firing on every hit must absorb into a host-path
    split (None) with the fault + degrade counters bumped; a single
    isolated shot must be retried in place."""
    from pathway_tpu.engine import faults
    from pathway_tpu.engine.native import dataplane as dp

    if not dp.available():
        pytest.skip("native dataplane unavailable")
    _mesh()
    from pathway_tpu.parallel import column_plane

    monkeypatch.setenv("PATHWAY_DEVICE_EXCHANGE", "1")
    rng = np.random.default_rng(9)
    batch = _native_batch(128, rng)
    shards = np.asarray(dp.route_key(batch.key_lo, batch.key_hi, 2), np.int64)
    column_plane.reset_stats()
    faults.install("mesh.device_wire@1+")
    try:
        ce = column_plane.ColumnExchanger()
        assert ce.split_batch(batch, shards, 2) is None
        st = column_plane.stats()
        assert st["wire_faults"] == 2  # shot + retried shot
        assert st["host_degrades"] == 1
        # a lone shot (fresh schedule, hit 1 only) retries in place and
        # succeeds — the retry's probe is hit 2, which doesn't fire
        faults.install("mesh.device_wire@1")
        subs = ce.split_batch(batch, shards, 2)
        assert subs is not None
        assert column_plane.stats()["wire_faults"] == 3
        assert column_plane.stats()["host_degrades"] == 1
    finally:
        faults.reset()
        column_plane.reset_stats()


def test_planner_retunes_column_plane_without_vector_exchanger(monkeypatch):
    """Scalar-only workloads never build the vector exchanger: the
    planner must still tune the column plane's row threshold in both
    directions, and a fence that moves no knob must not burn the retune
    budget or record a phantom replan."""
    _mesh()
    from pathway_tpu.internals.planner import AdaptivePolicy
    from pathway_tpu.parallel import column_plane as cp
    from pathway_tpu.parallel import device_exchange as dx

    monkeypatch.setenv("PATHWAY_DEVICE_EXCHANGE", "1")
    monkeypatch.setattr(dx, "_ENGINE_EXCHANGER", None)
    ce = cp.ColumnExchanger()
    monkeypatch.setattr(cp, "_ENGINE_EXCHANGER", ce)

    class _Metrics:
        def __init__(self, inv, rows):
            self._v = {
                "pathway_device_exchange_invocations": inv,
                "pathway_device_exchange_rows": rows,
            }

        def counter_value(self, name):
            return self._v.get(name, 0)

        def counter(self, name, inc=1, help=None):
            pass

    class _Plane:
        def __init__(self, inv, rows):
            self.metrics = _Metrics(inv, rows)

        def record(self, *args, **kwargs):
            pass

    pol = AdaptivePolicy(graph=None, min_rows_per_exchange=64)
    base = ce._auto_min_rows
    # thin batches (8 rows/invocation): the row threshold doubles
    assert pol._retune_exchange(_Plane(10, 80)) == 1
    assert ce._auto_min_rows == base * 2
    # sustained wins (>= 8x the floor): it halves back down
    assert pol._retune_exchange(_Plane(10, 10 * 64 * 8)) == 1
    assert ce._auto_min_rows == base
    # mid-band rows/invocation: no knob moves, no budget burned
    burned = pol._exchange_tuned
    assert pol._retune_exchange(_Plane(10, 10 * 64)) == 0
    assert pol._exchange_tuned == burned


# ---------------------------------------------------- engine end-to-end


def _run_wordcount(tmp_path, tag: str, env_extra: dict) -> tuple[str, dict]:
    import json as _json

    inp = os.path.join(str(tmp_path), "in.jsonl")
    if not os.path.exists(inp):
        with open(inp, "w") as f:
            for i in range(3000):
                f.write('{"word": "w%d"}\n' % (i % 61))
    out = os.path.join(str(tmp_path), f"out_{tag}.csv")
    code = f"""
import json, sys
sys.path.insert(0, {REPO!r})
import pathway_tpu as pw
from pathway_tpu.parallel import column_plane

t = pw.io.jsonlines.read({inp!r}, schema=pw.schema_from_types(word=str), mode="static")
res = t.groupby(t.word).reduce(t.word, n=pw.reducers.count())
pw.io.csv.write(res, {out!r})
pw.run()
print("STATS " + json.dumps(column_plane.stats()))
"""
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PATHWAY_THREADS": "4", **env_extra,
    }
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    stats = _json.loads(
        [ln for ln in r.stdout.splitlines() if ln.startswith("STATS ")][-1][6:]
    )
    with open(out) as f:
        return f.read(), stats


@pytest.mark.slow
def test_engine_shuffle_device_vs_host_byte_identical(tmp_path):
    """The acceptance A/B: PATHWAY_DEVICE_EXCHANGE=0 reproduces the
    forced column plane's shuffled outputs byte-identically, and the
    forced run really rode the collective."""
    from pathway_tpu.engine.native import dataplane as dp

    if not dp.available():
        # the column plane lifts NativeBatch columns; under the object
        # plane (PATHWAY_TPU_NATIVE=0) no collective can engage
        pytest.skip("native dataplane unavailable")
    dev, dev_stats = _run_wordcount(
        tmp_path, "dev", {"PATHWAY_DEVICE_EXCHANGE": "1"}
    )
    host, host_stats = _run_wordcount(
        tmp_path, "host", {"PATHWAY_DEVICE_EXCHANGE": "0"}
    )
    assert dev == host
    assert dev_stats["invocations"] > 0
    assert host_stats["invocations"] == 0


# ------------------------------------------------------- sharded ANN


def test_ivf_sharded_matches_unsharded():
    """List-sharded IVF-PQ search returns the same result sets as the
    unsharded program (each shard rescans a candidate superset, so
    recall can only match or improve) with global slot ids."""
    mesh = _mesh()
    from pathway_tpu.ops import ivf as _ivf

    rng = np.random.default_rng(1)
    n, d = 3000, 32
    centers = rng.normal(size=(30, d))
    docs = (
        centers[rng.integers(0, 30, n)] + 0.1 * rng.normal(size=(n, d))
    ).astype(np.float32)
    idx = _ivf.build_ivf_pq(docs, metric="cos")
    q = (
        centers[rng.integers(0, 30, 8)] + 0.1 * rng.normal(size=(8, d))
    ).astype(np.float32)
    s_un, _ = _ivf.ivf_pq_search(q, idx, 10)
    sidx = _ivf.shard_ivf_pq(idx, mesh)
    s_sh, d_sh = _ivf.ivf_pq_search_sharded(q, sidx, 10)
    s_un, s_sh, d_sh = map(np.asarray, (s_un, s_sh, d_sh))
    qq = q / np.linalg.norm(q, axis=1, keepdims=True)
    dd = docs / np.linalg.norm(docs, axis=1, keepdims=True)
    exact = np.argsort(-(qq @ dd.T), axis=1)[:, :10]
    for i in range(len(q)):
        rec_un = len(set(s_un[i]) & set(exact[i]))
        rec_sh = len(set(s_sh[i]) & set(exact[i]))
        assert rec_sh >= rec_un
        assert (s_sh[i] >= 0).all() and np.isfinite(d_sh[i]).all()


def test_ivf_pq_index_sharded_search_parity():
    """IvfPqIndex(sharded=True): same result set as the default index
    through adds, retractions, and the lazy view rebuild."""
    _mesh()
    from pathway_tpu.indexing.ann import IvfPqIndex
    from pathway_tpu.internals.keys import Key

    rng = np.random.default_rng(2)
    d = 16
    a = IvfPqIndex(
        dimensions=d, train_min=64, sharded=True, background_retrain=False
    )
    b = IvfPqIndex(dimensions=d, train_min=64, background_retrain=False)
    centers = rng.normal(size=(8, d))
    for i in range(400):
        v = (centers[i % 8] + 0.05 * rng.normal(size=d)).astype(np.float32)
        a.add(Key(i), v)
        b.add(Key(i), v)
    q = (centers[2] + 0.05 * rng.normal(size=d)).astype(np.float32)
    ra = a.search(q, 10)
    rb = b.search(q, 10)
    assert {k.value for k, _ in ra} == {k.value for k, _ in rb}
    assert a._shard_search and a._sharded_failures == 0
    for i in range(0, 60):
        a.remove(Key(i))
        b.remove(Key(i))
    ra2 = a.search(q, 10)
    rb2 = b.search(q, 10)
    assert {k.value for k, _ in ra2} == {k.value for k, _ in rb2}
    assert all(k.value >= 60 for k, _ in ra2)


# --------------------------------------------------- mesh slot pools


def test_mesh_spanning_slot_pool_byte_identical():
    """`mesh_span=True`: the slot pool spans the mesh (n_slots x
    shards) and per-request tokens are byte-identical to the
    single-device pool."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device (virtual) mesh")
    from pathway_tpu.models import lm_config, transformer as tfm
    from pathway_tpu.serving.continuous_batching import ContinuousBatcher

    class Tok:
        def tokenize(self, s):
            return [2 + (ord(c) % 40) for c in s][:12]

    cfg = lm_config(
        vocab_size=128, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_len=32,
    )
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    def drive(span, name):
        cb = ContinuousBatcher(
            params=params, cfg=cfg, tokenizer=Tok(), n_steps=3,
            n_slots=2, name=name, mesh_span=span,
        )
        try:
            futs = [cb.submit(f"prompt {i}") for i in range(4)]
            return [f.result(timeout=120) for f in futs], cb.n_slots
        finally:
            cb.close()

    out_off, slots_off = drive(False, "cp-t-off")
    out_on, slots_on = drive(True, "cp-t-on")
    assert slots_off == 2
    assert slots_on == 2 * len(jax.devices())
    assert out_off == out_on
