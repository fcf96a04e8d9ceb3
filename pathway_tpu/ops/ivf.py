"""IVF-PQ kernels: coarse k-means routing + product-quantized ADC scan.

The brute-force slab (`ops/topk.py`) reads every doc row per query —
perfect MXU utilization, but HBM traffic grows linearly with the corpus
and ~2.4k q/s at 10k docs will not survive the 100M-doc story
(ROADMAP item 3). IVF-PQ bends that curve twice:

* **IVF (inverted file)** — docs are routed to the nearest of `L`
  coarse k-means centroids; a query scores only the `nprobe` closest
  lists, cutting the scanned fraction to ~nprobe/L.
* **PQ (product quantization)** — each doc row is stored as `m` uint8
  codes (one 256-entry codebook per d/m-wide subspace), so the scan
  reads m bytes/row instead of 2d (bf16). Distances come from a per
  query lookup table (ADC): score(q, x) = Σ_m LUT[m, code_m(x)].

The layout is device-resident and fixed-shape: per-list slabs packed
into one `[L, cap, m]` code cube plus `[L, cap]` validity/slot maps, so
probe → ADC scan → top-k compiles ONCE per (shape bucket) and streaming
growth only re-buckets at powers of two — the same jit-cache discipline
as the slab index. Like `knn_search_quantized`, the final ranking is an
exact f32 rescore of the top ADC candidates, so residual error comes
only from candidate selection (which lists were probed), never from the
quantization of the winners' scores.

Training (`train_coarse_centroids`, `train_pq_codebooks`) is plain
seeded numpy on purpose: it runs OFF the wave path (background retrain
in `pathway_tpu/indexing/ann.py`) and must be deterministic across
hosts for the A/B test legs.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import numpy as np

__all__ = [
    "IvfPqArrays",
    "ShardedIvfPq",
    "auto_lists",
    "auto_nprobe",
    "auto_subvectors",
    "train_coarse_centroids",
    "train_pq_codebooks",
    "pq_encode",
    "assign_lists",
    "pack_lists",
    "build_ivf_pq",
    "ivf_pq_search",
    "ivf_pq_search_host",
    "shard_ivf_pq",
    "ivf_pq_search_sharded",
]


class IvfPqArrays(NamedTuple):
    """The device-resident IVF-PQ layout (see module docstring).

    `slots` maps a (list, pos) cell back to the global row id in `full`
    (-1 on padding cells); `full` keeps the exact rows for the rescore
    phase, indexed by that global id.
    """

    centroids: np.ndarray  # [L, d] f32 (unit-norm for cos)
    codes: np.ndarray  # [L, cap, m] uint8 — PQ codes per list cell
    valid: np.ndarray  # [L, cap] bool — False = padding or tombstone
    slots: np.ndarray  # [L, cap] int32 — global row id (-1 pad)
    codebooks: np.ndarray  # [m, 256, d/m] f32
    full: np.ndarray  # [n_pad, d] f32 — exact rescore rows


# ------------------------------------------------------------- sizing

def auto_lists(n: int, lo: int = 8, hi: int = 4096) -> int:
    """Default coarse-list count: ~sqrt(n) rounded to a power of two.
    Keeps per-list fill near sqrt(n), the classic IVF balance point
    between probe cost (L) and scan cost (n/L)."""
    if n <= 0:
        return lo
    return int(min(hi, max(lo, 1 << round(math.log2(max(math.sqrt(n), 1.0))))))


def auto_nprobe(n_lists: int) -> int:
    """Default probe width: L/8 clamped to [4, 64]. At small L this scans
    ~12.5% of lists; at large L the absolute cap holds the scanned cell
    count (nprobe × cap) flat while the corpus grows — the whole point
    of the index. The per-query recall knob; raise toward L for
    exact-grade recall."""
    return max(4, min(64, n_lists // 8))


def auto_candidates(k: int) -> int:
    """Default ADC-candidate budget for the exact-rescore phase. PQ
    scores are noisy (8-dim subspaces quantized to 256 entries), so the
    rescore set must be generously wider than k — the gather is c*d per
    query, noise next to the scan, and recall@10 on clustered corpora
    moves from ~0.34 (c=64) to >0.95 (c=512)."""
    return max(48 * k, 256)


def auto_subvectors(dim: int, lo: int = 4, hi: int = 64) -> int:
    """Default PQ split: d/8 subspaces (8 dims per codebook), clamped,
    and snapped down to a divisor of `dim`."""
    m = max(lo, min(hi, dim // 8))
    while dim % m != 0:
        m -= 1
    return max(1, m)


# ------------------------------------------------------------ training

def _chunked_argmin_l2(x: np.ndarray, centers: np.ndarray, chunk: int = 65536):
    """argmin_j ||x_i - c_j||^2 without materializing [n, k] at once."""
    cc = (centers * centers).sum(1)
    out = np.empty(x.shape[0], np.int32)
    for s in range(0, x.shape[0], chunk):
        block = x[s : s + chunk]
        d = cc[None, :] - 2.0 * (block @ centers.T)
        out[s : s + chunk] = np.argmin(d, axis=1)
    return out


def train_coarse_centroids(
    vecs: np.ndarray,
    n_lists: int,
    *,
    iters: int = 8,
    seed: int = 0,
    spherical: bool = True,
    sample: int = 262_144,
) -> np.ndarray:
    """Seeded Lloyd k-means over (a sample of) the rows. `spherical`
    renormalizes centroids each round (cosine routing). Empty clusters
    are re-seeded from the densest cluster's points so every list stays
    reachable."""
    n, d = vecs.shape
    rng = np.random.default_rng(seed)
    x = vecs
    if n > sample:
        x = vecs[rng.choice(n, sample, replace=False)]
    k = min(n_lists, x.shape[0])
    centers = x[rng.choice(x.shape[0], k, replace=False)].astype(np.float32).copy()
    for _ in range(iters):
        assign = _chunked_argmin_l2(x, centers)
        counts = np.bincount(assign, minlength=k)
        sums = np.zeros((k, d), np.float64)
        np.add.at(sums, assign, x)
        nonempty = counts > 0
        centers[nonempty] = (
            sums[nonempty] / counts[nonempty, None]
        ).astype(np.float32)
        empty = np.flatnonzero(~nonempty)
        if empty.size:
            donors = rng.choice(x.shape[0], empty.size)
            centers[empty] = x[donors]
        if spherical:
            centers /= np.maximum(
                np.linalg.norm(centers, axis=1, keepdims=True), 1e-12
            )
    if k < n_lists:  # corpus smaller than the list budget: repeat rows
        reps = rng.choice(k, n_lists - k)
        centers = np.concatenate([centers, centers[reps]], axis=0)
    return centers


def train_pq_codebooks(
    vecs: np.ndarray,
    m: int,
    *,
    iters: int = 6,
    seed: int = 0,
    sample: int = 131_072,
) -> np.ndarray:
    """Per-subspace 256-entry k-means codebooks, [m, 256, d/m] f32.
    Corpora smaller than 256 rows train fewer real entries; the rest are
    zero-padded (codes never reference pad entries)."""
    n, d = vecs.shape
    if d % m != 0:
        raise ValueError(f"dim {d} not divisible by {m} subvectors")
    dsub = d // m
    rng = np.random.default_rng(seed + 1)
    x = vecs
    if n > sample:
        x = vecs[rng.choice(n, sample, replace=False)]
    books = np.zeros((m, 256, dsub), np.float32)
    ksub = min(256, x.shape[0])
    for j in range(m):
        sub = x[:, j * dsub : (j + 1) * dsub].astype(np.float32)
        centers = sub[rng.choice(sub.shape[0], ksub, replace=False)].copy()
        for _ in range(iters):
            assign = _chunked_argmin_l2(sub, centers)
            counts = np.bincount(assign, minlength=ksub)
            sums = np.zeros((ksub, dsub), np.float64)
            np.add.at(sums, assign, sub)
            nonempty = counts > 0
            centers[nonempty] = (
                sums[nonempty] / counts[nonempty, None]
            ).astype(np.float32)
            empty = np.flatnonzero(~nonempty)
            if empty.size:
                centers[empty] = sub[rng.choice(sub.shape[0], empty.size)]
        books[j, :ksub] = centers
    return books


def pq_encode(
    vecs: np.ndarray, codebooks: np.ndarray, chunk: int = 65536
) -> np.ndarray:
    """Encode rows to [n, m] uint8 codes (nearest codebook entry per
    subspace)."""
    n, d = vecs.shape
    m, _, dsub = codebooks.shape
    codes = np.empty((n, m), np.uint8)
    for j in range(m):
        sub = vecs[:, j * dsub : (j + 1) * dsub].astype(np.float32)
        codes[:, j] = _chunked_argmin_l2(sub, codebooks[j], chunk).astype(
            np.uint8
        )
    return codes


def assign_lists(
    vecs: np.ndarray, centroids: np.ndarray, chunk: int = 65536
) -> np.ndarray:
    """Route rows to their nearest coarse centroid (L2 — equivalent to
    max inner product for unit-norm rows and centroids)."""
    return _chunked_argmin_l2(vecs.astype(np.float32), centroids, chunk)


def assign_lists_balanced(
    vecs: np.ndarray,
    centroids: np.ndarray,
    cap: int,
    *,
    n_cand: int = 4,
    chunk: int = 65536,
) -> np.ndarray:
    """Route rows to their nearest centroid WITH a per-list cap: a row
    whose nearest list is full spills to its next-nearest with space
    (up to `n_cand` preferences, then the least-filled list).

    Skewed corpora make plain nearest-centroid assignment pile into hot
    lists, and the device layout pays scan cost of nprobe × cap(longest
    list) — padding, not data. Bounding fill keeps the padded cube
    dense; spilled rows stay recallable because multi-probe reads their
    second-nearest list anyway.
    """
    vecs = vecs.astype(np.float32, copy=False)
    n = vecs.shape[0]
    L = centroids.shape[0]
    if n > L * cap:
        raise ValueError(f"{n} rows exceed total capacity {L}x{cap}")
    cand = np.empty((n, n_cand), np.int32)
    cc = (centroids * centroids).sum(1)
    nc = min(n_cand, L)
    for s in range(0, n, chunk):
        block = vecs[s : s + chunk]
        dist = cc[None, :] - 2.0 * (block @ centroids.T)
        part = np.argpartition(dist, nc - 1, axis=1)[:, :nc]
        order = np.argsort(np.take_along_axis(dist, part, 1), axis=1)
        cand[s : s + chunk, :nc] = np.take_along_axis(part, order, 1)
        if nc < n_cand:
            cand[s : s + chunk, nc:] = cand[s : s + chunk, :1]
    assign = np.full(n, -1, np.int32)
    fill = np.zeros(L, np.int64)
    remaining = np.arange(n)
    for r in range(n_cand):
        if remaining.size == 0:
            break
        want = cand[remaining, r]
        order = np.argsort(want, kind="stable")
        sorted_want = want[order]
        uniq, starts, counts = np.unique(
            sorted_want, return_index=True, return_counts=True
        )
        pos_in_group = np.arange(sorted_want.size) - np.repeat(starts, counts)
        accept = pos_in_group < (cap - fill[sorted_want])
        taken = remaining[order[accept]]
        assign[taken] = sorted_want[accept]
        fill[uniq] += np.minimum(counts, np.maximum(cap - fill[uniq], 0))
        remaining = remaining[order[~accept]]
    for row in remaining:  # rare tail: every preferred list was full
        lst = int(np.argmin(fill))
        assign[row] = lst
        fill[lst] += 1
    return assign


def pack_lists(
    assign: np.ndarray,
    codes: np.ndarray,
    n_lists: int,
    *,
    cap: int | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack per-row codes into the [L, cap, m] cube + valid/slot maps.
    `cap` defaults to the longest list rounded up to a power of two (so
    shape buckets stay stable as lists fill)."""
    counts = np.bincount(assign, minlength=n_lists)
    longest = int(counts.max()) if counts.size else 1
    if cap is None:
        cap = 1 << math.ceil(math.log2(max(longest, 8)))
    elif cap < longest:
        raise ValueError(f"cap {cap} < longest list {longest}")
    m = codes.shape[1]
    cube = np.zeros((n_lists, cap, m), np.uint8)
    valid = np.zeros((n_lists, cap), bool)
    slots = np.full((n_lists, cap), -1, np.int32)
    order = np.argsort(assign, kind="stable")
    pos = np.zeros(n_lists, np.int64)
    for row in order:
        lst = assign[row]
        p = pos[lst]
        cube[lst, p] = codes[row]
        valid[lst, p] = True
        slots[lst, p] = row
        pos[lst] = p + 1
    return cube, valid, slots


def build_ivf_pq(
    docs: np.ndarray,
    *,
    n_lists: int | None = None,
    subvectors: int | None = None,
    metric: str = "cos",
    seed: int = 0,
    iters: int = 8,
) -> IvfPqArrays:
    """One-shot index build over a static doc matrix (the bench and
    `make_knn_searcher` path; the incremental engine index lives in
    `pathway_tpu/indexing/ann.py`)."""
    docs = np.asarray(docs, np.float32)
    n, d = docs.shape
    if metric in ("cos", "cosine"):
        docs = docs / np.maximum(
            np.linalg.norm(docs, axis=1, keepdims=True), 1e-12
        )
    L = n_lists or auto_lists(n)
    m = subvectors or auto_subvectors(d)
    centroids = train_coarse_centroids(
        docs, L, iters=iters, seed=seed, spherical=metric in ("cos", "cosine")
    )
    books = train_pq_codebooks(docs, m, seed=seed)
    codes = pq_encode(docs, books)
    # cap at 2x the average fill (pow2): the probe scan pays nprobe x cap
    # whatever the data skew, so the cube must stay dense
    cap = 1 << math.ceil(math.log2(max(8, 2 * ((n + L - 1) // L))))
    assign = assign_lists_balanced(docs, centroids, cap)
    cube, valid, slots = pack_lists(assign, codes, L, cap=cap)
    import jax.numpy as jnp

    # f32, not bf16: the rescore exists to restore exact order among
    # near-tied winners, and bf16-rounded rows (2^-8 resolution) cap
    # recall@10 at ~0.95 on clustered corpora. Rescore traffic is
    # c*d per query, so f32 costs capacity only — and the capacity
    # story belongs to the PQ codes, not the rescore rows.
    full = jnp.asarray(docs, jnp.float32)
    return IvfPqArrays(
        centroids=centroids,
        codes=cube,
        valid=valid,
        slots=slots,
        codebooks=books,
        full=full,
    )


# -------------------------------------------------------------- search

def _ivf_pq_search_fn(
    q,
    centroids,
    codes,
    valid,
    slots,
    codebooks,
    full,
    *,
    k: int,
    nprobe: int,
    candidates: int,
    metric: str = "cos",
    n_live: int | None = None,
):
    """The resident program: probe → ADC scan → exact rescore → top-k.

    Returns (slot_ids [B, k] int32, distances [B, k] f32); empty ranks
    carry slot -1 / distance +inf. Jitted via `ivf_pq_search` or routed
    through a DevicePlane program by the incremental index (same fn, so
    both share the compile-ledger discipline).

    `n_live` (static) masks trailing PAD lists out of the probe: the
    tiered index dispatches on a pow2-padded hot sub-cube whose pad
    centroids are zeros — without the mask a zero (or duplicated)
    centroid could steal a probe slot from a real list.
    """
    import jax
    import jax.numpy as jnp

    B, d = q.shape
    L, cap, m = codes.shape
    dsub = d // m
    q = q.astype(jnp.float32)
    if metric in ("cos", "cosine"):
        q = q / jnp.maximum(
            jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12
        )
    # ---- probe: similarity to every coarse centroid, top-nprobe lists.
    # L is small (<= ~4k): this matmul is negligible next to the scan.
    if metric == "l2sq":
        csim = -(
            (q * q).sum(1, keepdims=True)
            - 2.0 * q @ centroids.T
            + (centroids * centroids).sum(1)[None, :]
        )
    else:
        csim = q @ centroids.T
    if n_live is not None and n_live < L:
        csim = jnp.where(jnp.arange(L)[None, :] < n_live, csim, -jnp.inf)
    P = min(nprobe, n_live if n_live is not None else L)
    _, probe = jax.lax.top_k(csim, P)  # [B, P]
    # ---- ADC lookup table: one [m, 256] row of partial scores per query
    qs = q.reshape(B, m, dsub)
    if metric == "l2sq":
        # ||q_s - c||^2 per subspace entry; summed = approx distance
        lut = (
            (qs * qs).sum(-1)[:, :, None]
            - 2.0 * jnp.einsum("bms,mcs->bmc", qs, codebooks)
            + (codebooks * codebooks).sum(-1)[None, :, :]
        )
        lut = -lut  # uniform larger-is-better
    else:
        lut = jnp.einsum("bms,mcs->bmc", qs, codebooks)  # [B, m, 256]
    # ---- scan the probed lists' code cells
    pcodes = codes[probe].reshape(B, P * cap, m)  # [B, P*cap, m]
    pvalid = valid[probe].reshape(B, P * cap)
    pslots = slots[probe].reshape(B, P * cap)
    gathered = jnp.take_along_axis(
        lut, pcodes.transpose(0, 2, 1).astype(jnp.int32), axis=2
    )  # [B, m, P*cap]
    adc = gathered.sum(axis=1)  # [B, P*cap]
    adc = jnp.where(pvalid, adc, -jnp.inf)
    # ---- exact rescore of the top ADC candidates (tiny: c*d per query)
    c = min(candidates, P * cap)
    _, cand = jax.lax.top_k(adc, c)
    cslots = jnp.take_along_axis(pslots, cand, axis=1)  # [B, c]
    cvalid = jnp.take_along_axis(pvalid, cand, axis=1)
    rows = full[jnp.clip(cslots, 0, None)]  # [B, c, d]
    if metric == "l2sq":
        diff = q[:, None, :] - rows.astype(jnp.float32)
        exact = -jnp.sum(diff * diff, axis=-1)
    else:
        # f32 accumulation AND f32 operands: clustered corpora pack the
        # winners' sims within bf16's ~2^-8 resolution near 1.0, and a
        # bf16 rescore scrambles exactly the order it exists to restore.
        # The gather is tiny (c*d per query) so the upcast is free.
        exact = jnp.einsum(
            "bd,bcd->bc",
            q,
            rows.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    exact = jnp.where(cvalid, exact, -jnp.inf)
    kk = min(k, c)
    s, pos = jax.lax.top_k(exact, kk)
    out_slots = jnp.take_along_axis(cslots, pos, axis=1)
    if metric == "l2sq":
        dist = -s
    elif metric == "dot":
        dist = -s
    else:
        dist = 1.0 - s
    hit = jnp.isfinite(s) & (s > -jnp.inf)
    out_slots = jnp.where(hit, out_slots, -1)
    dist = jnp.where(hit, dist, jnp.inf)
    return out_slots.astype(jnp.int32), dist.astype(jnp.float32)


@functools.lru_cache(maxsize=1)
def _jitted_search():
    import jax

    return jax.jit(
        _ivf_pq_search_fn,
        static_argnames=("k", "nprobe", "candidates", "metric", "n_live"),
    )


def ivf_pq_search(
    queries,
    index: IvfPqArrays,
    k: int,
    *,
    nprobe: int | None = None,
    candidates: int | None = None,
    metric: str = "cos",
):
    """Functional entry point over `build_ivf_pq` output (one jit cache
    entry per shape bucket × (k, nprobe, candidates, metric))."""
    L = index.centroids.shape[0]
    nprobe = nprobe or auto_nprobe(L)
    # floor the rescore budget at one full list: a clustered query's
    # near-ties are mostly one list's fill, and ADC noise alone must not
    # cut within that set
    candidates = candidates or max(auto_candidates(k), index.codes.shape[1])
    return _jitted_search()(
        queries,
        index.centroids,
        index.codes,
        index.valid,
        index.slots,
        index.codebooks,
        index.full,
        k=k,
        nprobe=nprobe,
        candidates=candidates,
        metric=metric,
    )


def sub_arrays(index: IvfPqArrays, lists, codes=None) -> IvfPqArrays:
    """Restrict the layout to a subset of routing lists (host-side).

    `slots` keep GLOBAL row ids and `full` passes through whole, so
    results over the sub-layout are directly comparable to the full
    index's — and each query's top-nprobe WITHIN a subset that contains
    its global top-nprobe lists is exactly its global top-nprobe (they
    dominate every other member). `codes` optionally overrides the code
    slices (the tiered index substitutes blocks unpacked from cold
    runs)."""
    lists = np.asarray(lists, np.int64)
    return IvfPqArrays(
        centroids=np.asarray(index.centroids, np.float32)[lists],
        codes=np.asarray(index.codes)[lists] if codes is None else codes,
        valid=np.asarray(index.valid)[lists],
        slots=np.asarray(index.slots)[lists],
        codebooks=index.codebooks,
        full=index.full,
    )


class ShardedIvfPq(NamedTuple):
    """The IVF-PQ layout sharded by ROUTING LIST over a device mesh.

    Lists are the natural shard unit (docs/retrieval.md): each chip holds
    L/shards whole lists — its slice of the code cube, validity/slot maps,
    and a LIST-LOCAL copy of the exact rescore rows in cell layout
    (`cells[l, p] = full[slots[l, p]]`), so probe → ADC scan → rescore all
    run without touching another chip's memory. Only the per-query local
    top-k (k slots + k distances per shard) crosses the interconnect in
    the cross-shard merge — O(q·k·shards) ICI traffic, vs O(q·cap·nprobe)
    had the scan itself been split mid-list. Centroids and codebooks are
    tiny and replicated; `slots` keeps GLOBAL row ids so merged results
    are indistinguishable from the unsharded index's.
    """

    centroids: "object"  # [Lp, d] f32, replicated (pad lists masked)
    codes: "object"  # [Lp, cap, m] u8, sharded over `axis`
    valid: "object"  # [Lp, cap] bool, sharded
    slots: "object"  # [Lp, cap] i32 global row ids, sharded
    codebooks: "object"  # [m, 256, d/m] f32, replicated
    cells: "object"  # [Lp, cap, d] f32 list-local rescore rows, sharded
    n_lists: int  # real (unpadded) list count
    mesh: "object"
    axis: str


def shard_ivf_pq(index: IvfPqArrays, mesh, axis: str = "data") -> ShardedIvfPq:
    """Place an IvfPqArrays layout onto `mesh` sharded by routing list.

    Pads the list dimension to a multiple of the shard count (pad lists
    are all-invalid and masked out of the probe), re-materializes the
    rescore rows in list-cell layout so each shard's rescore is local,
    and device_puts every array with its PartitionSpec.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    s = mesh.shape[axis]
    centroids = np.asarray(index.centroids, np.float32)
    codes = np.asarray(index.codes)
    valid = np.asarray(index.valid)
    slots = np.asarray(index.slots, np.int32)
    full = np.asarray(index.full, np.float32)
    L, cap, m = codes.shape
    d = centroids.shape[1]
    Lp = -(-L // s) * s
    if Lp != L:
        pad = Lp - L
        centroids = np.concatenate([centroids, np.zeros((pad, d), np.float32)])
        codes = np.concatenate([codes, np.zeros((pad, cap, m), np.uint8)])
        valid = np.concatenate([valid, np.zeros((pad, cap), bool)])
        slots = np.concatenate([slots, np.full((pad, cap), -1, np.int32)])
    cells = np.zeros((Lp, cap, d), np.float32)
    v = valid
    cells[v] = full[slots[v]]

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return ShardedIvfPq(
        centroids=put(centroids, P()),
        codes=put(codes, P(axis, None, None)),
        valid=put(valid, P(axis, None)),
        slots=put(slots, P(axis, None)),
        codebooks=put(np.asarray(index.codebooks, np.float32), P()),
        cells=put(cells, P(axis, None, None)),
        n_lists=L,
        mesh=mesh,
        axis=axis,
    )


@functools.lru_cache(maxsize=32)
def _sharded_search_program(
    mesh, axis: str, k: int, nprobe: int, candidates: int, metric: str,
    n_lists: int,
):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]

    def local(q, centroids, codes, valid, slots, codebooks, cells):
        # codes/valid/slots/cells: THIS shard's lists [Ls, cap, ...];
        # q/centroids/codebooks replicated — the probe is the same
        # deterministic computation on every shard
        shard = jax.lax.axis_index(axis)
        Ls, cap, m = codes.shape
        B, d = q.shape
        dsub = d // m
        q = q.astype(jnp.float32)
        if metric in ("cos", "cosine"):
            q = q / jnp.maximum(
                jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12
            )
        if metric == "l2sq":
            csim = -(
                (q * q).sum(1, keepdims=True)
                - 2.0 * q @ centroids.T
                + (centroids * centroids).sum(1)[None, :]
            )
        else:
            csim = q @ centroids.T
        Lp = centroids.shape[0]
        # pad lists (all-invalid) must never win a probe slot
        csim = jnp.where(jnp.arange(Lp)[None, :] < n_lists, csim, -jnp.inf)
        Pn = min(nprobe, n_lists)
        _, probe = jax.lax.top_k(csim, Pn)  # [B, Pn] GLOBAL list ids
        local_id = probe - shard * Ls
        owned = (local_id >= 0) & (local_id < Ls)
        lidx = jnp.clip(local_id, 0, Ls - 1)
        pcodes = codes[lidx].reshape(B, Pn * cap, m)
        pvalid = (valid[lidx] & owned[:, :, None]).reshape(B, Pn * cap)
        pslots = slots[lidx].reshape(B, Pn * cap)
        qs = q.reshape(B, m, dsub)
        if metric == "l2sq":
            lut = (
                (qs * qs).sum(-1)[:, :, None]
                - 2.0 * jnp.einsum("bms,mcs->bmc", qs, codebooks)
                + (codebooks * codebooks).sum(-1)[None, :, :]
            )
            lut = -lut
        else:
            lut = jnp.einsum("bms,mcs->bmc", qs, codebooks)
        gathered = jnp.take_along_axis(
            lut, pcodes.transpose(0, 2, 1).astype(jnp.int32), axis=2
        )
        adc = gathered.sum(axis=1)
        adc = jnp.where(pvalid, adc, -jnp.inf)
        c = min(candidates, Pn * cap)
        _, cand = jax.lax.top_k(adc, c)  # [B, c] flat probed-cell index
        cslots = jnp.take_along_axis(pslots, cand, axis=1)
        cvalid = jnp.take_along_axis(pvalid, cand, axis=1)
        # rescore rows come from the LOCAL cell layout: candidate
        # (probed row, cell) -> this shard's [Ls*cap, d] flat rows
        probe_row = cand // cap
        cell = cand % cap
        cand_list = jnp.take_along_axis(lidx, probe_row, axis=1)
        flat = cells.reshape(Ls * cap, d)
        rows = flat[cand_list * cap + cell]  # [B, c, d]
        if metric == "l2sq":
            diff = q[:, None, :] - rows
            exact = -jnp.sum(diff * diff, axis=-1)
        else:
            exact = jnp.einsum(
                "bd,bcd->bc", q, rows, preferred_element_type=jnp.float32
            )
        exact = jnp.where(cvalid, exact, -jnp.inf)
        kk = min(k, c)
        s_loc, pos = jax.lax.top_k(exact, kk)
        slots_loc = jnp.take_along_axis(cslots, pos, axis=1)
        # ---- cross-shard merge: k slots + k scores per shard on the wire
        all_s = jax.lax.all_gather(s_loc, axis)  # [shards, B, kk]
        all_slots = jax.lax.all_gather(slots_loc, axis)
        cand_s = jnp.transpose(all_s, (1, 0, 2)).reshape(B, n_shards * kk)
        cand_slots = jnp.transpose(all_slots, (1, 0, 2)).reshape(
            B, n_shards * kk
        )
        km = min(k, n_shards * kk)
        ms, mpos = jax.lax.top_k(cand_s, km)
        mslots = jnp.take_along_axis(cand_slots, mpos, axis=1)
        if metric in ("l2sq", "dot"):
            dist = -ms
        else:
            dist = 1.0 - ms
        hit = jnp.isfinite(ms) & (ms > -jnp.inf)
        mslots = jnp.where(hit, mslots, -1)
        dist = jnp.where(hit, dist, jnp.inf)
        return mslots.astype(jnp.int32), dist.astype(jnp.float32)

    import jax as _jax

    return _jax.jit(
        _jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(), P(), P(axis, None, None), P(axis, None),
                P(axis, None), P(), P(axis, None, None),
            ),
            out_specs=(P(), P()),
            # after the all_gather every shard holds identical merged
            # results, which the varying-axes inference cannot prove
            check_vma=False,
        )
    )


def ivf_pq_search_sharded(
    queries,
    sindex: ShardedIvfPq,
    k: int,
    *,
    nprobe: int | None = None,
    candidates: int | None = None,
    metric: str = "cos",
):
    """Search a list-sharded index: per-shard probe/ADC/rescore over its
    own lists, cross-shard top-k merge over the interconnect. Returns
    (global slot ids [B, k] i32, distances [B, k] f32) with the same
    -1/+inf empty-rank convention as `ivf_pq_search`; result sets match
    the unsharded index up to the candidate budget (each shard rescans
    its own top-`candidates`, a superset of the global budget, so recall
    can only match or improve)."""
    import jax.numpy as jnp

    L = sindex.n_lists
    cap = sindex.codes.shape[1]
    nprobe = nprobe or auto_nprobe(L)
    candidates = candidates or max(auto_candidates(k), cap)
    fn = _sharded_search_program(
        sindex.mesh, sindex.axis, k, min(nprobe, L), candidates,
        "cos" if metric == "cosine" else metric, L,
    )
    return fn(
        jnp.asarray(queries, jnp.float32),
        sindex.centroids,
        sindex.codes,
        sindex.valid,
        sindex.slots,
        sindex.codebooks,
        sindex.cells,
    )


def ivf_pq_search_host(
    queries: np.ndarray,
    index: IvfPqArrays,
    k: int,
    *,
    nprobe: int | None = None,
    candidates: int | None = None,
    metric: str = "cos",
) -> tuple[np.ndarray, np.ndarray]:
    """Pure-numpy mirror of the device program (graceful-degradation
    path of the incremental index; also the no-jax fallback). Same
    probe/ADC/rescore structure, so the candidate sets match the device
    path up to float associativity."""
    q = np.asarray(queries, np.float32)
    if q.ndim == 1:
        q = q[None, :]
    if metric in ("cos", "cosine"):
        q = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
    B, d = q.shape
    L, cap, m = index.codes.shape
    dsub = d // m
    P = min(nprobe or auto_nprobe(L), L)
    c_budget = candidates or max(auto_candidates(k), cap)
    full = np.asarray(index.full, np.float32)
    if metric == "l2sq":
        csim = -(
            (q * q).sum(1, keepdims=True)
            - 2.0 * q @ index.centroids.T
            + (index.centroids * index.centroids).sum(1)[None, :]
        )
    else:
        csim = q @ index.centroids.T
    out_slots = np.full((B, k), -1, np.int32)
    out_dist = np.full((B, k), np.inf, np.float32)
    for b in range(B):
        probe = np.argpartition(-csim[b], min(P, L) - 1)[:P]
        pcodes = index.codes[probe].reshape(P * cap, m)
        pvalid = index.valid[probe].reshape(P * cap)
        pslots = index.slots[probe].reshape(P * cap)
        qs = q[b].reshape(m, dsub)
        if metric == "l2sq":
            lut = -(
                (qs * qs).sum(-1)[:, None]
                - 2.0 * np.einsum("ms,mcs->mc", qs, index.codebooks)
                + (index.codebooks * index.codebooks).sum(-1)
            )
        else:
            lut = np.einsum("ms,mcs->mc", qs, index.codebooks)
        adc = lut[np.arange(m)[None, :], pcodes.astype(np.int64)].sum(1)
        adc[~pvalid] = -np.inf
        c = min(c_budget, adc.shape[0])
        cand = np.argpartition(-adc, c - 1)[:c]
        cand = cand[pvalid[cand]]
        if cand.size == 0:
            continue
        cslots = pslots[cand]
        rows = full[cslots]
        if metric == "l2sq":
            diff = q[b][None, :] - rows
            exact = -np.sum(diff * diff, axis=-1)
        else:
            exact = rows @ q[b]
        kk = min(k, exact.shape[0])
        top = np.argpartition(-exact, kk - 1)[:kk]
        top = top[np.argsort(-exact[top], kind="stable")]
        out_slots[b, :kk] = cslots[top]
        out_dist[b, :kk] = (
            -exact[top] if metric in ("l2sq", "dot") else 1.0 - exact[top]
        )
    return out_slots, out_dist
