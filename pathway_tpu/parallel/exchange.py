"""Key-hash record exchange over the device mesh — the ICI data plane.

Reference parity: timely's exchange pacts route each record to the worker
owning hash(key) % n_workers over shared-memory channels or TCP
(external/timely-dataflow/communication/src/networking.rs). Here the shuffle
of a batch of (key, payload) rows is ONE jit-compiled XLA program: each
shard sorts its rows into per-destination buckets (static capacity, padded)
and a single `all_to_all` moves the buckets across the interconnect. Scalar
control traffic stays on host; bulk numeric payloads ride ICI.

Static-shape design: XLA needs fixed shapes, so each shard sends exactly
`capacity + 1` slots to every destination (the extra slot is a trash slot
absorbing masked-out and overflowing rows), padding unused slots with a
validity flag.

Routing over the REAL 128-bit key space: the u32 `keys` carried through
the exchange are identifiers, not the routing domain. Callers pass
`dests` — the destination shard per row, computed host-side with the
exact 128-bit `key % n_shards` (dataplane.dp_route_key) or any other
content-stable rule — so device routing agrees bit-for-bit with the
engine's host exchange (engine/workers._shard_of).

Overflow: `exchange_by_key` flags it; `exchange_with_respill` handles it
properly — the host knows every (src, dst) bucket count exactly, so it
ships rows in ceil(max_count / capacity) rounds, each round sending the
next `capacity` rows of each bucket. No data is dropped and capacity
never balloons to the worst case.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


class ExchangeResult(NamedTuple):
    keys: Array  # [shards, (cap+1) * shards] u32 — received keys per slot
    payloads: Array  # [shards, (cap+1) * shards, d] — received payloads
    valid: Array  # [shards, (cap+1) * shards] bool — slot occupancy
    # some bucket exceeded capacity: the overflowing rows landed in the
    # trash slot (marked invalid), so rows are MISSING when this is set —
    # use exchange_with_respill for the wrapper that re-ships them
    overflowed: Array  # [] bool


def _bucketize(keys, payloads, dests, valid_in, n_shards: int, cap: int,
               axis: str):
    """Sort one shard's rows into n_shards buckets of cap+1 slots each
    (slot `cap` of each bucket is the trash slot: masked-out rows and
    bucket overflow land there, always marked invalid)."""
    me = jax.lax.axis_index(axis)
    dest = jnp.where(valid_in, dests, me)  # masked rows stay "local"
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    sorted_valid = valid_in[order]
    # slot within destination bucket = running index among VALID
    # same-destination rows (arrival order preserved by the stable sort)
    same = (sorted_dest[:, None] == jnp.arange(n_shards)[None, :]) & sorted_valid[:, None]
    within = jnp.cumsum(same, axis=0)[jnp.arange(keys.shape[0]), sorted_dest] - 1
    fits = sorted_valid & (within < cap)
    overflow = jnp.any(sorted_valid & (within >= cap))
    slot = sorted_dest * (cap + 1) + jnp.where(fits, within, cap)
    width = n_shards * (cap + 1)
    bucket_keys = jnp.zeros((width,), keys.dtype).at[slot].set(keys[order])
    bucket_pay = (
        jnp.zeros((width,) + payloads.shape[1:], payloads.dtype)
        .at[slot]
        .set(payloads[order])
    )
    bucket_valid = jnp.zeros((width,), bool).at[slot].set(fits)
    # the trash slot may have been scattered with a row's data; force-mark
    # every bucket's slot `cap` invalid
    trash = jnp.arange(n_shards) * (cap + 1) + cap
    bucket_valid = bucket_valid.at[trash].set(False)
    return bucket_keys, bucket_pay, bucket_valid, overflow


# mesh -> small stable token for program names: two distinct meshes with
# the same axis/shape must NOT share one registered program (the
# shard_map closes over the mesh). The lru_cache below already keeps a
# strong ref to every cached mesh, so tokens never alias live meshes.
_MESH_TOKENS: dict = {}


def _mesh_token(mesh: Mesh) -> int:
    tok = _MESH_TOKENS.get(mesh)
    if tok is None:
        tok = _MESH_TOKENS[mesh] = len(_MESH_TOKENS)
    return tok


@functools.lru_cache(maxsize=64)
def _exchange_program(mesh: Mesh, axis: str, n_shards: int, cap: int,
                      donate: bool = False):
    """One compiled exchange program per (mesh, axis, capacity): rebuilding
    the shard_map closure per call would retrace+recompile every batch.

    `donate=True` donates the keys/payload/valid staging buffers to XLA.
    Donation aliases input to output storage only when byte sizes match,
    which holds exactly when the caller pads its rows to
    ``n_shards * (cap + 1)`` per shard — the steady-state single-round
    layout `exchange_with_respill` produces for near-uniform waves. The
    staging memory of wave N is then reused as the receive buffers of the
    same dispatch instead of accumulating a second copy per wave.

    The jit is owned by the device plane's per-bucket compile ledger
    (engine/device_plane.py): every dispatch charges bucket ``cap``, so
    adversarial capacity churn shows up as new (program, bucket) rows
    while steady-state ragged waves — whose padded shapes are fully
    determined by (cap, n_shards, lanes) — keep each row pinned at one
    compilation. A failing XLA dispatch degrades to the eager shard_map
    host path via the plane's quarantine instead of killing the wave."""

    def local(k, p, d, v):
        bk, bp, bv, overflow = _bucketize(k, p, d, v, n_shards, cap, axis)
        w = cap + 1
        bk = bk.reshape(n_shards, w)
        bp = bp.reshape((n_shards, w) + p.shape[1:])
        bv = bv.reshape(n_shards, w)
        rk = jax.lax.all_to_all(bk, axis, 0, 0, tiled=False)
        rp = jax.lax.all_to_all(bp, axis, 0, 0, tiled=False)
        rv = jax.lax.all_to_all(bv, axis, 0, 0, tiled=False)
        ov = jax.lax.pmax(overflow.astype(jnp.int32), axis)
        return (
            rk.reshape(1, n_shards * w),
            rp.reshape((1, n_shards * w) + p.shape[1:]),
            rv.reshape(1, n_shards * w),
            ov.reshape(1),
        )

    mapped = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
    )
    from pathway_tpu.engine.device_plane import get_device_plane

    name = (
        f"exchange.a2a[{axis}]:s{n_shards}:c{cap}:m{_mesh_token(mesh)}"
        + (":donated" if donate else "")
    )
    # dests (arg 2) has no same-dtype output to alias; donating it
    # would only draw the "unusable donation" warning
    prog = get_device_plane().program(
        name, mapped, donate_argnums=(0, 1, 3) if donate else ()
    )

    def dispatch(*args):
        return prog(*args, bucket=cap)

    return dispatch


def exchange_by_key(
    keys: Array,
    payloads: Array,
    mesh: Mesh,
    axis: str = "data",
    capacity: int | None = None,
    dests: Array | None = None,
    valid: Array | None = None,
    donate: bool = False,
) -> ExchangeResult:
    """Shuffle rows so shard s receives every row with dests == s
    (default dests: keys % n_shards).

    keys: [n] uint32 (row key identifiers), sharded over `axis`.
    payloads: [n, d] numeric payloads, same sharding.
    dests: [n] int32 destination shard per row (host-computed exact
    128-bit routing) — MUST be in [0, n_shards): out-of-range scatter
    indices are dropped by XLA without any signal, so host-array dests
    are validated here; valid: [n] bool row mask (False rows don't ship).
    Output arrays keep the shard dimension explicit: result.keys[s] are
    the rows now owned by shard s.
    """
    n_shards = mesh.shape[axis]
    rows_total = keys.shape[0]
    if rows_total % n_shards != 0:
        raise ValueError(f"row count {rows_total} not divisible by {n_shards}")
    rows_local = rows_total // n_shards
    cap = capacity or rows_local
    if dests is None:
        dests = (keys % n_shards).astype(jnp.int32)
    elif isinstance(dests, np.ndarray):
        if len(dests) and (dests.min() < 0 or dests.max() >= n_shards):
            raise ValueError(
                f"dests outside [0, {n_shards}): rows would be silently "
                "dropped by the device scatter"
            )
    if valid is None:
        valid = jnp.ones(rows_total, bool)

    fn = _exchange_program(mesh, axis, n_shards, cap, donate)
    rk, rp, rv, ov = fn(
        keys, payloads, jnp.asarray(dests, jnp.int32), valid
    )
    return ExchangeResult(
        keys=rk, payloads=rp, valid=rv, overflowed=jnp.any(ov > 0)
    )


def exchange_by_key_checked(
    keys: Array,
    payloads: Array,
    mesh: Mesh,
    axis: str = "data",
    capacity: int | None = None,
    max_retries: int = 3,
) -> ExchangeResult:
    """Legacy wrapper: retries with doubled capacity while `overflowed`.
    Prefer exchange_with_respill (no data loss, bounded memory)."""
    n_shards = mesh.shape[axis]
    cap = capacity or keys.shape[0] // n_shards
    for _ in range(max_retries + 1):
        result = exchange_by_key(keys, payloads, mesh, axis, capacity=cap)
        if not bool(result.overflowed):
            return result
        cap *= 2
    raise RuntimeError(
        f"exchange overflowed even at capacity {cap // 2} per bucket "
        f"({max_retries} retries) — key distribution is pathologically "
        "skewed; pre-aggregate or rebalance keys"
    )


def route128(key_lo: np.ndarray, key_hi: np.ndarray, n_shards: int) -> np.ndarray:
    """Exact destination over the 128-bit key space (key % n_shards),
    identical to engine/workers._shard_of for record keys. Uses the C
    kernel when present."""
    try:
        from pathway_tpu.engine.native import dataplane as dp

        if dp.available():
            return dp.route_key(
                np.ascontiguousarray(key_lo, np.uint64),
                np.ascontiguousarray(key_hi, np.uint64),
                n_shards,
            )
    except Exception:  # noqa: BLE001
        pass
    m = n_shards
    r64 = pow(2, 64, m)
    return np.asarray(
        [
            (int(hi) % m * r64 + int(lo) % m) % m
            for lo, hi in zip(key_lo, key_hi)
        ],
        np.int64,
    )


def _verifier_on() -> bool:
    """Plan-verifier gate for the per-wave donation guard: the cached
    mirror (refreshed at every session's execute seam) — an env read
    per wave is the PR 9(h) bug class."""
    from pathway_tpu.internals import verifier

    return verifier.enabled_cached()


def plan_respill_layout(
    capacity: int | None, max_bucket: int, per: int, n_shards: int
) -> tuple[bool, int, int, int]:
    """The respill layout decision as a pure function of the wave shape:
    returns (donate, cap, rounds, rows_local).

    Steady-state donation sizes a SINGLE-round layout from the measured
    max bucket — each shard sends n_shards*(max_bucket+1) slots, which
    byte-matches the receive buffers, so the donated program aliases
    them and steady-state waves reuse staging memory. Taken only while
    the staging overhead stays bounded (~25% over the real rows; the
    n_shards^2 floor keeps small waves eligible). Skewed waves keep the
    multi-round respill UNDONATED: the device arrays are reused across
    rounds there, so aliasing would corrupt round 2+ — the invariant
    internals/verifier.py re-probes over a shape grid."""
    donate = (
        capacity is None
        and max_bucket >= 1
        and n_shards * (max_bucket + 1)
        <= per + max(per // 4, n_shards * n_shards)
    )
    if donate:
        cap, rounds = max_bucket, 1
        rows_local = n_shards * (cap + 1)
    else:
        cap = capacity or max(min(max_bucket, max(per // 2, 1)), 1)
        rounds = max(1, -(-max_bucket // cap))
        rows_local = max(per, 1)
    return donate, cap, rounds, rows_local


def exchange_with_respill(
    key_ids: np.ndarray,
    payloads: np.ndarray,
    dests: np.ndarray,
    mesh: Mesh,
    axis: str = "data",
    capacity: int | None = None,
):
    """Host-orchestrated multi-round exchange: rows are shipped in
    ceil(max_bucket / capacity) rounds, each round sending at most
    `capacity` rows of every (src, dst) bucket — overflow rows are
    RE-SPILLED to later rounds instead of retrying the whole batch at a
    bigger capacity.

    key_ids: [n] uint32 identifiers; payloads: [n, d]; dests: [n] exact
    destination shards (route128 of the full key). Rows are split evenly
    over source shards in order. Returns (keys_per_dest, payload_per_dest,
    src_index_per_dest): numpy arrays per destination shard, in GLOBAL
    ARRIVAL ORDER (each row's original index), which is the engine's
    same-key ordering invariant — a retraction never overtakes the insert
    it cancels, even when they land in different respill rounds.
    """
    n_shards = mesh.shape[axis]
    n = len(key_ids)
    pos = np.arange(n)
    # contiguous even split of the REAL rows over source shards; bucket
    # stats are computed on real rows only, BEFORE the padded layout is
    # chosen, so pad rows can neither consume capacity slots nor inflate
    # the round count
    per = -(-n // n_shards) if n else 0
    shard = pos // per if n else pos
    dests64 = np.asarray(dests, np.int64)
    # per-(src,dst) within-bucket rank, vectorized: row order IS
    # (src-major, arrival) order, so the rank is the running count per
    # (src,dst) pair
    sd = shard * n_shards + dests64
    order = np.argsort(sd, kind="stable")
    sorted_sd = sd[order]
    group_start = np.r_[0, np.nonzero(np.diff(sorted_sd))[0] + 1]
    group_len = np.diff(np.r_[group_start, n])
    within_sorted = np.arange(n) - np.repeat(group_start, group_len)
    within = np.empty(n, np.int64)
    within[order] = within_sorted
    max_bucket = int(group_len.max()) if n else 0
    # steady-state donation vs multi-round respill: the layout decision
    # and its aliasing rule live in plan_respill_layout
    donate, cap, rounds, rows_local = plan_respill_layout(
        capacity, max_bucket, per, n_shards
    )
    if _verifier_on():
        # the donation aliasing rule, re-checked at the live decision
        # (internals/verifier.py also re-probes the planner statically)
        from pathway_tpu.internals.verifier import check_donation

        check_donation(donate, rounds, rows_local, n_shards, cap)
    # per-shard padded layout: shard s holds its run of `per` real rows
    # followed by invalid pad slots up to rows_local
    total = rows_local * n_shards
    padded_pos = shard * rows_local + (pos - shard * per)
    orig_of = np.full(total, -1, np.int64)
    orig_of[padded_pos] = pos
    pk = np.zeros(total, key_ids.dtype)
    pk[padded_pos] = key_ids
    ppay = np.zeros((total,) + payloads.shape[1:], payloads.dtype)
    ppay[padded_pos] = payloads
    pdests = np.zeros(total, np.int64)
    pdests[padded_pos] = dests64
    key_ids, payloads, dests = pk, ppay, pdests

    keys_d = jax.device_put(
        jnp.asarray(key_ids, jnp.uint32),
        NamedSharding(mesh, P(axis)),
    )
    pay_d = jax.device_put(
        jnp.asarray(payloads), NamedSharding(mesh, P(axis, *([None] * (payloads.ndim - 1))))
    )
    dest_d = jax.device_put(
        jnp.asarray(dests, jnp.int32), NamedSharding(mesh, P(axis))
    )
    acc_pay: list[list] = [[] for _ in range(n_shards)]
    acc_keys: list[list] = [[] for _ in range(n_shards)]
    acc_src: list[list] = [[] for _ in range(n_shards)]
    dests_np = np.asarray(dests, np.int64)
    for r in range(rounds):
        sel = np.zeros(total, bool)
        sel[padded_pos] = (within >= r * cap) & (within < (r + 1) * cap)
        valid_d = jax.device_put(
            jnp.asarray(sel), NamedSharding(mesh, P(axis))
        )
        res = exchange_by_key(
            keys_d, pay_d, mesh, axis, capacity=cap, dests=dest_d,
            valid=valid_d, donate=donate,
        )
        assert not bool(res.overflowed)  # capacity rounds preclude overflow
        rk = np.asarray(res.keys)
        rp = np.asarray(res.payloads)
        rv = np.asarray(res.valid)
        for d in range(n_shards):
            # received slot order is (src-major, within-bucket arrival) =
            # ascending padded index among this round's selected rows,
            # mapped back to the caller's pre-padding row indices
            idx = np.nonzero(sel & (dests_np == d))[0]
            acc_keys[d].append(rk[d][rv[d]])
            acc_pay[d].append(rp[d][rv[d]])
            acc_src[d].append(orig_of[idx])
    out_keys, out_pay, out_src = [], [], []
    for d in range(n_shards):
        k = np.concatenate(acc_keys[d]) if acc_keys[d] else np.empty(0, np.uint32)
        p = (
            np.concatenate(acc_pay[d])
            if acc_pay[d]
            else np.empty((0,) + payloads.shape[1:], payloads.dtype)
        )
        s = np.concatenate(acc_src[d]) if acc_src[d] else np.empty(0, np.int64)
        # restore global arrival order across rounds
        reorder = np.argsort(s, kind="stable")
        out_keys.append(k[reorder])
        out_pay.append(p[reorder])
        out_src.append(s[reorder])
    return out_keys, out_pay, out_src


def exchange_columns_with_respill(
    columns: "list[np.ndarray]",
    dests: np.ndarray,
    mesh: Mesh,
    axis: str = "data",
    capacity: int | None = None,
):
    """Shuffle a SET of aligned 64-bit scalar columns — a NativeBatch's
    (key_lo, key_hi, token, diff) plus any extra numeric columns — to
    their destination shards in ONE collective per round.

    Each uint64/int64 column becomes TWO uint32 lanes of a [n, 2k]
    payload matrix (a bit-exact little-endian view — JAX truncates u64
    under the default 32-bit mode, so 64-bit values must never enter XLA
    as u64; this mirrors the i32-as-f32 transport of the vector plane),
    so the whole column set crosses the interconnect in a single
    `all_to_all` instead of one dispatch per column. Returns
    ``(cols_per_dest, src_per_dest)``: for every destination shard, the
    column list back in the input dtypes plus the original row indices,
    both in global arrival order (the engine's same-key ordering
    invariant).
    """
    assert columns, "need at least one column"
    n = len(columns[0])
    dtypes = []
    lanes = []
    for c in columns:
        c = np.ascontiguousarray(c)
        assert c.dtype.itemsize == 8 and c.ndim == 1 and len(c) == n
        dtypes.append(c.dtype)
        lanes.append(c.view(np.uint32).reshape(n, 2))
    payload = (
        np.stack(lanes, axis=1).reshape(n, 2 * len(columns))
        if n
        else np.empty((0, 2 * len(columns)), np.uint32)
    )
    ids = (np.arange(n, dtype=np.uint64) & 0xFFFFFFFF).astype(np.uint32)
    _keys, pays, srcs = exchange_with_respill(
        ids, payload, np.asarray(dests, np.int64), mesh, axis, capacity
    )
    n_shards = mesh.shape[axis]
    cols_per_dest: list[list[np.ndarray]] = []
    for d in range(n_shards):
        p = pays[d]  # [m, 2k] u32, arrival order
        cols_per_dest.append(
            [
                np.ascontiguousarray(p[:, 2 * j : 2 * j + 2])
                .view(dtypes[j])
                .reshape(-1)
                for j in range(len(columns))
            ]
        )
    return cols_per_dest, srcs


@functools.partial(jax.jit, static_argnames=("n_shards",))
def partition_counts(keys: Array, n_shards: int) -> Array:
    """Histogram of destination shards — the host scheduler uses this to
    spot skew before committing to a capacity."""
    dest = keys % n_shards
    return jnp.bincount(dest, length=n_shards)
