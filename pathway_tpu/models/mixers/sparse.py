"""The `sparse` kind: softmax attention over the blocks of keys each query
chooses by a score over pooled keys (InfLLM v2, `SparseSpec`).

A sparse layer keeps rows of keys as a softmax layer does, but at LOGICAL
positions ("k_sparse"/"v_sparse": the prefill turns the left pad behind
the prompt, so that a block of the selection is a block of rows), and a
pooled key every `stride` positions ("k_pool"); each query scores the
pooled keys it sees whole, chooses blocks of rows by them and attends those
(`select_blocks`). Up to `dense_len` positions a row attends every earlier
key as a softmax layer does. Everything here is in logical positions,
counted from a row's first real token.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from pathway_tpu.models.config import SparseSpec
from pathway_tpu.models.layers import Array, Kind, attend, summed
from pathway_tpu.models.mixers import softmax
from pathway_tpu.models.mixers.softmax import project


def pool_keys(k: Array, sq: SparseSpec) -> Array:
    """The pooled keys of rows k [b, kv heads, s, dh] that start at logical
    position 0: [b, kv heads, s / stride, dh] (s rounded up to whole
    blocks), pooled key i the mean of
    positions stride i .. stride i + kernel - 1 (a window that runs past
    the end takes zeros there: no query can see it yet). Summed in float32,
    kept in k's dtype."""
    b, hk, s, dh = k.shape
    k = jnp.pad(k, ((0, 0), (0, 0), (0, -s % sq.block), (0, 0)))
    n = k.shape[2] // sq.stride
    part = k.astype(jnp.float32).reshape(
        b, hk, n, sq.stride, dh
    ).sum(axis=3)
    whole = -(-sq.kernel // sq.stride)  # strides a window spans
    part = jnp.pad(part, ((0, 0), (0, 0), (0, whole - 1), (0, 0)))
    total = sum(part[:, :, j:j + n] for j in range(whole))
    return (total / sq.kernel).astype(k.dtype)


def select_blocks(q: Array, pooled: Array, t: Array, dense: Array,
                  sq: SparseSpec) -> Array:
    """The blocks each query attends: q [b, nq, kv heads, group, dh], pooled
    [b, kv heads, n_pool, dh], t [b, nq] the queries' logical positions,
    dense [b] or [b, nq] the rows that attend every earlier position ->
    [b, kv heads, nq, n_pool * stride / block] bool, one set for a group.

    A query sees pooled key i when the whole window lies at or before it;
    its relevance is the softmax over the pooled keys it sees, summed over
    the group's heads; a block's score is the largest relevance among the
    pooled keys that overlap it. The first `init_blocks` blocks and the
    `local_blocks` that end in the query's own are always taken, then the
    best others up to `topk`, or every block at or before the query where
    those are fewer. Scores from the inputs' dtype with float32
    accumulation, the softmax in float32."""
    b, nq, hk, g, dh = q.shape
    n_pool = pooled.shape[2]
    m = sq.block // sq.stride  # pooled keys that start in a block
    before = -(-sq.kernel // sq.stride) - 1  # and those that reach in from the last
    nb = n_pool // m
    scores = jnp.einsum(
        "bqkgd,bkid->bkgqi", q, pooled, preferred_element_type=jnp.float32
    ) / math.sqrt(dh)
    ends = sq.stride * jnp.arange(n_pool) + sq.kernel - 1
    seen = (ends[None, None, :] <= t[:, :, None])[:, None, None]  # [b, 1, 1, nq, n_pool]
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    relevance = jnp.sum(jnp.where(seen, probs, 0.0), axis=2)  # [b, kv heads, nq, n_pool]
    by_block = relevance.reshape(b, hk, nq, nb, m)
    score = by_block.max(axis=-1)
    if before:
        reach = by_block[..., m - before:].max(axis=-1)
        score = jnp.maximum(
            score, jnp.pad(reach[..., :-1], ((0, 0), (0, 0), (0, 0), (1, 0)))
        )
    own = (t // sq.block)[:, None, :, None]  # [b, 1, nq, 1]
    blk = jnp.arange(nb)
    visible = blk <= own
    forced = (blk < sq.init_blocks) | (blk > own - sq.local_blocks)
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(visible, score, -jnp.inf)
    # a block's rank among the scores, counted and not sorted (a `top_k` of
    # 384 scores a query was a sort of 80 ms a 24k-token prefill on a v5e);
    # of equal scores, which neighbouring blocks share with the pooled key
    # that reaches from one into the next, the lower block goes first
    mine, theirs = score[..., :, None], score[..., None, :]
    ahead = (theirs > mine) | ((theirs == mine) & (blk[None, :] < blk[:, None]))
    chosen = (jnp.sum(ahead, axis=-1, dtype=jnp.int32) < sq.topk) & visible
    every = jnp.reshape(dense, (b, 1, -1, 1))
    return jnp.where(every, visible, chosen)


def _keys_of_blocks(blocks: Array, at: Array, sq: SparseSpec) -> Array:
    """blocks [b, kv heads, nq, nb] -> whether each query may read the key at
    logical position at [b, s] (negative: no key): [b, kv heads, nq, s]."""
    nb = blocks.shape[-1]
    idx = jnp.clip(at // sq.block, 0, nb - 1)[:, None, None, :]
    idx = jnp.broadcast_to(idx, blocks.shape[:3] + idx.shape[-1:])
    return jnp.take_along_axis(blocks, idx, axis=-1) & (at >= 0)[:, None, None, :]


# a prefill's selection scores [heads, queries, pooled keys] are float32:
# at 24,576 tokens 4.8 GB for the whole prompt. The queries go through in
# chunks whose scores stay under this; ops/sparse_attention.py's kernel,
# which keeps them in VMEM, takes the same chunks, so that the selection is
# the prefill's one loop either way
_SELECT_SCORE_BYTES = 256 << 20


def sparse_prefill_uses_kernel(cfg: Any, width: int) -> bool:
    """Whether the sparse layers of a prefill `width` wide run
    ops/sparse_attention.py's two kernels, `sparse_select` for the blocks
    each query chooses (the set `select_blocks` gives, its scores kept in
    VMEM) and `sparse_prefill_attention` over those blocks: where
    `prefill_uses_kernel` holds, at a width past `dense_len` (up to it they
    run `prefill_attention` as every softmax layer does and choose
    nothing), for a decoder that has such layers. Elsewhere, and in every
    step, `select_blocks` chooses."""
    return (
        bool(cfg.n_mixer_layers("sparse"))
        and width > cfg.sparse.dense_len
        and softmax.prefill_uses_kernel(cfg, width)
    )


def sparse_step_uses_kernel(cfg: Any) -> bool:
    """Whether a step's sparse layers run ops/sparse_attention.py
    `sparse_decode_attention`, which fetches only the tiles that hold a
    block the query chose and writes the step's row on its way, and not the
    plain `attend` over all of the slot's rows under the selection's mask:
    where `step_uses_kernel` holds, for a decoder with such layers whose
    rows are whole tiles of `sparse_decode_tile` (a tile's rows a multiple
    of a packed sublane tile)."""
    if not cfg.n_mixer_layers("sparse") or not softmax.step_uses_kernel(cfg):
        return False
    from pathway_tpu.ops.sparse_attention import sparse_decode_tile

    sq = cfg.sparse
    tile = sparse_decode_tile(sq.block, sq.topk, sq.dense_len)
    return cfg.max_len % tile == 0 and tile % 16 == 0


class Sparse(Kind):
    name = "sparse"
    # summed over real queries, key heads and sparse layers: the blocks a
    # query attended, and the blocks at or before it
    prefill_counters = step_counters = summed(
        "sparse_blocks_read", "sparse_blocks_visible"
    )

    def cache(self, cfg, n, batch):
        hk, dh = cfg.kv_heads, cfg.head_dim
        rows = jax.ShapeDtypeStruct((n, batch, hk, cfg.max_len, dh), cfg.dtype)
        return {
            "k_sparse": rows, "v_sparse": rows,
            "k_pool": jax.ShapeDtypeStruct(
                (n, batch, hk, cfg.max_len // cfg.sparse.stride, dh), cfg.dtype
            ),
        }

    def prefill(self, xin, block, spec, li, rows):
        """Keys, values and pooled keys into the layer's leaves at their
        logical positions, and each query over the blocks it chooses (every
        earlier key where the prompt is no longer than `dense_len`, which a
        width under it settles when traced). `mask`: the causal mask of the
        plain path, None where the kernels run."""
        cfg, cache, valid, pos_idx, counters = (
            rows.cfg, rows.cache, rows.valid, rows.pos, rows.counters
        )
        q, k, v = project(xin, block, spec, rows, self.heads(cfg))
        mask = None if softmax.prefill_uses_kernel(cfg, rows.width) else rows.mask
        sq = cfg.sparse
        b, p, h, dh = q.shape
        hk = k.shape[2]
        kname, vname, pname = "k_sparse", "v_sparse", "k_pool"
        n = jnp.sum(valid, axis=1).astype(jnp.int32)  # real tokens of each row
        with jax.named_scope("cache_write"):
            # head-major, and each row's first real token in row 0: the pad
            # goes behind the prompt, where every step writes over it
            turn = jax.vmap(lambda a, by: jnp.roll(a, by, axis=1))
            kt = turn(k.transpose(0, 2, 1, 3), n - p)
            vt = turn(v.transpose(0, 2, 1, 3), n - p)
            cache[kname] = jax.lax.dynamic_update_slice(
                cache[kname], kt[None], (li, 0, 0, 0, 0)
            )
            cache[vname] = jax.lax.dynamic_update_slice(
                cache[vname], vt[None], (li, 0, 0, 0, 0)
            )
        real = valid.astype(bool)
        own = jnp.where(real, pos_idx // sq.block + 1, 0)  # blocks at or before each query
        with jax.named_scope("attn"), jax.named_scope("attn_sparse"):
            with jax.named_scope("pool"):
                pooled = pool_keys(kt, sq)
                cache[pname] = jax.lax.dynamic_update_slice(
                    cache[pname], pooled[None], (li, 0, 0, 0, 0)
                )
            counters["sparse_blocks_visible"].append(hk * jnp.sum(own, dtype=jnp.int32))
            if p <= sq.dense_len:
                counters["sparse_blocks_read"].append(hk * jnp.sum(own, dtype=jnp.int32))
                if mask is None:
                    from pathway_tpu.ops.attention import prefill_attention

                    return prefill_attention(q, k, v, valid, None)
                return attend(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), mask, cfg)
            with jax.named_scope("select"):
                select = select_blocks
                if mask is None:
                    # the same set from ops/sparse_attention.py's kernel, which
                    # keeps the scores in VMEM: imported where it is traced
                    from pathway_tpu.ops.sparse_attention import sparse_select

                    select = sparse_select
                chunk = p
                while chunk > 128 and chunk % 2 == 0 and (
                    4 * b * h * chunk * pooled.shape[2] > _SELECT_SCORE_BYTES
                ):
                    chunk //= 2
                qg = q.reshape(b, p // chunk, chunk, hk, h // hk, dh)
                tq = jnp.where(real, pos_idx, -1).reshape(b, p // chunk, chunk)
                blocks = jax.lax.map(
                    lambda qt: select(qt[0], pooled, qt[1], n <= sq.dense_len, sq),
                    (qg.transpose(1, 0, 2, 3, 4, 5), tq.transpose(1, 0, 2)),
                )  # [chunks, b, kv heads, chunk, blocks]
                blocks = blocks.transpose(1, 2, 0, 3, 4).reshape(b, hk, p, -1)
                counters["sparse_blocks_read"].append(
                    jnp.sum(blocks & real[:, None, :, None], dtype=jnp.int32)
                )
            if mask is None:
                from pathway_tpu.ops.sparse_attention import sparse_prefill_attention

                return sparse_prefill_attention(q, k, v, valid, blocks, sq.block)
            at = jnp.where(real, pos_idx, -1)  # a key's logical position
            ok = _keys_of_blocks(blocks, at, sq) & mask
            return attend(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), ok, cfg)

    def step(self, xin, block, spec, li, rows):
        """Every row at its logical position t [b]: the key and value go
        into row t of the slot, the pooled key whose window the row
        completes (or has completed, up to stride - 1 steps ago: the same
        rows, the same mean) is written again, and the query attends the
        blocks it chooses among those at or before t."""
        cfg, cache, live, counters = rows.cfg, rows.cache, rows.live, rows.counters
        q, k, v = project(xin, block, spec, rows, self.heads(cfg))
        t = rows.pos[:, 0]
        sq = cfg.sparse
        b, hk = q.shape[0], cfg.kv_heads
        slots, heads = jnp.arange(b)[:, None], jnp.arange(hk)[None, :]
        kname, vname, pname = "k_sparse", "v_sparse", "k_pool"
        with jax.named_scope("attn"), jax.named_scope("attn_sparse"):
            with jax.named_scope("pool"):
                # the newest pooled key whose window ends at or before t; before
                # the first has ended this writes one nobody sees yet. The
                # window's rows from the leaf, the step's own key among them
                newest = jnp.maximum(t - (sq.kernel - 1), 0) // sq.stride
                first = newest * sq.stride
                # (a slice a slot out of the stacked leaf itself: batched, the
                # slices become a gather for which the compiler relays the leaf)
                window = jnp.concatenate([
                    jax.lax.dynamic_slice(
                        cache[kname], (li, slot, 0, first[slot], 0),
                        (1, 1, hk, sq.kernel, cfg.head_dim),
                    )[0] for slot in range(b)
                ])  # [b, kv heads, kernel, dh]
                own = (first[:, None] + jnp.arange(sq.kernel) == t[:, None])
                window = jnp.where(own[:, None, :, None], k[:, 0][:, :, None, :], window)
                mean = jnp.mean(window.astype(jnp.float32), axis=2).astype(cfg.dtype)
                cache[pname] = cache[pname].at[li, slots, heads, newest[:, None]].set(mean)
            with jax.named_scope("select"):
                blocks = select_blocks(
                    q.reshape(b, 1, hk, -1, cfg.head_dim), cache[pname][li],
                    t[:, None], t < sq.dense_len, sq,
                )
                seen = live[:, :, None, None]
                counters["sparse_blocks_read"].append(jnp.sum(blocks & seen, dtype=jnp.int32))
                counters["sparse_blocks_visible"].append(
                    hk * jnp.sum(jnp.where(live[:, 0], t // sq.block + 1, 0), dtype=jnp.int32)
                )
            if sparse_step_uses_kernel(cfg):
                # imported where it is traced: Pallas loads when a program
                # first needs it
                from pathway_tpu.ops.sparse_attention import (
                    sparse_decode_attention, sparse_decode_tile,
                )

                ctx, cache[kname], cache[vname] = sparse_decode_attention(
                    q[:, 0], k[:, 0], v[:, 0], cache[kname], cache[vname], li, t,
                    blocks[:, :, 0], block=sq.block,
                    tile=sparse_decode_tile(sq.block, sq.topk, sq.dense_len),
                    steps=sq.topk,
                )
                return ctx[:, None]
        with jax.named_scope("cache_write"):
            cache[kname] = cache[kname].at[li, slots, heads, t[:, None]].set(k[:, 0])
            cache[vname] = cache[vname].at[li, slots, heads, t[:, None]].set(v[:, 0])
        with jax.named_scope("attn"), jax.named_scope("attn_sparse"):
            at = jnp.broadcast_to(jnp.arange(cfg.max_len)[None, :], (b, cfg.max_len))
            ok = _keys_of_blocks(blocks, at, sq) & (at <= t[:, None])[:, None, None, :]
            return attend(q, cache[kname][li], cache[vname][li], ok, cfg)


SPARSE = Sparse()
