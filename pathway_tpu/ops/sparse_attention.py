"""The sparse mixer's prefill attention as a Pallas kernel: causal softmax
attention of whole prompts to themselves in which every query reads only the
blocks of keys it chose (models/transformer.py `select_blocks`), one set for
the query heads that share a key head.

It shares with `ops/attention.py prefill_attention` what is one thing: the
tile (`prefill_tile`), the streaming softmax's start and finish, and the
tile body `_fold_key_tile`, which takes a key tile's scores for the group's
heads to the running maximum, sum and accumulator. Its own are one more
operand, the chosen blocks [b, kv heads, p, blocks], and the element mask
it hands that body: causal order, the keys' validity and, made once a tile
pair for the whole group, the product of the queries' chosen blocks with
the keys' one-hot blocks. With every block chosen it returns what
`prefill_attention` returns, bit for bit. A tile pair in which no query
chose a block is still computed under its mask, and at the kernel's tile
nothing is lost by that: a query takes its blocks where its own scores
send it, so among 256 neighbouring queries every key tile under the
diagonal is somebody's (PERF.md section 5: 0 of 4,278 tile pairs empty at
24k tokens). What the kernel costs is its rate, and that is the body's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.ops.attention import (
    _MASKED, _PREFILL_VMEM, _finish_softmax, _fold_key_tile, _prefill_vmem,
    _start_softmax, _written_rows, prefill_tile,
)


def _sparse_prefill_kernel(first_ref, held_ref, pad_ref, q_ref, k_ref, v_ref,
                           valid_ref, blocks_ref, o_ref, m_ref, l_ref, acc_ref,
                           *, t: int, group: int, dh: int, block: int,
                           scale: float):
    """One grid step (row, key head, query tile, k-th needed key tile): the
    group's query tile [t, group * dh] against one key tile [t, dh], under
    the causal mask, the keys' validity and the blocks each query chose."""
    bi, qi, kk = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    kt = first_ref[bi] + kk  # the key tile
    held = held_ref[bi * pl.num_programs(2) + jnp.minimum(kt, qi)]  # its valid keys
    q0, k0 = qi * t, kt * t

    @pl.when(kk == 0)
    def _start():
        _start_softmax(m_ref, l_ref, acc_ref)

    # past the diagonal the clamped tile is not run again; a tile with no
    # valid key is not run at all
    @pl.when((kt <= qi) & (held > 0))
    def _fold():
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        # a key's block, counted from the row's first real token, as a
        # one-hot column: the queries' chosen blocks times it say which
        # queries chose the key's block
        nb = blocks_ref.shape[3]
        of_key = jax.lax.div(
            jnp.maximum(k0 - pad_ref[bi] + jax.lax.broadcasted_iota(
                jnp.int32, (nb, t), 1), 0), block,
        )
        one_hot = (
            jax.lax.broadcasted_iota(jnp.int32, (nb, t), 0) == of_key
        ).astype(blocks_ref.dtype)
        chosen = jnp.dot(
            blocks_ref[0, 0], one_hot, preferred_element_type=jnp.float32
        ) > 0.5
        # a query none of whose chosen keys has come yet is wiped by its
        # first (`_fold_key_tile`), and every real query chose its own
        # block, on the diagonal
        _fold_key_tile(
            q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
            (kpos <= qpos) & (valid_ref[0] != 0) & chosen,
            group=group, dh=dh, scale=scale,
        )

    @pl.when(kk == pl.num_programs(3) - 1)
    def _finish():
        _finish_softmax(o_ref, l_ref, acc_ref, group=group, dh=dh)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sparse_prefill_attention(
    q: jax.Array,  # [b, p, heads, dh]
    k: jax.Array,  # [b, p, kv heads, dh]
    v: jax.Array,  # [b, p, kv heads, dh]
    valid: jax.Array,  # [b, p] 1/0: the keys that are real (a LEFT pad is not)
    blocks: jax.Array,  # [b, kv heads, p, n blocks] bool: what each query chose
    block: int,  # positions a block holds
    *,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of whole prompts to themselves over chosen blocks:
    query i of a row attends the valid keys j <= i whose block, (j - left
    pad) // `block`, is set in `blocks[row, key head, i]`. Returns the
    context [b, p, heads * dh]. Products in the inputs' dtype with float32
    accumulation, softmax in float32, as models/transformer.py `_attend`
    states them; the order of the sums is another. Every real query has
    to have chosen its own block (the selection's local blocks see to it); a
    query with no key to attend (a row of the padding) returns some finite
    vector.

    The inputs are read as `_qkv` leaves them, and tiles, grid and the
    skipped tiles of the left pad are `prefill_attention`'s (dh a multiple
    of 128; a width that is none of 128 padded at the end). The mask of a
    tile pair is one product [t, blocks] x [blocks, t] of the queries'
    chosen blocks with the keys' one-hot blocks, shared by the group."""
    b, p0, h, dh = q.shape
    hk = k.shape[2]
    group = h // hk
    if dh % 128 or h % hk:
        raise ValueError(f"sparse_prefill_attention needs heads of a multiple of "
                         f"128 lanes in whole groups, got {h} x {dh} over {hk}")
    extra = -p0 % 128
    if extra:
        q, k, v = (jnp.pad(a, ((0, 0), (0, extra), (0, 0), (0, 0))) for a in (q, k, v))
        valid = jnp.pad(valid, ((0, 0), (0, extra)))
        blocks = jnp.pad(blocks, ((0, 0), (0, 0), (0, extra), (0, 0)))
    p = p0 + extra
    nb = blocks.shape[3]
    # lanes of the chosen blocks: a whole number of lane tiles
    blocks = jnp.pad(
        blocks, ((0, 0), (0, 0), (0, 0), (0, -nb % 128))
    ).astype(q.dtype)
    t = prefill_tile(p, dh, group, q.dtype.itemsize)
    n = p // t
    valid = valid.astype(jnp.int32)
    held = jnp.sum(valid.reshape(b, n, t), axis=2)  # valid keys of each key tile
    first = jnp.argmax(held > 0, axis=1).astype(jnp.int32)
    pad = jnp.argmax(valid > 0, axis=1).astype(jnp.int32)  # the left pad's length

    def q_block(bi, j, qi, kk, first_ref, held_ref, pad_ref):
        return bi, qi, j

    def key_tile(bi, qi, kk, first_ref):
        return jnp.minimum(first_ref[bi] + kk, qi)

    def k_block(bi, j, qi, kk, first_ref, held_ref, pad_ref):
        return bi, key_tile(bi, qi, kk, first_ref), j

    def valid_block(bi, j, qi, kk, first_ref, held_ref, pad_ref):
        return bi, 0, key_tile(bi, qi, kk, first_ref)

    def chosen_block(bi, j, qi, kk, first_ref, held_ref, pad_ref):
        return bi, j, qi, 0

    vmem = _prefill_vmem(t, dh, group, q.dtype.itemsize) + (
        # the chosen blocks double-buffered, the one-hot and the mask
        2 * 2 * t * blocks.shape[3] * q.dtype.itemsize + 2 * t * t * 4
    )
    out = pl.pallas_call(
        functools.partial(
            _sparse_prefill_kernel, t=t, group=group, dh=dh, block=block,
            scale=1.0 / math.sqrt(dh),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hk, n, n),
            in_specs=[
                pl.BlockSpec((1, t, group * dh), q_block),
                pl.BlockSpec((1, t, dh), k_block),
                pl.BlockSpec((1, t, dh), k_block),
                pl.BlockSpec((1, 1, t), valid_block),
                pl.BlockSpec((1, 1, t, blocks.shape[3]), chosen_block),
            ],
            out_specs=pl.BlockSpec((1, t, group * dh), q_block),
            scratch_shapes=[
                pltpu.VMEM((group, t, 128), jnp.float32),  # running maximum
                pltpu.VMEM((group, t, 128), jnp.float32),  # running sum
                pltpu.VMEM((t, group * dh), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, p, h * dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=2 * max(_PREFILL_VMEM, vmem),
        ),
        name="sparse_prefill_attention",
        interpret=interpret,
    )(
        first, held.reshape(b * n), pad,
        q.reshape(b, p, h * dh), k.reshape(b, p, hk * dh), v.reshape(b, p, hk * dh),
        valid.reshape(b, 1, p), blocks,
    )
    return out[:, :p0] if extra else out


# ------------------------------------------------- decode-step attention
#
# A step's query reads the blocks it chose and nothing else: of a slot's
# 24k rows the 4,096 of 64 blocks. The leaf's rows are at logical positions,
# so a block of the selection is a block of rows, and the kernel below is
# `ops/attention.py decode_attention` with the tiles to fetch taken from a
# table in scalar memory instead of counted up from the first live one.


def _sparse_decode_kernel(layer_ref, t_ref, count_ref, tiles_ref, chosen_ref,
                          q_ref, kn_ref, vn_ref, k_ref, v_ref, o_ref, ko_ref,
                          vo_ref, m_ref, l_ref, acc_ref,
                          *, tile: int, block: int, nb: int, sub: int,
                          scale: float):
    """One grid step (slot, key head, kk-th tile the head's query needs): the
    group's query heads [group, dh] against a tile [tile, dh] of the key
    head's keys and values, under the mask of the blocks it chose."""
    del layer_ref  # the index maps' business
    si, hi, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    head = si * pl.num_programs(1) + hi
    t = t_ref[si]  # the row of the step's own key
    kt = tiles_ref[head * pl.num_programs(2) + jnp.minimum(kk, count_ref[head] - 1)]

    @pl.when(kk == 0)
    def _start():
        # the step's own key is the first the softmax meets: it comes
        # from the operands, for it is in no tile of the leaf yet
        own = jnp.sum(
            q_ref[...].astype(jnp.float32) * kn_ref[...].astype(jnp.float32),
            axis=1, keepdims=True,
        ) * scale  # [group, 1]
        m_ref[...] = jnp.broadcast_to(own, m_ref.shape)
        l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.broadcast_to(
            vn_ref[...].astype(jnp.float32), acc_ref.shape
        )

    # past the last tile of the table the clamped tile is not run again
    @pl.when(kk < count_ref[head])
    def _fold():
        k, v = k_ref[...], v_ref[...]
        s = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [group, tile]
        inside = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        row = kt * tile + inside
        # a row is a key if its block was chosen and it lies before the
        # step's own row, which still holds what the slot's last request
        # left (the step's own key came from the operands)
        ok = jnp.zeros((1, tile), jnp.bool_)
        for j in range(tile // block):
            taken = chosen_ref[head * nb + kt * (tile // block) + j] != 0
            ok = ok | ((inside >= j * block) & (inside < (j + 1) * block) & taken)
        ok = ok & (row < t)
        s = jnp.where(ok, s, _MASKED)
        m_prev = m_ref[...]  # [group, 128], every lane the same
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(e, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            e.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    # the tile of the step's own row is always in the table (a query's own
    # block is chosen): the few rows around it go back to the leaf with the
    # new row among them
    @pl.when((kt == jax.lax.div(t, tile)) & (kk < count_ref[head]))
    def _write():
        at = jax.lax.div(t - kt * tile, sub) * sub
        mine = kt * tile + at + jax.lax.broadcasted_iota(
            jnp.int32, (sub, 1), 0
        ) == t
        for new_ref, old_ref, out_ref in (
            (kn_ref, k_ref, ko_ref), (vn_ref, v_ref, vo_ref)
        ):
            old = old_ref[pl.ds(pl.multiple_of(at, sub), sub), :]
            out_ref[...] = jnp.where(mine, new_ref[...], old)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


def sparse_decode_tile(block: int, topk: int, dense_len: int) -> int:
    """Rows a grid step of `sparse_decode_attention` fetches: whole blocks,
    and so many that `topk` tiles hold a dense row's `dense_len` positions
    (two blocks, 128 rows, at InfLLM v2's 64 x 64 and 8,192)."""
    return block * max(1, -(-dense_len // (topk * block)))


@functools.partial(jax.jit, static_argnames=("block", "tile", "steps", "interpret"))
def sparse_decode_attention(
    q: jax.Array,  # [slots, heads, dh]: one token a slot
    k_new: jax.Array,  # [slots, kv heads, dh]: the token's key
    v_new: jax.Array,  # [slots, kv heads, dh]: and value
    k_cache: jax.Array,  # [layers, slots, kv heads, rows, dh]: a whole leaf
    v_cache: jax.Array,  # the same
    layer: jax.Array,  # scalar int32: the layer's index along the leaf
    t: jax.Array,  # [slots] int32: each slot's logical position, its row
    blocks: jax.Array,  # [slots, kv heads, rows / block] bool: chosen blocks
    *,
    block: int,  # positions a block holds
    tile: int,  # rows a grid step fetches (`sparse_decode_tile`)
    steps: int,  # tiles a head's query can need at most: the grid's length
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One step of a sparse layer's attention over the slot cache, read and
    written where it lies: each slot's key and value go into row `t` of its
    slot, and its query attends the rows before `t` of the blocks it chose,
    and its own. Only the tiles that hold a chosen block are fetched (a tile
    is `tile / block` neighbouring blocks, of which the kernel masks those
    not chosen); the leaves are operands and results of one buffer, as in
    `ops/attention.py decode_attention`, whose arithmetic this is. Returns
    (context [slots, heads * dh], k_cache, v_cache).

    `blocks[slot, head]` must hold the block of row `t` (the selection's
    local blocks do) and no more than `steps` tiles. A free slot (t 0)
    writes and attends its row 0. dh must be a multiple of 128."""
    n, h, dh = q.shape
    _, _, hk, rows, _ = k_cache.shape
    group = h // hk
    if dh % 128 or h % hk or rows % tile or tile % block:
        raise ValueError(f"sparse_decode_attention needs heads of a multiple of "
                         f"128 lanes in whole groups and rows in whole tiles, "
                         f"got {h} x {dh} over {hk}, {rows} rows, tiles of {tile}")
    sub = _written_rows(tile, k_cache.dtype.itemsize)
    per_tile = tile // block
    nb = rows // block
    blocks = blocks[:, :, :nb]
    needed = blocks.reshape(n, hk, rows // tile, per_tile).any(axis=-1)
    count = jnp.sum(needed, axis=-1, dtype=jnp.int32)
    # the needed tiles, in order, then the others
    order = jnp.argsort(~needed, axis=-1, stable=True)[..., :steps].astype(jnp.int32)

    def q_block(si, hi, kk, *refs):
        return si, hi, 0, 0

    def k_block(si, hi, kk, layer_ref, t_ref, count_ref, tiles_ref, chosen_ref):
        head = si * hk + hi
        at = tiles_ref[head * steps + jnp.minimum(kk, count_ref[head] - 1)]
        return layer_ref[0], si, hi, at, 0

    def written_block(si, hi, kk, layer_ref, t_ref, *refs):
        return layer_ref[0], si, hi, jax.lax.div(t_ref[si], sub), 0

    leaf = jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype)
    out, k_cache, v_cache = pl.pallas_call(
        functools.partial(
            _sparse_decode_kernel, tile=tile, block=block, nb=nb, sub=sub,
            scale=1.0 / math.sqrt(dh),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n, hk, steps),
            in_specs=[
                pl.BlockSpec((None, None, group, dh), q_block),
                pl.BlockSpec((None, None, 1, dh), q_block),
                pl.BlockSpec((None, None, 1, dh), q_block),
                pl.BlockSpec((None, None, None, tile, dh), k_block),
                pl.BlockSpec((None, None, None, tile, dh), k_block),
            ],
            out_specs=[
                pl.BlockSpec((None, None, group, dh), q_block),
                pl.BlockSpec((None, None, None, sub, dh), written_block),
                pl.BlockSpec((None, None, None, sub, dh), written_block),
            ],
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),  # running maximum
                pltpu.VMEM((group, 128), jnp.float32),  # running sum
                pltpu.VMEM((group, dh), jnp.float32),  # accumulator
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((n, hk, group, dh), q.dtype), leaf, leaf],
        # the leaves are written in place (operands count the five
        # prefetched scalars too)
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="sparse_decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), t.astype(jnp.int32),
        count.reshape(n * hk), order.reshape(n * hk * steps),
        blocks.astype(jnp.int32).reshape(n * hk * nb),
        q.reshape(n, hk, group, dh), k_new.reshape(n, hk, 1, dh),
        v_new.reshape(n, hk, 1, dh), k_cache, v_cache,
    )
    return out.reshape(n, h * dh), k_cache, v_cache
