"""The sparse mixer's prefill attention as a Pallas kernel: causal softmax
attention of whole prompts to themselves in which every query reads only the
blocks of keys it chose (models/mixers/sparse.py `select_blocks`), one set for
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

The chosen blocks themselves come from `sparse_select` below, the
selection's arithmetic kept in VMEM, and a step's selected-block attention
from `sparse_decode_attention`.
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
    accumulation, softmax in float32, as models/layers.py `attend`
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


# ------------------------------------------------- the prefill's selection
#
# Which blocks each query of a prefill attends, models/mixers/sparse.py
# `select_blocks` as one kernel. Written in XLA, its float32 scores [heads,
# queries, pooled keys] cross HBM several times and its ranking counts every
# pair of blocks (PERF.md section 5: 49.8 ms of a 24k-token prefill on a
# v5e). A grid step here holds a tile of queries, its key head's pooled keys
# and everything between them in VMEM, and finds the topk-th score by
# bisection. Queries are lanes; pooled keys, and the blocks, are sublanes.

_SELECT_TILE = 256  # queries a grid step holds, where the chunk has them
_SELECT_KEY_BLOCKS = 64  # blocks of pooled keys a pass over them takes


def _select_kernel(need_ref, dense_ref, q_ref, pool_ref, t_ref, o_ref,
                   s_ref, rel_ref, score_ref, reach_ref,
                   *, group: int, dh: int, m: int, before: int, bc: int,
                   sq):
    """One grid step (row, key head, query tile): the blocks each of the
    tile's queries chooses, [tq, blocks] 1/0. The pooled keys come in chunks
    of `bc` blocks, and within a chunk the j-th pooled key of every block lies
    in rows j * bc .. (j + 1) * bc - 1, so that a block's score is the
    maximum of m slices. Only the chunks that the tile's last query sees into
    (`need`) are scored."""
    bi, qi = pl.program_id(0), pl.program_id(2)
    need = need_ref[bi * pl.num_programs(2) + qi]
    t = t_ref[0]  # [1, tq] the queries' logical positions, -1 a pad
    tq = t.shape[1]
    rows = m * bc
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    # the pooled key of a chunk's row r, counted from the chunk's first
    within = (r & (bc - 1)) * m + (r >> (bc.bit_length() - 1))
    rel_ref[...] = jnp.zeros(rel_ref.shape, jnp.float32)

    def seen(c):
        return sq.stride * (c * rows + within) + sq.kernel - 1 <= t

    def chunk(c):
        return pl.ds(pl.multiple_of(c * rows, rows), rows)

    # as `select_blocks`: each head's softmax over the pooled keys it sees,
    # from scores in float32 divided by sqrt(dh), summed over the group
    def head(hh, carry):
        qh = q_ref[0, :, pl.ds(pl.multiple_of(hh * dh, 128), dh)]  # [tq, dh]

        def scores(c, mx):
            s = jax.lax.dot_general(
                pool_ref[0, 0, chunk(c), :], qh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) / math.sqrt(dh)
            s = jnp.where(seen(c), s, _MASKED)
            s_ref[chunk(c), :] = s
            return jnp.maximum(mx, jnp.max(s, axis=0, keepdims=True))

        mx = jax.lax.fori_loop(
            0, need, scores, jnp.full((1, tq), -jnp.inf, jnp.float32)
        )

        def exps(c, total):
            e = jnp.exp(s_ref[chunk(c), :] - mx)
            s_ref[chunk(c), :] = e
            return total + jnp.sum(e, axis=0, keepdims=True)

        total = jax.lax.fori_loop(0, need, exps, jnp.zeros((1, tq), jnp.float32))

        def relevance(c, carry):
            rel_ref[chunk(c), :] += jnp.where(seen(c), s_ref[chunk(c), :] / total, 0.0)
            return carry

        return jax.lax.fori_loop(0, need, relevance, carry)

    jax.lax.fori_loop(0, group, head, 0)
    # a block's score: the largest relevance among the pooled keys that
    # start in it and those that reach in from the block before
    for c in range(score_ref.shape[0] // bc):
        part = [rel_ref[c * rows + j * bc:c * rows + (j + 1) * bc, :] for j in range(m)]
        score_ref[c * bc:(c + 1) * bc, :] = functools.reduce(jnp.maximum, part)
        if before:
            reach_ref[c * bc:(c + 1) * bc, :] = functools.reduce(
                jnp.maximum, part[m - before:]
            )
    nb = score_ref.shape[0]
    blk = jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    score = score_ref[...]
    if before:
        reach = pltpu.roll(reach_ref[...], 1, 0)  # the block before's
        score = jnp.maximum(score, jnp.where(blk == 0, 0.0, reach))
    visible = blk * sq.block <= t
    forced = (blk < sq.init_blocks) | ((blk + sq.local_blocks) * sq.block > t)
    score = jnp.where(visible, jnp.where(forced, jnp.inf, score), -jnp.inf)
    # the scores as int32 in the same order, and the topk-th largest of each
    # query's by bisection over them, from the sign bit down
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)
    key = bits ^ ((bits >> 31) & 0x7FFFFFFF)

    def count(at_least):
        return jnp.sum((key >= at_least).astype(jnp.int32), axis=0, keepdims=True)

    def bisect(i, at):
        up = at | (jnp.int32(1) << (30 - i))
        return jnp.where(count(up) >= sq.topk, up, at)

    kth = jax.lax.fori_loop(0, 31, bisect, jnp.where(
        count(0) >= sq.topk, 0, jnp.iinfo(jnp.int32).min
    ))
    # every block above it, and of those equal to it the lowest: a running
    # count of the equal ones along the blocks, as one product
    above, tie = key > kth, key == kth
    lower = (
        jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 1)
        < jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
    ).astype(jnp.float32).astype(jnp.bfloat16)
    tied_before = jnp.dot(
        lower, tie.astype(jnp.float32).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    ahead = jnp.sum(above.astype(jnp.float32), axis=0, keepdims=True)
    chosen = (above | (tie & (ahead + tied_before < sq.topk))) & visible
    chosen = chosen | (visible & (dense_ref[bi] != 0))  # a dense row: all it sees
    o_ref[0, 0] = chosen.astype(jnp.int32).T


def _select_vmem(tq: int, group: int, dh: int, n_pool: int, nb: int,
                 itemsize: int) -> int:
    """VMEM of one grid step of `sparse_select`, in bytes: its operands and
    result double-buffered, its four scratch arrays, and what the ranking
    holds at once (the blocks' keys, masks and running counts, and the
    triangle of the count of ties)."""
    io = 2 * (tq * group * dh * itemsize + n_pool * dh * itemsize + tq * 4 + tq * nb * 4)
    scratch = 2 * n_pool * tq * 4 + 2 * nb * tq * 4
    live = 6 * nb * tq * 4 + nb * nb * 6
    return io + scratch + live


@functools.partial(jax.jit, static_argnames=("sq", "key_blocks", "interpret"))
def sparse_select(
    q: jax.Array,  # [b, nq, kv heads, group, dh]
    pooled: jax.Array,  # [b, kv heads, n_pool, dh]: models/mixers/sparse.py `pool_keys`
    t: jax.Array,  # [b, nq] int32: the queries' logical positions, -1 a pad
    dense: jax.Array,  # [b] bool: the rows that attend every earlier position
    sq,  # models/config.py `SparseSpec`
    *,
    key_blocks: int = _SELECT_KEY_BLOCKS,
    interpret: bool = False,
) -> jax.Array:
    """models/mixers/sparse.py `select_blocks` as one kernel: the blocks each
    query attends, [b, kv heads, nq, n_pool * stride / block] bool, one set
    for a group, by the same arithmetic up to the ranking (scores from the
    inputs' dtype with float32 accumulation divided by sqrt(dh), the softmax
    over the pooled keys a query sees in float32, summed over the group's
    heads; a block's score the largest of its pooled keys' and the one that
    reaches in; init and local blocks first, nothing after the query's own)
    and the same set: the `topk` best, of equal scores the lower block. The
    sums run in another order, so a block whose score is within float32
    rounding of the topk-th may fall the other way.

    No sort and no count over pairs of blocks: the topk-th score of each
    query is found by bisection over an int32 key of the same order (32
    passes of compare and count over [blocks, tile]), every block above it
    is taken, and of those equal to it the lowest up to `topk`. A grid step
    scores only the chunks of `key_blocks` blocks that the tile's last query
    sees into (the causal half). dh must be a multiple of 128; queries and
    blocks are padded inside to whole lane tiles."""
    b, nq0, hk, group, dh = q.shape
    n_pool = pooled.shape[2]
    m = sq.block // sq.stride  # pooled keys that start in a block
    before = -(-sq.kernel // sq.stride) - 1  # and those that reach in from the last
    nb0 = n_pool // m
    bc = key_blocks
    if dh % 128 or 128 % bc or bc * m % 16:
        raise ValueError(f"sparse_select needs heads of a multiple of 128 lanes "
                         f"and chunks of whole tiles, got {dh} and {bc} x {m}")
    nb = -(-nb0 // 128) * 128
    rows = m * bc
    # blocks past the prompt's: zero keys that nobody sees, in no query's set
    pooled = jnp.pad(pooled, ((0, 0), (0, 0), (0, nb * m - n_pool), (0, 0)))
    pooled = pooled.reshape(b, hk, nb // bc, bc, m, dh).transpose(0, 1, 2, 4, 3, 5)
    pooled = pooled.reshape(b, hk, nb * m, dh)
    extra = -nq0 % 128
    q = q.reshape(b, nq0, hk * group * dh)
    t = t.astype(jnp.int32)
    if extra:
        q = jnp.pad(q, ((0, 0), (0, extra), (0, 0)))
        t = jnp.pad(t, ((0, 0), (0, extra)), constant_values=-1)
    nq = nq0 + extra
    tq = _SELECT_TILE if nq % _SELECT_TILE == 0 else 128
    n = nq // tq
    # the chunks of pooled keys whose first window ends at or before the
    # tile's last query
    last = jnp.max(t.reshape(b, n, tq), axis=2)
    need = jnp.clip(
        (last - (sq.kernel - 1)) // (sq.stride * rows) + 1, 0, nb // bc
    ).astype(jnp.int32)
    vmem = _select_vmem(tq, group, dh, nb * m, nb, q.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(
            _select_kernel, group=group, dh=dh, m=m, before=before, bc=bc, sq=sq,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hk, n),
            in_specs=[
                pl.BlockSpec((1, tq, group * dh), lambda bi, j, qi, *_: (bi, qi, j)),
                pl.BlockSpec((1, 1, nb * m, dh), lambda bi, j, qi, *_: (bi, j, 0, 0)),
                pl.BlockSpec((1, 1, tq), lambda bi, j, qi, *_: (bi, 0, qi)),
            ],
            out_specs=pl.BlockSpec((1, 1, tq, nb), lambda bi, j, qi, *_: (bi, j, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((nb * m, tq), jnp.float32),  # one head's scores
                pltpu.VMEM((nb * m, tq), jnp.float32),  # the group's relevance
                pltpu.VMEM((nb, tq), jnp.float32),  # the blocks' own maxima
                pltpu.VMEM((nb, tq), jnp.float32),  # what reaches into the next
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, nq, nb), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=2 * max(_PREFILL_VMEM, vmem),
        ),
        name="sparse_select",
        interpret=interpret,
    )(
        need.reshape(b * n), jnp.reshape(dense, (b,)).astype(jnp.int32),
        q, pooled, t.reshape(b, 1, nq),
    )
    return out[:, :, :nq0, :nb0] != 0


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
