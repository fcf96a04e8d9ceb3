"""The attention of a latent layer (models/config.py `LatentSpec`:
multi-head latent attention), as Pallas kernels for the TPU.

A latent layer's head scores a query of nope + rope lanes (128 + 64 at the
published widths) against keys as wide and sums values of another width
(128), and what a position keeps is one low-rank row all heads share
(`c_kv`, 512 lanes) and one rotary key (`k_rope`, 64). Neither fits the
kernels of ops/attention.py, whose q, k and v are one width of whole lane
tiles and whose cache leaf is rows of whole heads.

* `latent_prefill_attention`: a prompt's causal attention to its own
  expanded keys and values, on `prefill_attention`'s tile body
  (`_fold_key_tile`) with the scores kept in VMEM. A grid step runs a
  block of heads (`latent_prefill_block`), each against its own key and
  value lanes, so that the body's unrolled loop has one head's softmax to
  run behind another's products, as a group of query heads over one key
  head has in `prefill_attention`. A head's score is two products, its
  128 nope lanes against its own key's and its rotary lanes against the
  one rotary key of the position, which is fetched once a grid step as
  the row the cache keeps and is never repeated for the heads in memory;
  q_nope, k_nope and v are read where their products leave them, and so
  are the query's rotary lanes, which the kernel turns itself, once a
  query tile (models/layers.py `rope`'s arithmetic over a lane tile
  a head).
* `latent_decode_attention`: a step's attention in the absorbed form. A
  slot's heads (their nope lanes already times W_uk: `kv_rank` wide) share
  every latent row, so the whole of a slot's heads meets a tile of `c_kv`
  in one product, adds the rotary part's product with the tile of
  `k_rope`, and sums the tile's latent rows by the softmax's weights. Only
  the tiles that hold a live row of the slot are fetched, out of the
  stacked leaves where they lie.

models/mixers/latent.py `latent_prefill_uses_kernel` and
`latent_step_uses_kernel` say which programs run these.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.ops.attention import (
    _MASKED, _PREFILL_VMEM, _finish_softmax, _fold_key_tile, _prefill_vmem,
    _start_softmax,
)


_HEAD_BLOCK = 8  # heads a grid step of the prefill kernel at most
_BLOCK_TILE = 512  # and the tile of its queries and keys
_BLOCK_VMEM = 24 << 20  # what `latent_prefill_block`'s sum may reach


def latent_prefill_block(p: int, heads: int, dh: int, rope: int,
                         itemsize: int = 2) -> tuple[int, int]:
    """The block of `latent_prefill_attention`: (heads a grid step, the
    tile of queries and of keys) for a width p that 128 divides. A
    function of the shapes alone: the tile is the largest multiple of 128
    that divides p and is at most 512; the heads are the largest count up
    to 8 that divides the layer's heads (8 of 64; 3 of 3; 1 of 11, which
    is the kernel of one head a grid step) and keeps the VMEM sum of a grid
    step (ops/attention.py `_prefill_vmem`, the keys and values `hb` heads
    wide, the rotary lanes beside them) under 24 MB: (8, 512) at
    rag-longcat-flash-omni's (p 10,240, 64 heads of 128 nope lanes, the
    rotary lanes in a tile of 128, bf16: 22.3 MB).
    On the chip (PERF.md section 6, PR 44; the kernel alone at that shape,
    a left pad of 200, ms a call and G scores computed a second), heads x
    tile: 1 x 640 23.94 (149: the kernel as it was, one head a grid step:
    nothing for a head's softmax to hide behind), 1 x 512 23.22, 2 x 512
    20.66, 2 x 640 20.70, 2 x 1024 20.08, 4 x 256 27.14, 4 x 512 18.83,
    4 x 640 19.65, 4 x 1024 19.11, 8 x 256 24.16, **8 x 512 17.92 (197)**,
    8 x 640 18.98, 16 x 256 22.75; with an empty body (grid steps and
    fetches alone) 6.81 at 4 x 512 and 7.04 at 1 x 640. More heads a step
    are worth less each time (-2.6, -1.8, -0.9 ms from 1 to 2 to 4 to 8 at
    512) and cost the compiler their unrolled body twice (3.9 s at 4, 7.1
    at 8); past 512 a tile only adds to what the diagonal wastes, under it
    the step's fixed cost shows."""
    t = max(t for t in range(128, min(p, _BLOCK_TILE) + 1, 128) if p % t == 0)
    fits = [
        n for n in range(1, _HEAD_BLOCK + 1)
        if heads % n == 0 and _prefill_vmem(
            t, dh, n, itemsize, own_keys=True, rope=rope
        ) <= _BLOCK_VMEM
    ]
    return fits[-1] if fits else 1, t


def _prefill_kernel(first_ref, held_ref, q_ref, qr_ref, cos_ref, sin_ref, k_ref,
                    kr_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref,
                    turned_ref, *, t: int, hb: int, dn: int, dv: int, half: int,
                    scale: float):
    """One grid step (row, block of heads, query tile, k-th needed key
    tile): the block's query tiles [t, hb * dn] and their rotary lanes
    [t, hb * dr] against the heads' own key tiles [hb * dn, t], the one
    rotary key tile [t, dr] and the heads' value tiles [hb * dv, t];
    ops/attention.py `_prefill_kernel` without a window, its group a block
    of heads that share no key. The query's rotary lanes come as their
    product left them and are turned here, once a query tile, into
    `turned_ref`."""
    bi, qi, kk = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    kt = first_ref[bi] + kk  # the key tile
    held = held_ref[bi * pl.num_programs(2) + jnp.minimum(kt, qi)]  # its valid keys
    dr = kr_ref.shape[-1]

    @pl.when(kk == 0)
    def _start():
        _start_softmax(m_ref, l_ref, acc_ref)
        # models/layers.py `rope` over a lane tile a head: the tables
        # hold each cosine twice and the sines with their first half
        # negated, zeros behind the rotary lanes, so that
        # [x1 cos - x2 sin, x2 cos + x1 sin] = x cos + partner sin, where a
        # lane's partner is `half` lanes up in the first half and down in
        # the second; float32, rounded once, as `_rope` rounds
        cos, sin = cos_ref[0], sin_ref[0]
        first = jax.lax.broadcasted_iota(jnp.int32, (t, dr), 1) < half
        for g in range(hb):
            lanes = slice(g * dr, (g + 1) * dr)
            x = qr_ref[0, :, lanes].astype(jnp.float32)
            partner = jnp.where(
                first, pltpu.roll(x, dr - half, 1), pltpu.roll(x, half, 1)
            )
            turned_ref[0, :, lanes] = (x * cos + partner * sin).astype(
                turned_ref.dtype
            )

    fold = functools.partial(
        _fold_key_tile, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
        group=hb, dh=dn, scale=scale, dv=dv, own_keys=True,
        rope=(turned_ref, kr_ref),
    )
    # a tile on the diagonal or with a key that is not valid takes an
    # element mask; past the diagonal the clamped tile is not run again, and
    # a tile with no valid key is not run at all
    edge = (kt == qi) | (held < t)
    needed = (kt <= qi) & (held > 0)

    @pl.when(needed & edge)
    def _edge():
        qpos = qi * t + jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        kpos = kt * t + jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        fold((kpos <= qpos) & (valid_ref[0] != 0))

    @pl.when(needed & jnp.logical_not(edge))
    def _inner():
        fold(None)

    @pl.when(kk == pl.num_programs(3) - 1)
    def _finish():
        _finish_softmax(o_ref, l_ref, acc_ref, group=hb, dh=dv)


# jitted so that the layers of one program share one trace of the kernel
# and one lowering to Mosaic (ops/attention.py `prefill_attention` has why)
@functools.partial(jax.jit, static_argnames=("scale", "half", "interpret"))
def latent_prefill_attention(
    q_nope: jax.Array,  # [b, p, heads, nope]
    q_rope: jax.Array,  # [b, p, heads, rope lanes]: NOT turned, zeros behind
    k_nope: jax.Array,  # [b, heads, nope, p]: expanded from the latent rows
    k_rope: jax.Array,  # [b, p, rope lanes]: the one rotary key, turned, zeros behind
    v: jax.Array,  # [b, heads, dv, p]: likewise
    valid: jax.Array,  # [b, p] 1/0: the keys that are real
    cos: jax.Array,  # [b, p, rope lanes] float32: `rope_tables`, zeros behind
    sin: jax.Array,  # likewise
    *,
    scale: float,
    half: int,  # half of the rotary lanes: how far a lane's partner lies
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of whole prompts to themselves, the scores kept in
    VMEM: query i of a row attends the valid keys j <= i with
    softmax((q_n . k_n + turn(q_r) . k_r) x scale) over them, and returns
    the context [b, p, heads * dv]. A head's score is two products in the
    inputs' dtype with float32 accumulation, summed in float32; softmax in
    float32, as models/mixers/latent.py `_attend_latent` states them; the
    order of the sums is another. turn(q_r) is `_rope` of the query's
    rotary lanes by the tables `cos` and `sin` (ops/rowwise.py
    `rope_tables` of the rows' positions; ones and zeros turn nothing), in
    float32 and rounded once, as `_rope` rounds.

    The operands are read where the projections leave them: a head's nope
    lanes are a block of lanes of the query product's flattened head axis;
    its nope keys and its values lie with the heads outermost and the
    positions along the lanes, which is how the TPU's compiler leaves the
    two products that expand them from the latent rows (asked for rows of
    heads it computes them so all the same and copies them, 168 MB each a
    layer); and the rotary key is ONE row of a lane tile a position
    (models/mixers/latent.py `_in_rope_lanes`: what the cache keeps), fetched
    once a grid step for all of the step's heads and never repeated in
    memory; the query's rotary lanes lie in a lane tile a head, zeros
    behind them as behind the key's and the tables', as their product
    leaves them when W_qb's rotary columns go in with zero columns behind
    (an array of 64 lanes a head takes a tile's room a head in memory
    anyway, and XLA's passes over such an array, `_rope`'s among them,
    move four times their bytes). nope, rope lanes and dv must be
    multiples of 128. A width that is no multiple of 128 is padded at the
    end (keys never valid, queries cut off). Grid (b, heads / hb, p / t, key
    tiles), (hb, t) from `latent_prefill_block`: the tile body's loop runs
    over the hb heads of a grid step, each against its own key and value
    lanes. As `prefill_attention`, a grid step past the diagonal names the
    tiles already in VMEM, and the key tiles wholly inside a row's left
    padding are neither fetched nor run."""
    b, p0, h, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], v.shape[2]
    if dn % 128 or dr % 128 or dv % 128:
        raise ValueError(f"latent_prefill_attention needs nope lanes, rotary "
                         f"lanes and values of a multiple of 128 lanes each, "
                         f"got {dn}, {dr} and {dv}")
    extra = -p0 % 128
    if extra:
        q_nope, q_rope = (
            jnp.pad(a, ((0, 0), (0, extra), (0, 0), (0, 0))) for a in (q_nope, q_rope)
        )
        k_nope, v = (
            jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, extra))) for a in (k_nope, v)
        )
        k_rope, cos, sin = (
            jnp.pad(a, ((0, 0), (0, extra), (0, 0))) for a in (k_rope, cos, sin)
        )
        valid = jnp.pad(valid, ((0, 0), (0, extra)))
    p = p0 + extra
    itemsize = q_nope.dtype.itemsize
    hb, t = latent_prefill_block(p, h, max(dn, dv), dr, itemsize)
    n = p // t
    valid = valid.astype(jnp.int32)
    held = jnp.sum(valid.reshape(b, n, t), axis=2)  # valid keys of each key tile
    first = jnp.argmax(held > 0, axis=1).astype(jnp.int32)

    def q_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, qi, j

    def k_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, j, jnp.minimum(first_ref[bi] + kk, qi)

    def shared_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, jnp.minimum(first_ref[bi] + kk, qi), 0

    def table_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, qi, 0

    def valid_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, 0, jnp.minimum(first_ref[bi] + kk, qi)

    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, t=t, hb=hb, dn=dn, dv=dv, half=half, scale=scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h // hb, n, n),
            in_specs=[
                pl.BlockSpec((1, t, hb * dn), q_block),
                pl.BlockSpec((1, t, hb * dr), q_block),
                pl.BlockSpec((1, t, dr), table_block),
                pl.BlockSpec((1, t, dr), table_block),
                pl.BlockSpec((1, hb * dn, t), k_block),
                pl.BlockSpec((1, t, dr), shared_block),
                pl.BlockSpec((1, hb * dv, t), k_block),
                pl.BlockSpec((1, 1, t), valid_block),
            ],
            out_specs=pl.BlockSpec((1, t, hb * dv), q_block),
            scratch_shapes=[
                pltpu.VMEM((hb, t, 128), jnp.float32),  # running maximum
                pltpu.VMEM((hb, t, 128), jnp.float32),  # running sum
                pltpu.VMEM((t, hb * dv), jnp.float32),  # accumulator
                pltpu.VMEM((1, t, hb * dr), q_rope.dtype),  # the rotary lanes, turned
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, p, h * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=2 * max(_PREFILL_VMEM, _prefill_vmem(
                t, max(dn, dv), hb, itemsize, own_keys=True, rope=dr
            )),
        ),
        name="latent_prefill_attention",
        interpret=interpret,
    )(
        first, held.reshape(b * n),
        q_nope.reshape(b, p, h * dn), q_rope.reshape(b, p, h * dr), cos, sin,
        k_nope.reshape(b, h * dn, p), k_rope, v.reshape(b, h * dv, p),
        valid.reshape(b, 1, p),
    )
    return out[:, :p0] if extra else out


# ------------------------------------------------- decode-step attention

_DECODE_ROWS = 1024  # latent rows a grid step fetches: 1 MiB of c_kv at 512 lanes


def latent_decode_tile(rows: int) -> int:
    """Rows of `c_kv` (and of `k_rope`) a grid step of
    `latent_decode_attention` fetches of a leaf that keeps `rows` a slot:
    about a megabyte, so that the step's fixed cost is small beside the
    fetch, or the slot's whole rows where they are fewer."""
    return min(rows, _DECODE_ROWS)


def _decode_kernel(layer_ref, pos_ref, pad_ref, q_ref, qr_ref, c_ref, r_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, t: int, scale: float):
    """One grid step (slot, kk-th needed tile): the slot's heads [heads,
    kv_rank] and their rotary lanes [heads, rope] against a tile of latent
    rows [t, kv_rank] and of rotary keys [t, rope]."""
    del layer_ref  # the index maps' business
    si, kk = pl.program_id(0), pl.program_id(1)
    pos, pad = pos_ref[si], pad_ref[si]
    last = jax.lax.div(pos, t)
    kt = jnp.minimum(jax.lax.div(pad, t), last) + kk

    @pl.when(kk == 0)
    def _start():
        _start_softmax(m_ref, l_ref, acc_ref)

    # past the last live tile the clamped tile is not run again
    @pl.when(kt <= last)
    def _fold():
        c = c_ref[...]
        s = (
            jax.lax.dot_general(
                q_ref[...], c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) + jax.lax.dot_general(
                qr_ref[...], r_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ) * scale  # [heads, t]
        row = kt * t + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
        s = jnp.where((row >= pad) & (row <= pos), s, _MASKED)
        m_prev = m_ref[...]  # [heads, 128], every lane the same
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(e, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            e.astype(c.dtype), c, preferred_element_type=jnp.float32
        )

    @pl.when(kk == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


# jitted for `latent_prefill_attention`'s reason; the layer is an operand,
# not a constant of the kernel, so that one lowering serves every layer
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_decode_attention(
    q: jax.Array,  # [slots, heads, kv_rank]: the nope lanes times W_uk
    q_rope: jax.Array,  # [slots, heads, rope lanes], turned, zeros behind
    c_cache: jax.Array,  # [layers, slots, rows, kv_rank]: a whole leaf
    r_cache: jax.Array,  # [layers, slots, rows, rope lanes]: likewise
    layer: jax.Array,  # scalar int32: the layer's index along the leaves
    pos: jax.Array,  # [slots] int32: each slot's physical position
    pad_len: jax.Array,  # [slots] int32: each slot's left pad
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """One step of a latent layer's attention over the slot cache in the
    absorbed form, read where it lies: each slot's heads attend its latent
    rows pad_len .. pos (the step's own row is in the leaf already) with
    softmax((q . c_kv + q_rope . k_rope) x scale), and what comes back is
    the weighted sum of the latent rows themselves, [slots, heads,
    kv_rank]: the caller's W_uv makes a head's output of it. Only the tiles
    pad_len // t .. pos // t are fetched: a grid step past the last names
    the tile already in VMEM, so it fetches nothing and runs nothing. A
    free slot (pos 0) attends its row 0.

    Products in the cache's dtype with float32 accumulation, softmax in
    float32, the weights cast to the cache's dtype before the second
    product. kv_rank must be a multiple of 128 and `latent_decode_tile`
    divide the rows. Grid (slots, rows / t)."""
    n, h, r = q.shape
    _, _, rows, _ = c_cache.shape
    dr = q_rope.shape[-1]
    t = latent_decode_tile(rows)
    if r % 128 or rows % t:
        raise ValueError(f"latent_decode_attention needs latent rows of a "
                         f"multiple of 128 lanes in whole tiles of {t}, got "
                         f"{rows} x {r}")

    def q_block(si, kk, layer_ref, pos_ref, pad_ref):
        return si, 0, 0

    def row_block(si, kk, layer_ref, pos_ref, pad_ref):
        last = jax.lax.div(pos_ref[si], t)
        first = jnp.minimum(jax.lax.div(pad_ref[si], t), last)
        return layer_ref[0], si, jnp.minimum(first + kk, last), 0

    return pl.pallas_call(
        functools.partial(_decode_kernel, t=t, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n, rows // t),
            in_specs=[
                pl.BlockSpec((None, h, r), q_block),
                pl.BlockSpec((None, h, dr), q_block),
                pl.BlockSpec((None, None, t, r), row_block),
                pl.BlockSpec((None, None, t, dr), row_block),
            ],
            out_specs=pl.BlockSpec((None, h, r), q_block),
            scratch_shapes=[
                pltpu.VMEM((h, 128), jnp.float32),  # running maximum
                pltpu.VMEM((h, 128), jnp.float32),  # running sum
                pltpu.VMEM((h, r), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, h, r), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="latent_decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), pos.astype(jnp.int32),
        pad_len.astype(jnp.int32), q, q_rope, c_cache, r_cache,
    )
