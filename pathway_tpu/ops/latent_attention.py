"""The attention of a latent layer (models/transformer.py `LatentSpec`:
multi-head latent attention), as Pallas kernels for the TPU.

A latent layer's head scores a query of nope + rope lanes (128 + 64 at the
published widths) against keys as wide and sums values of another width
(128), and what a position keeps is one low-rank row all heads share
(`c_kv`, 512 lanes) and one rotary key (`k_rope`, 64). Neither fits the
kernels of ops/attention.py, whose q, k and v are one width of whole lane
tiles and whose cache leaf is rows of whole heads.

* `latent_prefill_attention`: a prompt's causal attention to its own
  expanded keys and values, on `prefill_attention`'s tile body
  (`_fold_key_tile`) with the scores kept in VMEM. The query/key width is
  carried padded with zeros to the next lane tile (192 -> 256): a zero lane
  adds nothing to a score, and a quarter of the first product's passes are
  spent on it.
* `latent_decode_attention`: a step's attention in the absorbed form. A
  slot's heads (their nope lanes already times W_uk: `kv_rank` wide) share
  every latent row, so the whole of a slot's heads meets a tile of `c_kv`
  in one product, adds the rotary part's product with the tile of
  `k_rope`, and sums the tile's latent rows by the softmax's weights. Only
  the tiles that hold a live row of the slot are fetched, out of the
  stacked leaves where they lie.

models/transformer.py `latent_prefill_uses_kernel` and
`latent_step_uses_kernel` say which programs run these.
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
    _start_softmax, prefill_tile,
)


def _prefill_kernel(first_ref, held_ref, q_ref, k_ref, v_ref, valid_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, t: int, dk: int, dv: int,
                    scale: float):
    """One grid step (row, head, query tile, k-th needed key tile): a head's
    query tile [t, dk] against its key tile [t, dk] and value tile [t, dv];
    ops/attention.py `_prefill_kernel` without a window or a group."""
    bi, qi, kk = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    kt = first_ref[bi] + kk  # the key tile
    held = held_ref[bi * pl.num_programs(2) + jnp.minimum(kt, qi)]  # its valid keys

    @pl.when(kk == 0)
    def _start():
        _start_softmax(m_ref, l_ref, acc_ref)

    fold = functools.partial(
        _fold_key_tile, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
        group=1, dh=dk, scale=scale, dv=dv,
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
        _finish_softmax(o_ref, l_ref, acc_ref, group=1, dh=dv)


# jitted so that the layers of one program share one trace of the kernel
# and one lowering to Mosaic (ops/attention.py `prefill_attention` has why)
@functools.partial(jax.jit, static_argnames=("interpret",))
def latent_prefill_attention(
    q_nope: jax.Array,  # [b, p, heads, nope]
    q_rope: jax.Array,  # [b, p, heads, rope], turned
    k_nope: jax.Array,  # [b, p, heads, nope]: expanded from the latent rows
    k_rope: jax.Array,  # [b, p, heads, rope]: the one rotary key, a head each
    v: jax.Array,  # [b, p, heads, dv]
    valid: jax.Array,  # [b, p] 1/0: the keys that are real
    *,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of whole prompts to themselves, the scores kept in
    VMEM: query i of a row attends the valid keys j <= i with
    softmax((q_n . k_n + q_r . k_r) / sqrt(nope + rope)) over them, and
    returns the context [b, p, heads * dv]. Products in the inputs' dtype
    with float32 accumulation, softmax in float32, as models/transformer.py
    `_attend_latent` states them; the order of the sums is another.

    The two parts of a head lie side by side in one query (and key) of
    nope + rope lanes padded with zeros to whole lane tiles; dv must be a
    multiple of 128. A width that is no multiple of 128 is padded at the
    end (keys never valid, queries cut off). Grid (b, heads, p / t, key
    tiles), t from `prefill_tile` of the padded width: as
    `prefill_attention`, a grid step past the diagonal names the tile
    already in VMEM, and the key tiles wholly inside a row's left padding
    are neither fetched nor run."""
    b, p0, h, dn = q_nope.shape
    dv = v.shape[-1]
    dk0 = dn + q_rope.shape[-1]
    dk = -(-dk0 // 128) * 128
    if dv % 128:
        raise ValueError(f"latent_prefill_attention needs values of a multiple "
                         f"of 128 lanes, got {dv}")
    fill = jnp.zeros((b, p0, h, dk - dk0), q_nope.dtype)
    q = jnp.concatenate([q_nope, q_rope, fill], axis=-1)
    k = jnp.concatenate([k_nope, k_rope, fill], axis=-1)
    extra = -p0 % 128
    if extra:
        q, k, v = (jnp.pad(a, ((0, 0), (0, extra), (0, 0), (0, 0))) for a in (q, k, v))
        valid = jnp.pad(valid, ((0, 0), (0, extra)))
    p = p0 + extra
    t = prefill_tile(p, dk, 1, q.dtype.itemsize)
    n = p // t
    valid = valid.astype(jnp.int32)
    held = jnp.sum(valid.reshape(b, n, t), axis=2)  # valid keys of each key tile
    first = jnp.argmax(held > 0, axis=1).astype(jnp.int32)

    def q_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, qi, j

    def k_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, jnp.minimum(first_ref[bi] + kk, qi), j

    def valid_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, 0, jnp.minimum(first_ref[bi] + kk, qi)

    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, t=t, dk=dk, dv=dv, scale=1.0 / math.sqrt(dk0)
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, n, n),
            in_specs=[
                pl.BlockSpec((1, t, dk), q_block),
                pl.BlockSpec((1, t, dk), k_block),
                pl.BlockSpec((1, t, dv), k_block),
                pl.BlockSpec((1, 1, t), valid_block),
            ],
            out_specs=pl.BlockSpec((1, t, dv), q_block),
            scratch_shapes=[
                pltpu.VMEM((1, t, 128), jnp.float32),  # running maximum
                pltpu.VMEM((1, t, 128), jnp.float32),  # running sum
                pltpu.VMEM((t, dv), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, p, h * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=2 * max(
                _PREFILL_VMEM, _prefill_vmem(t, dk, 1, q.dtype.itemsize)
            ),
        ),
        name="latent_prefill_attention",
        interpret=interpret,
    )(
        first, held.reshape(b * n),
        q.reshape(b, p, h * dk), k.reshape(b, p, h * dk), v.reshape(b, p, h * dv),
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
