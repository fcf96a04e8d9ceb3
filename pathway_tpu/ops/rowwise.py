"""The row-wise chain between a prefill's qkv product and its mixer as one
Pallas pass: the q/k norm, the rotary positions and the zero of a pad's key,
over heads of q or k read where the product left them.

Written in XLA (models/layers.py `rmsnorm`, `rope`, the `where` of the
linear kind's `prefill`) each link is a fusion of its own over the whole
tensor, with float32 arrays of it between them: the split of the product, the
norm's mean of squares, its scaling, two relayouts, the rotation, the zero.
Here a grid step is a tile of rows of a few whole heads: HBM sees the
product's lanes once and the result once.

The arithmetic is `_rmsnorm`'s and `_rope`'s, in float32 from the product's
element to the stored one and rounded to the input's dtype once, at the end
of the chain: where the program XLA compiles for a TPU from those two
functions rounds. (`_rmsnorm` casts its result to bf16 and `_rope` casts it
back; inside one fusion the TPU backend drops that pair, as
`xla_allow_excess_precision` lets it, so on the chip the normed value
reaches the rotation in float32. A pass that rounds there as well is the
CPU's arithmetic and not the chip's: a third of its elements differ from the
chip's by a bf16 ulp or more. PERF.md section 6, PR 42.) Those two
functions stay the definition; what differs is the order of the 128
squares' sum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 512  # a tile's rows
_HEADS = 8  # and its heads at most: 1 MiB of bf16 at 128 lanes a head


def _rowwise_kernel(*refs, heads: int, dh: int, norm: bool, rotary: bool,
                    zero: bool):
    """One grid step (row, tile of rows, group of heads). `refs`: the
    product's block, then the norm's scale [1, dh], the tables' blocks
    [1, rows, dh] and the live rows' block [1, rows, 1], each only where
    its part runs, then the output's block."""
    refs = list(refs)
    x_ref, o_ref = refs.pop(0), refs.pop()
    scale = refs.pop(0)[...] if norm else None
    cos, sin = (refs.pop(0)[0], refs.pop(0)[0]) if rotary else (None, None)
    live = refs.pop(0)[0] != 0 if zero else None
    for h in range(heads):
        lanes = slice(h * dh, (h + 1) * dh)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        if norm:  # `_rmsnorm`
            var = jnp.mean(x * x, axis=-1, keepdims=True)
            x = x * jax.lax.rsqrt(var + 1e-6) * scale
        if rotary:
            # `_rope`: [x1 cos - x2 sin, x2 cos + x1 sin] with the halves
            # swapped by a roll of the lanes and the sign in the table
            x = x * cos + pltpu.roll(x, dh // 2, 1) * sin
        x = x.astype(o_ref.dtype)
        if zero:
            x = jnp.where(live, x, jnp.zeros_like(x))
        o_ref[0, :, lanes] = x


# jitted so that the layers of one program share one trace of the kernel
# and one lowering to Mosaic, as `prefill_attention`
@functools.partial(
    jax.jit, static_argnames=("first", "heads", "dh", "interpret")
)
def rowwise_heads(
    x: jax.Array,  # [b, p, lanes]: whole heads of dh lanes side by side
    scale: jax.Array | None,  # [dh]: the norm's, or None for no norm
    rope: tuple[jax.Array, jax.Array] | None,  # `rope_tables`, or None
    live: jax.Array | None,  # [b, p] bool: rows not live come out zero
    *,
    first: int,
    heads: int,
    dh: int,
    interpret: bool = False,
) -> jax.Array:
    """Heads `first` .. `first + heads - 1` of x, each normed over its dh
    lanes (`scale`), rotated (`rope`) and zeroed where its row is not
    `live`, every part optional: [b, p, heads * dh] of x's dtype. dh must be
    a multiple of 128 (a lane tile).

    Grid (b, tiles of rows, groups of heads), the heads innermost so that a
    tile's tables are fetched once for all of its groups."""
    b, p, _ = x.shape
    if dh % 128:
        raise ValueError(f"rowwise_heads needs heads of a multiple of 128 "
                         f"lanes, got {dh}")
    # the heads a block: as many as divide the run and where it starts
    group = next(g for g in (_HEADS, 4, 2, 1) if heads % g == 0 and first % g == 0)
    rows = min(_ROWS, p)

    def tile(bi, ri, hi):
        return bi, ri, 0

    operands = [x]
    in_specs = [pl.BlockSpec(
        (1, rows, group * dh), lambda bi, ri, hi: (bi, ri, first // group + hi)
    )]
    if scale is not None:
        operands.append(scale.astype(jnp.float32).reshape(1, dh))
        in_specs.append(pl.BlockSpec((1, dh), lambda bi, ri, hi: (0, 0)))
    if rope is not None:
        operands += rope
        in_specs += [pl.BlockSpec((1, rows, dh), tile)] * 2
    if live is not None:
        operands.append(live.astype(jnp.int32)[:, :, None])
        in_specs.append(pl.BlockSpec((1, rows, 1), tile))
    return pl.pallas_call(
        functools.partial(
            _rowwise_kernel, heads=group, dh=dh, norm=scale is not None,
            rotary=rope is not None, zero=live is not None,
        ),
        grid=(b, pl.cdiv(p, rows), heads // group),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, group * dh), lambda bi, ri, hi: (bi, ri, hi)),
        out_shape=jax.ShapeDtypeStruct((b, p, heads * dh), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        name="rowwise_heads",
        interpret=interpret,
    )(*operands)


def rope_tables(pos: jax.Array, theta: float, dh: int) -> tuple[jax.Array, jax.Array]:
    """`_rope`'s cosines and sines as `rowwise_heads` takes them: pos [b, p]
    logical positions -> two float32 [b, p, dh], the angles by `_rope`'s own
    expression, the cosine of each twice along the head and the sine with
    its first half negated, so that
    [x1 cos - x2 sin, x2 cos + x1 sin] = x * cos + roll(x, dh / 2) * sin
    over whole lane tiles (a table of dh / 2 lanes would be padded to a
    tile in VMEM anyway)."""
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, :, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return (
        jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([-sin, sin], axis=-1)
    )
