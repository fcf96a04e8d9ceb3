"""The linear mixer's prefill as a Pallas kernel: the chunked scan of
models/mixers/linear.py `linear_scan` with a chunk's pairs and the carried
state kept in VMEM.

    o_t = (q_t / sqrt(dh)) S_t ,  S_t = lambda S_{t-1} + k_t^T v_t ,
    lambda = exp(-slope) a head

Written in XLA the scan is a `lax.scan` over chunks whose float32 pairs
[heads, chunk, chunk] and states [heads, dh, dh] cross HBM every chunk. Here
a grid step is one chunk of one head: the pairs, their decay mask and the
state never leave VMEM, and HBM sees q, k, v once, the output once and the
last state once. A chunk's output block is a head's dh lanes, the group of
the layer's output norm (models/mixers/linear.py `_linear_out`), so with that
norm's scale the kernel norms the float32 block where it lies: the norm's
reduction, its scaling, their relayouts and the float32 arrays between them
never cross HBM. What leaves is the normed block in float32, as the
un-normed one did: the one rounding of `_linear_out` is its caller's, and
on a TPU XLA makes it inside the product of the output gate, after the
gate's multiplication (PERF.md section 6, PR 42).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _linear_kernel(slope_ref, q_ref, k_ref, v_ref, *rest, chunk: int,
                   scale: float):
    """One grid step (row, head, chunk): the chunk's queries against its own
    keys under the decay mask, and against the state the chunks before it
    left; then the state moves on by the chunk. `rest`: the output norm's
    scale [1, dh] where there is one, then the outputs and the scratch."""
    *norm_ref, o_ref, last_ref, state_ref = rest
    ci = pl.program_id(2)
    slope = slope_ref[pl.program_id(1)]  # the head's, a scalar

    @pl.when(ci == 0)
    def _start():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]  # [chunk, dh]
    ago = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        - jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    ).astype(jnp.float32)
    decay = jnp.where(ago >= 0.0, jnp.exp(-slope * jnp.maximum(ago, 0.0)), 0.0)
    pairs = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * decay
    inner = jnp.dot(pairs.astype(v.dtype), v, preferred_element_type=jnp.float32)
    at = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(jnp.float32)
    state = state_ref[...]
    high = jax.lax.Precision.HIGHEST
    carried = jnp.dot(
        q.astype(jnp.float32) * jnp.exp(-slope * (at + 1.0)), state,
        precision=high, preferred_element_type=jnp.float32,
    )
    out = (inner + carried) * scale
    if norm_ref:  # `_rmsnorm` of the float32 block
        var = jnp.mean(out * out, axis=-1, keepdims=True)
        out = out * jax.lax.rsqrt(var + 1e-6) * norm_ref[0][...]
    o_ref[0] = out
    left = k.astype(jnp.float32) * jnp.exp(-slope * (chunk - 1.0 - at))
    state = jnp.exp(-slope * chunk) * state + jax.lax.dot_general(
        left, v.astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=high, preferred_element_type=jnp.float32,
    )
    state_ref[...] = state

    @pl.when(ci == pl.num_programs(2) - 1)
    def _finish():
        last_ref[0, 0] = state


# jitted so that the linear layers of one program share one trace of the
# kernel and one lowering to Mosaic, as `prefill_attention`
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def linear_prefill_attention(
    q: jax.Array,  # [b, p, heads, dh]
    k: jax.Array,  # [b, p, heads, dh]: a pad's key zeroed
    v: jax.Array,  # [b, p, heads, dh]
    slopes: jax.Array,  # [heads] float32: head h decays by exp(-slopes[h])
    chunk: int = 256,
    out_norm: jax.Array | None = None,  # [dh]: the output norm's scale
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The linear mixer over whole prompts. Returns (o [b, p, heads, dh]
    float32, the state after the last position [b, heads, dh, dh] float32);
    with `out_norm` o is RMS-normed over each head's dh and scaled by it,
    in float32: models/mixers/linear.py `_linear_out` before its cast.

    Inside a chunk (q k^T * D) v with D_ij = lambda^(i-j) for j <= i, the
    products of the inputs' dtype with float32 accumulation and the masked
    pairs cast to v's dtype before the second product; across chunks the
    state, float32, multiplied in float32 (`highest`), as models/
    models/mixers/linear.py `linear_scan` states them; the order of the sums is
    another. A width that `chunk` does not divide is padded in FRONT with
    zeros, which add nothing to a state of zeros and decay nothing of it;
    the batcher's own left pad is the same thing, so long as its keys are
    zeroed. dh must be a multiple of 128 (a lane tile).

    Grid (b, heads, p / chunk), the last axis in order: the state is
    scratch that a head's chunks hand on."""
    b, p0, h, dh = q.shape
    if dh % 128:
        raise ValueError(f"linear_prefill_attention needs heads of a multiple "
                         f"of 128 lanes, got {dh}")
    chunk = min(chunk, -(-p0 // 8) * 8)
    extra = -p0 % chunk
    if extra:
        q, k, v = (jnp.pad(a, ((0, 0), (extra, 0), (0, 0), (0, 0))) for a in (q, k, v))
    p = p0 + extra

    def rows(bi, hi, ci):
        return bi, ci, hi

    norm = [] if out_norm is None else [out_norm.astype(jnp.float32).reshape(1, dh)]
    out, last = pl.pallas_call(
        functools.partial(_linear_kernel, chunk=chunk, scale=1.0 / math.sqrt(dh)),
        grid=(b, h, p // chunk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # every head's slope
            pl.BlockSpec((1, chunk, dh), rows),
            pl.BlockSpec((1, chunk, dh), rows),
            pl.BlockSpec((1, chunk, dh), rows),
        ] + [pl.BlockSpec((1, dh), lambda bi, hi, ci: (0, 0)) for _ in norm],
        out_specs=[
            pl.BlockSpec((1, chunk, dh), rows),
            pl.BlockSpec((1, 1, dh, dh), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, p, h * dh), jnp.float32),
            jax.ShapeDtypeStruct((b, h, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="linear_prefill_attention",
        interpret=interpret,
    )(
        slopes.astype(jnp.float32),
        q.reshape(b, p, h * dh), k.reshape(b, p, h * dh), v.reshape(b, p, h * dh),
        *norm,
    )
    return out.reshape(b, p, h, dh)[:, extra:], last
