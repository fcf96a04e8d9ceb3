"""The linear mixer's prefill as a Pallas kernel: the chunked scan of
models/transformer.py `linear_scan` with a chunk's pairs and the carried
state kept in VMEM.

    o_t = (q_t / sqrt(dh)) S_t ,  S_t = lambda S_{t-1} + k_t^T v_t ,
    lambda = exp(-slope) a head

Written in XLA the scan is a `lax.scan` over chunks whose float32 pairs
[heads, chunk, chunk] and states [heads, dh, dh] cross HBM every chunk. Here
a grid step is one chunk of one head: the pairs, their decay mask and the
state never leave VMEM, and HBM sees q, k, v once, the output once and the
last state once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _linear_kernel(slope_ref, q_ref, k_ref, v_ref, o_ref, last_ref, state_ref,
                   *, chunk: int, scale: float):
    """One grid step (row, head, chunk): the chunk's queries against its own
    keys under the decay mask, and against the state the chunks before it
    left; then the state moves on by the chunk."""
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
    o_ref[0] = ((inner + carried) * scale).astype(o_ref.dtype)
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
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The linear mixer over whole prompts. Returns (o [b, p, heads, dh]
    float32, the state after the last position [b, heads, dh, dh] float32).

    Inside a chunk (q k^T * D) v with D_ij = lambda^(i-j) for j <= i, the
    products of the inputs' dtype with float32 accumulation and the masked
    pairs cast to v's dtype before the second product; across chunks the
    state, float32, multiplied in float32 (`highest`), as models/
    transformer.py `linear_scan` states them; the order of the sums is
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

    out, last = pl.pallas_call(
        functools.partial(_linear_kernel, chunk=chunk, scale=1.0 / math.sqrt(dh)),
        grid=(b, h, p // chunk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # every head's slope
            pl.BlockSpec((1, chunk, dh), rows),
            pl.BlockSpec((1, chunk, dh), rows),
            pl.BlockSpec((1, chunk, dh), rows),
        ],
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
    )
    return out.reshape(b, p, h, dh)[:, extra:], last
