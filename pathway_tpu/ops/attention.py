"""Fused multi-head attention kernels for the TPU numeric plane.

The flagship embedder runs many short sequences (RAG chunks, seq <= 128)
at large batch. XLA's stock lowering of that shape materializes the
[b, h, q, k] score tensor in HBM and inserts relayout copies between the
fused qkv projection and the per-head batched matmuls — measured ~17 ms
per layer at (b=4096, s=64, h=6, dh=64) on v5e, ~7x the bandwidth floor.

`fused_qkv_attention` is a Pallas kernel that takes the *fused* qkv
projection output [b, s, 3*d] straight from the MXU, does the head
split, scores, masked softmax, and value contraction entirely in VMEM,
and writes only ctx [b, s, d] back to HBM. Traffic per call is the
read of qkv and the write of ctx — nothing else.

Reference parity: replaces the torch SDPA used by the reference's local
embedding models (`/root/reference/python/pathway/xpacks/llm/embedders.py:270`
runs SentenceTransformer → torch attention); this is the TPU-native
equivalent of that hot loop.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def _attn_kernel(qkv_ref, bias_ref, out_ref, *, n_heads: int, head_dim: int,
                 scale: float):
    """One grid step: a [B, s, 3d] qkv block -> [B, s, d] context block.

    Head loop is a static Python loop (n_heads is small); each head does
    two B-batched (s x dh) matmuls with f32 accumulation and a VMEM-local
    f32 softmax. `bias_ref` is an additive key-axis mask [B, s] (0 for
    valid, -1e30 for padding).
    """
    d = n_heads * head_dim
    qkv = qkv_ref[:]  # [B, s, 3d] bf16
    bias = bias_ref[:]  # [B, s] f32
    bnum = qkv.shape[0]
    s = qkv.shape[1]
    batch_dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    for hi in range(n_heads):
        lo = hi * head_dim
        q = qkv[:, :, lo:lo + head_dim]
        k = qkv[:, :, d + lo:d + lo + head_dim]
        v = qkv[:, :, 2 * d + lo:2 * d + lo + head_dim]
        scores = batch_dot(q, k) * scale + bias[:, None, :]  # [B, s, s] f32
        m = jnp.max(scores, axis=-1, keepdims=True)
        e = jnp.exp(scores - m)
        probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(qkv.dtype)
        # ctx: [B, s, dh] — contraction over the key axis
        ctx = jax.lax.dot_general(
            probs, v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        out_ref[:, :, lo:lo + head_dim] = ctx.astype(out_ref.dtype)


def fused_qkv_attention(
    qkv: jax.Array,  # [b, s, 3*d] fused projection output
    token_mask: jax.Array,  # [b, s] 1/0
    n_heads: int,
    *,
    block_b: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """Bidirectional MHA over a fused qkv tensor; returns ctx [b, s, d].

    VMEM per grid step: the qkv block (block_b * s * 3d * 2 B) and the
    ctx block (a third of that), each double-buffered by the pipeline,
    plus one head's f32 scores [block_b, s, s] and their exp/probs. At
    the largest serving bucket (block_b=16, s=128, d=384) that is
    9.4 MB + 3.1 MB + ~3 MB. Mosaic (libtpu 0.0.34, TPU v5e) compiles
    that and every smaller bucket — s in 16..128, batch 8..4096, where
    batch 8 halves block_b to 8 — and `chip_smoke.py` checks each
    against `reference_attention` on the chip.
    """
    b, s, d3 = qkv.shape
    d = d3 // 3
    head_dim = d // n_heads
    scale = 1.0 / math.sqrt(head_dim)
    while b % block_b != 0:
        block_b //= 2
    bias = jnp.where(token_mask == 0, -1e30, 0.0).astype(jnp.float32)
    kernel = functools.partial(
        _attn_kernel, n_heads=n_heads, head_dim=head_dim, scale=scale
    )
    return pl.pallas_call(
        kernel,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, s, d3), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, s), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, s, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), qkv.dtype),
        interpret=interpret,
    )(qkv, bias)


def reference_attention(
    qkv: jax.Array, token_mask: jax.Array, n_heads: int
) -> jax.Array:
    """Plain-XLA einsum attention over the same fused-qkv contract —
    the path off the TPU and the numerical reference for tests."""
    b, s, d3 = qkv.shape
    d = d3 // 3
    dh = d // n_heads
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, n_heads, dh)
    k = k.reshape(b, s, n_heads, dh)
    v = v.reshape(b, s, n_heads, dh)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(dh)
    scores = jnp.where(token_mask[:, None, None, :] == 0, -1e30, scores)
    probs = jax.nn.softmax(scores, axis=-1).astype(qkv.dtype)
    ctx = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32
    ).astype(qkv.dtype)
    return ctx.reshape(b, s, d)


# --------------------------------------------------------- ring attention
#
# Long-context sequence/context parallelism: the sequence is sharded
# across a mesh axis; K/V blocks rotate around the ring via ppermute
# while each device accumulates its queries' attention with a streaming
# (flash-style) softmax. Peak memory per device is O(s_local^2) scores
# and one K/V block — sequences scale with the ring size. Communication
# rides ICI (ppermute neighbors), overlapping with each step's matmuls
# under XLA's latency-hiding scheduler.
#
# Reference parity: replaces the single-device torch SDPA ceiling of the
# reference's local models with the standard ring-attention construction
# (blockwise-parallel transformers over a device ring).


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    kv_mask: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Exact multi-head attention over a sequence sharded on `axis_name`.

    Call INSIDE shard_map with q/k/v [b, s_local, h, dh] holding this
    device's sequence block (global sequence = blocks in axis order).
    `kv_mask` [b, s_local] marks valid key positions of the local block
    (it rotates around the ring with K/V). Returns ctx [b, s_local, h, dh].
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, s_loc, h, dh = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(dh)
    qf = q.astype(jnp.float32)
    q_pos = my * s_loc + jnp.arange(s_loc)

    def accumulate(o, m, l, kblk, vblk, mblk, i):
        """Fold the currently-held K/V block into the streaming softmax."""
        src = (my - i) % n  # block index currently held
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", qf, kblk.astype(jnp.float32)) * sc
        )
        valid = mblk[:, None, None, :].astype(bool)
        if causal:
            k_pos = src * s_loc + jnp.arange(s_loc)
            valid = valid & (q_pos[:, None] >= k_pos[None, :])[None, None, :, :]
        scores = jnp.where(valid, scores, -jnp.inf)
        blk_max = jnp.max(scores, axis=-1)  # [b,h,q]
        new_m = jnp.maximum(m, blk_max)
        # rows with no valid key anywhere so far keep m=-inf; exp(-inf-(-inf))
        # would be NaN — pin those rows to 0 contribution
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        p = jnp.where(
            jnp.isfinite(scores), jnp.exp(scores - safe_m[..., None]), 0.0
        )
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32)
        )
        return o, new_m, l

    ring = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        # rotate first, then accumulate: n-1 rotations total (the local
        # block is folded before the scan; a final-step rotation would
        # only be discarded)
        o, m, l, kblk, vblk, mblk = carry
        kblk = jax.lax.ppermute(kblk, axis_name, ring)
        vblk = jax.lax.ppermute(vblk, axis_name, ring)
        mblk = jax.lax.ppermute(mblk, axis_name, ring)
        o, m, l = accumulate(o, m, l, kblk, vblk, mblk, i)
        return (o, m, l, kblk, vblk, mblk), None

    # build the initial carries FROM q so they inherit q's varying-axes
    # set under shard_map (the scan carry types must match whatever axes
    # the body's outputs vary over — ring axis AND any batch axes)
    o0 = jnp.transpose(qf * 0.0, (0, 2, 1, 3))  # [b,h,s,dh] zeros
    l0 = o0[..., 0]  # [b,h,s] zeros
    m0 = l0 - jnp.inf  # [b,h,s] -inf
    mask0 = (
        kv_mask if kv_mask is not None else jnp.ones((b, s_loc), jnp.int32)
    )
    o0, m0, l0 = accumulate(o0, m0, l0, k, v, mask0, 0)  # local block
    if n > 1:
        (o, m, l, _k, _v, _m), _ = jax.lax.scan(
            step, (o0, m0, l0, k, v, mask0), jnp.arange(1, n)
        )
    else:
        o, l = o0, l0
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
