"""Ring attention: exact sequence-parallel attention over a device ring.

Equivalence against full (single-device) attention on the virtual
8-device CPU mesh, including causal masks, padding, gradients, and the
sequence-parallel encoder path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.ops.attention import ring_attention


def _mesh(n=8, axis="seq"):
    return Mesh(np.asarray(jax.devices()[:n]), (axis,))


def _full_attention(q, k, v, mask, causal):
    # reference: plain f32 softmax attention over the whole sequence
    s = q.shape[1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / np.sqrt(q.shape[-1])
    valid = mask[:, None, None, :].astype(bool)
    if causal:
        tri = jnp.tril(jnp.ones((s, s), bool))
        valid = valid & tri[None, None]
    scores = jnp.where(valid, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_equals_full(causal):
    rng = np.random.default_rng(0)
    b, s, h, dh = 2, 64, 4, 16  # 8 blocks of 8
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, (b, s)), jnp.int32).at[:, 0].set(1)

    mesh = _mesh()
    got = jax.jit(
        jax.shard_map(
            lambda q_, k_, v_, m_: ring_attention(
                q_, k_, v_, "seq", causal=causal, kv_mask=m_
            ),
            mesh=mesh,
            in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                      P(None, "seq")),
            out_specs=P(None, "seq"),
        )
    )(q, k, v, mask)
    want = _full_attention(q, k, v, mask, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ring_gradients_flow():
    rng = np.random.default_rng(1)
    b, s, h, dh = 1, 32, 2, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    mask = jnp.ones((b, s), jnp.int32)
    mesh = _mesh()

    def loss_ring(q_, k_, v_):
        out = jax.shard_map(
            lambda a, b_, c, m: ring_attention(a, b_, c, "seq", kv_mask=m),
            mesh=mesh,
            in_specs=(P(None, "seq"),) * 4,
            out_specs=P(None, "seq"),
        )(q_, k_, v_, mask)
        return jnp.sum(out * out)

    def loss_full(q_, k_, v_):
        out = _full_attention(q_, k_, v_, mask, causal=False)
        return jnp.sum(out * out)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf), atol=5e-4)


def test_sequence_parallel_encoder_matches_single_device():
    """encode() under shard_map with cfg.seq_axis == full-sequence encode."""
    import dataclasses

    from pathway_tpu.models import embedder_config, encoder, transformer as tfm

    cfg = embedder_config(
        vocab_size=128, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=64, dtype=jnp.float32, fused_attention=False,
    )
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    b, s = 2, 64
    token_ids = jnp.asarray(rng.integers(2, 128, (b, s)), jnp.int32)
    token_mask = jnp.ones((b, s), jnp.int32)

    want = encoder.encode(params, token_ids, token_mask, cfg)

    mesh = _mesh()
    sp_cfg = dataclasses.replace(cfg, seq_axis="seq")

    def sp_encode(p, ids, m):
        return encoder.encode(p, ids, m, sp_cfg)

    got = jax.jit(
        jax.shard_map(
            sp_encode,
            mesh=mesh,
            in_specs=(P(), P(None, "seq"), P(None, "seq")),
            out_specs=P(),
        )
    )(params, token_ids, token_mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
