"""The plain block: the bi-directional text encoder (mean, first or last
pooled, L2-normalised embeddings) and the causal LM of that block with its
training step. A decoder of other kinds is models/transformer.py's.

- Forward is pure + jit-friendly: static shapes, no Python branching on
  data; attention uses one fused einsum per projection so the MXU sees
  [B*S, D] x [D, D'] matmuls in bf16 with f32 accumulation.
- `remat` wraps each block for the train step: activations are
  rematerialized in backward, trading MXU flops for HBM — the standard
  memory lever on TPU.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from pathway_tpu.models.config import TransformerConfig
from pathway_tpu.models.layers import (
    Array, Params, build_mask, ffn, kernel_may_run, rmsnorm,
)
from pathway_tpu.models.transformer import init_params, shard_params


def _attention(
    x: Array,
    block: Params,
    cfg: TransformerConfig,
    mask: Array,
    token_mask: Array,
) -> Array:
    # The qkv projection output feeds the fused Pallas attention kernel
    # directly (ops/attention.py): head split, scores, masked softmax and
    # the value contraction all stay in VMEM, so the only HBM traffic is
    # the qkv read and the ctx write. On non-TPU backends (and for the
    # causal LM path) the einsum reference implementation runs instead —
    # XLA's lowering there round-trips [b,h,s,s] scores through HBM,
    # which at flagship shapes is ~5x slower (measured on v5e).
    b, s, d = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    qkv = jnp.einsum(
        "bsd,de->bse", x, block["qkv"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    ).astype(cfg.dtype)
    if cfg.seq_axis is not None:
        from pathway_tpu.ops.attention import ring_attention

        q, k, v = jnp.split(qkv, 3, axis=-1)
        ctx = ring_attention(
            q.reshape(b, s, h, dh),
            k.reshape(b, s, h, dh),
            v.reshape(b, s, h, dh),
            cfg.seq_axis,
            causal=cfg.causal,
            kv_mask=token_mask,
        ).reshape(b, s, d)
    elif not cfg.causal and kernel_may_run(cfg):
        from pathway_tpu.ops.attention import fused_qkv_attention

        ctx = fused_qkv_attention(qkv, token_mask, h)
    elif not cfg.causal:
        from pathway_tpu.ops.attention import reference_attention

        ctx = reference_attention(qkv, token_mask, h)
    else:
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, h, dh)
        k = k.reshape(b, s, h, dh)
        v = v.reshape(b, s, h, dh)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) / math.sqrt(dh)
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        ctx = jnp.einsum(
            "bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32
        ).astype(cfg.dtype).reshape(b, s, d)
    return jnp.einsum(
        "bsd,de->bse", ctx, block["o"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    ).astype(cfg.dtype)


def _block_fwd(
    x: Array, block: Params, cfg: TransformerConfig, mask: Array, token_mask: Array
) -> Array:
    xin = rmsnorm(x, block["ln1_scale"])
    with jax.named_scope("attn"):
        x = x + _attention(xin, block, cfg, mask, token_mask)
    x = x + ffn(rmsnorm(x, block["ln2_scale"]), block, cfg)
    return x


def forward(
    params: Params, token_ids: Array, token_mask: Array, cfg: TransformerConfig
) -> Array:
    """Hidden states [b, s, d_model]."""
    if not cfg.plain:
        raise NotImplementedError(
            "forward (encode, logits, the training step) runs the plain "
            "block; a decoder of other kinds is served by models/transformer.py"
        )
    b, s = token_ids.shape
    x = params["tok_embed"].astype(cfg.dtype)[token_ids]
    if cfg.seq_axis is not None:
        # sequence-parallel block: positions offset by this device's block.
        # The ring size is static, so over-length sequences fail at trace
        # time (dynamic_slice would otherwise clamp and silently repeat
        # the final positions).
        n_blocks = jax.lax.psum(1, cfg.seq_axis)
        if n_blocks * s > cfg.max_len:
            raise ValueError(
                f"sequence-parallel length {n_blocks}x{s} exceeds "
                f"max_len={cfg.max_len}"
            )
        offset = jax.lax.axis_index(cfg.seq_axis) * s
        pos = jax.lax.dynamic_slice_in_dim(
            params["pos_embed"].astype(cfg.dtype), offset, s, axis=0
        )
        x = x + pos[None, :, :]
    else:
        x = x + params["pos_embed"].astype(cfg.dtype)[None, :s, :]
    mask = build_mask(token_mask, cfg.causal)
    blk = functools.partial(_block_fwd, cfg=cfg, mask=mask, token_mask=token_mask)
    for block in params["blocks"]:
        x = jax.checkpoint(blk)(x, block)
    return rmsnorm(x, params["ln_f_scale"])


def encode(
    params: Params, token_ids: Array, token_mask: Array, cfg: TransformerConfig
) -> Array:
    """Pooled, L2-normalized embeddings [b, embed_dim] (f32)."""
    h = forward(params, token_ids, token_mask, cfg)
    if cfg.seq_axis is not None and cfg.pool != "mean":
        # 'cls'/'last' would need a block broadcast across the ring
        raise NotImplementedError(
            "sequence-parallel encode supports mean pooling"
        )
    if cfg.pool == "mean":
        # bf16 mask-and-sum (HBM-bound step); divide in f32 for accuracy.
        # Under sequence parallelism the block-local partials combine over
        # the ring before the divide.
        m16 = token_mask.astype(cfg.dtype)[:, :, None]
        part = jnp.sum(h * m16, axis=1).astype(jnp.float32)
        cnt = jnp.sum(token_mask, axis=1)[:, None].astype(jnp.float32)
        if cfg.seq_axis is not None:
            part = jax.lax.psum(part, cfg.seq_axis)
            cnt = jax.lax.psum(cnt, cfg.seq_axis)
        pooled = part / jnp.maximum(cnt, 1.0)
    elif cfg.pool == "cls":
        pooled = h[:, 0, :].astype(jnp.float32)
    else:  # last valid token
        idx = jnp.maximum(jnp.sum(token_mask, axis=1) - 1, 0).astype(jnp.int32)
        pooled = h[jnp.arange(h.shape[0]), idx, :].astype(jnp.float32)
    from pathway_tpu.ops.distances import normalize

    return normalize(pooled @ params["head"].astype(jnp.float32))


def logits(
    params: Params, token_ids: Array, token_mask: Array, cfg: TransformerConfig
) -> Array:
    """LM logits [b, s, vocab] via tied embedding."""
    h = forward(params, token_ids, token_mask, cfg)
    return jnp.einsum(
        "bsd,vd->bsv", h, params["tok_embed"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


# ------------------------------------------------------------- train step


def lm_loss(
    params: Params, token_ids: Array, token_mask: Array, cfg: TransformerConfig
) -> Array:
    """Next-token cross-entropy. Requires a causal config: with bidirectional
    attention the target token is visible to its own position and the loss
    degenerates to copying."""
    if not cfg.causal:
        raise ValueError("lm_loss requires causal=True (use lm_config)")
    lg = logits(params, token_ids, token_mask, cfg)
    targets = jnp.roll(token_ids, -1, axis=1)
    valid = token_mask.astype(jnp.float32)
    valid = valid * jnp.roll(valid, -1, axis=1)
    valid = valid.at[:, -1].set(0.0)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[:, :, None], axis=-1)[:, :, 0]
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def make_train_step(cfg: TransformerConfig, learning_rate: float = 1e-3):
    """Returns (init_opt_state, train_step). AdamW via optax."""
    import optax

    tx = optax.adamw(learning_rate, weight_decay=0.01)

    def init_opt(params: Params):
        return tx.init(params)

    def train_step(params: Params, opt_state, token_ids: Array, token_mask: Array):
        loss, grads = jax.value_and_grad(lm_loss)(params, token_ids, token_mask, cfg)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return init_opt, train_step


class TransformerLM:
    """Convenience OO wrapper over the functional model."""

    def __init__(self, cfg: TransformerConfig, rng_seed: int = 0):
        self.cfg = cfg
        self.params = init_params(jax.random.PRNGKey(rng_seed), cfg)
        self._encode = jax.jit(functools.partial(encode, cfg=cfg))
        self._logits = jax.jit(functools.partial(logits, cfg=cfg))

    def encode(self, token_ids: Array, token_mask: Array) -> Array:
        return self._encode(self.params, token_ids, token_mask)

    def logits(self, token_ids: Array, token_mask: Array) -> Array:
        return self._logits(self.params, token_ids, token_mask)

    def shard(self, mesh: Mesh) -> None:
        # tensor-parallel params: switch off the fused attention kernel
        # (no partitioning rule for pallas_call — see TransformerConfig)
        self.cfg = dataclasses.replace(self.cfg, fused_attention=False)
        self.params = shard_params(self.params, mesh, self.cfg)
        self._encode = jax.jit(functools.partial(encode, cfg=self.cfg))
        self._logits = jax.jit(functools.partial(logits, cfg=self.cfg))

