"""TPU-native transformer: embedder (bi-directional + mean pool) and causal LM.

This is the flagship compute model of the framework — the engine behind the
local `JaxEmbedder` / reranker / on-TPU generation in `xpacks.llm`, replacing
the reference's torch `SentenceTransformerEmbedder`
(`/root/reference/python/pathway/xpacks/llm/embedders.py:270`) and
`HFPipelineChat` (`llms.py:441`) with batched XLA programs.

Design notes (TPU-first):
- Params are a plain pytree of `jnp` arrays; every leaf has a PartitionSpec
  in `param_specs()` implementing Megatron-style tensor parallelism over the
  mesh's `model` axis (attention heads + ffn hidden sharded), data
  parallelism over `data` (batch sharded), with XLA inserting the
  all-reduces at the row-parallel projections.
- Forward is pure + jit-friendly: static shapes, no Python branching on
  data; attention uses one fused einsum per projection so the MXU sees
  [B*S, D] x [D, D'] matmuls in bf16 with f32 accumulation.
- `remat` wraps each block for the train step: activations are
  rematerialized in backward, trading MXU flops for HBM — the standard
  memory lever on TPU.
- The causal decode path keeps a KV cache laid out [layers, B, S, H, Dh]
  sharded on heads, so generation is also tensor-parallel.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array
Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_len: int = 512
    causal: bool = False  # False: bi-directional encoder; True: decoder LM
    pool: str = "mean"  # encoder pooling: mean | cls | last
    dtype: Any = jnp.bfloat16
    embed_dim: int | None = None  # projection head dim (None = d_model)
    # Use the fused Pallas attention kernel (ops/attention.py) on TPU for
    # the non-causal path. MUST be False when params are tensor-parallel
    # over a mesh's `model` axis: pallas_call has no partitioning rule, so
    # a 'model'-sharded qkv operand cannot be auto-partitioned — use
    # `dataclasses.replace(cfg, fused_attention=False)`
    # (TransformerLM.shard does this for you).
    fused_attention: bool = True
    # Sequence/context parallelism: name of the mesh axis the sequence is
    # sharded over. When set, forward/encode must run INSIDE shard_map
    # with [b, s_local, ...] blocks; attention runs as ring attention
    # (ops/attention.py ring_attention — K/V blocks rotate over ICI with
    # streaming-softmax accumulation), and positions/pooling account for
    # the block offset. Long sequences scale with the ring size.
    seq_axis: str | None = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def __post_init__(self) -> None:
        if self.pool not in ("mean", "cls", "last"):
            raise ValueError(f"pool must be mean|cls|last, got {self.pool!r}")
        if self.d_model % self.n_heads != 0:
            raise ValueError("d_model must be divisible by n_heads")


def embedder_config(**kw) -> TransformerConfig:
    """SBERT-class text encoder."""
    return TransformerConfig(causal=False, **kw)


def lm_config(**kw) -> TransformerConfig:
    """Gemma-class causal decoder."""
    kw.setdefault("pool", "last")
    return TransformerConfig(causal=True, **kw)


# ------------------------------------------------------------------ params


def _init_block(
    rng: Array, cfg: TransformerConfig, dtype: Any = jnp.float32
) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 6)
    s = 1.0 / math.sqrt(d)
    return {
        "qkv": (jax.random.normal(ks[0], (d, 3 * d), jnp.float32) * s).astype(dtype),
        "o": (jax.random.normal(ks[1], (d, d), jnp.float32) * s).astype(dtype),
        "ff_in": (jax.random.normal(ks[2], (d, f), jnp.float32) * s).astype(dtype),
        "ff_out": (
            jax.random.normal(ks[3], (f, d), jnp.float32) * (1.0 / math.sqrt(f))
        ).astype(dtype),
        "ln1_scale": jnp.ones((d,), dtype),
        "ln2_scale": jnp.ones((d,), dtype),
    }


def init_params(
    rng: Array, cfg: TransformerConfig, dtype: Any = jnp.float32
) -> Params:
    """Random parameters. Every leaf is drawn in float32 and cast to
    `dtype` before the next is drawn, so a bf16 tree of a 2B-parameter
    decoder peaks at its own size plus one float32 leaf instead of the
    whole float32 tree (8 GB of a 16 GB chip)."""
    ks = jax.random.split(rng, cfg.n_layers + 3)
    e = cfg.embed_dim or cfg.d_model
    params: Params = {
        "tok_embed": (
            jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), jnp.float32)
            * 0.02
        ).astype(dtype),
        "pos_embed": (
            jax.random.normal(ks[1], (cfg.max_len, cfg.d_model), jnp.float32)
            * 0.02
        ).astype(dtype),
        "ln_f_scale": jnp.ones((cfg.d_model,), dtype),
        "head": (
            jax.random.normal(ks[2], (cfg.d_model, e), jnp.float32)
            * (1.0 / math.sqrt(cfg.d_model))
        ).astype(dtype),
        "blocks": [
            _init_block(ks[3 + i], cfg, dtype) for i in range(cfg.n_layers)
        ],
    }
    return params


def param_specs(cfg: TransformerConfig) -> Params:
    """PartitionSpecs: tensor-parallel over the `model` mesh axis.

    qkv/ff_in are column-parallel (output dim sharded); o/ff_out are
    row-parallel (input dim sharded) so XLA places one psum per block half.
    Embeddings shard the vocab/feature dim; norms are replicated.
    """
    block = {
        "qkv": P(None, "model"),
        "o": P("model", None),
        "ff_in": P(None, "model"),
        "ff_out": P("model", None),
        "ln1_scale": P(None),
        "ln2_scale": P(None),
    }
    return {
        "tok_embed": P("model", None),
        "pos_embed": P(None, None),
        "ln_f_scale": P(None),
        "head": P(None, "model"),
        "blocks": [dict(block) for _ in range(cfg.n_layers)],
    }


def shard_params(params: Params, mesh: Mesh, cfg: TransformerConfig) -> Params:
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def cast_params(params: Params, dtype: Any = jnp.bfloat16) -> Params:
    """bf16-resident inference params: cast once instead of per matmul.

    Training keeps the f32 master copy; serving paths (encode/generate)
    run on the cast tree so weight reads from HBM are half-width and no
    cast ops appear inside the jitted program.
    """
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params,
    )


# ----------------------------------------------------------------- forward


# The block's parts run under `jax.named_scope` (norm, attn, ff,
# cache_write, logits): operation metadata that a profiler trace shows per
# operation and that changes nothing in the compiled program.


def _rmsnorm(x: Array, scale: Array) -> Array:
    with jax.named_scope("norm"):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + 1e-6) * scale).astype(x.dtype)


_FUSED_ATTN_ENV: bool | None = None


def _use_fused_attention() -> bool:
    # the kill switch is read ONCE per process: _attention runs inside
    # jit traces, and an env read per trace is the hot-path bug class
    # the repo lint bans (PR 9(h))
    global _FUSED_ATTN_ENV
    if _FUSED_ATTN_ENV is None:
        import os

        _FUSED_ATTN_ENV = (
            os.environ.get("PATHWAY_TPU_FUSED_ATTN", "1") != "0"
        )
    return _FUSED_ATTN_ENV and jax.default_backend() == "tpu"


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
    elif not cfg.causal and cfg.fused_attention and _use_fused_attention():
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


def _ffn(x: Array, block: Params, cfg: TransformerConfig) -> Array:
    with jax.named_scope("ff"):
        hline = jnp.einsum(
            "bsd,df->bsf", x, block["ff_in"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
        hline = jax.nn.gelu(hline).astype(cfg.dtype)
        return jnp.einsum(
            "bsf,fd->bsd", hline, block["ff_out"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        ).astype(cfg.dtype)


def _block_fwd(
    x: Array, block: Params, cfg: TransformerConfig, mask: Array, token_mask: Array
) -> Array:
    xin = _rmsnorm(x, block["ln1_scale"])
    with jax.named_scope("attn"):
        x = x + _attention(xin, block, cfg, mask, token_mask)
    x = x + _ffn(_rmsnorm(x, block["ln2_scale"]), block, cfg)
    return x


def _build_mask(token_mask: Array, causal: bool) -> Array:
    # token_mask: [b, s] 1/0 valid; returns [b, 1, q, k] bool
    b, s = token_mask.shape
    attend = token_mask[:, None, None, :].astype(bool)
    if causal:
        tri = jnp.tril(jnp.ones((s, s), bool))
        attend = attend & tri[None, None, :, :]
    return attend


def forward(
    params: Params, token_ids: Array, token_mask: Array, cfg: TransformerConfig
) -> Array:
    """Hidden states [b, s, d_model]."""
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
    mask = _build_mask(token_mask, cfg.causal)
    blk = functools.partial(_block_fwd, cfg=cfg, mask=mask, token_mask=token_mask)
    for block in params["blocks"]:
        x = jax.checkpoint(blk)(x, block)
    return _rmsnorm(x, params["ln_f_scale"])


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


# ---------------------------------------------------------------- decoding


def init_kv_cache(cfg: TransformerConfig, batch: int) -> Params:
    shape = (cfg.n_layers, batch, cfg.max_len, cfg.n_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def decode_step(
    params: Params,
    cache: Params,
    token: Array,  # [b] current token ids
    pos: Array,  # scalar int32 position
    cfg: TransformerConfig,
    pad_len: Array | None = None,  # [b] left-pad lengths (batched serving)
) -> tuple[Array, Params]:
    """One autoregressive step with KV cache; returns ([b, vocab], cache).

    With `pad_len` the batch is LEFT-padded: each row's logical position
    is pos - pad_len (continuing the prefill's mask-cumsum positions) and
    pad cache slots never enter attention — a row's tokens match what an
    unpadded single-prompt run would produce."""
    b = token.shape[0]
    h, dh = cfg.n_heads, cfg.head_dim
    x = params["tok_embed"].astype(cfg.dtype)[token][:, None, :]  # [b,1,d]
    mask_len = cfg.max_len
    if pad_len is None:
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_embed"].astype(cfg.dtype), pos, 1, axis=0
        )[None]
        kmask = (jnp.arange(mask_len) <= pos)[None, None, None, :]
    else:
        x = x + params["pos_embed"].astype(cfg.dtype)[pos - pad_len][:, None, :]
        kmask = (
            (jnp.arange(mask_len)[None, :] <= pos)
            & (jnp.arange(mask_len)[None, :] >= pad_len[:, None])
        )[:, None, None, :]
    for li, block in enumerate(params["blocks"]):
        xin = _rmsnorm(x, block["ln1_scale"])
        with jax.named_scope("attn"):
            qkv = jnp.einsum(
                "bsd,de->bse", xin, block["qkv"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, 1, h, dh)
            k = k.reshape(b, 1, h, dh)
            v = v.reshape(b, 1, h, dh)
        with jax.named_scope("cache_write"):
            cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], k[None], (li, 0, pos, 0, 0)
            )
            cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], v[None], (li, 0, pos, 0, 0)
            )
        with jax.named_scope("attn"):
            keys, vals = cache["k"][li], cache["v"][li]  # [b, S, h, dh]
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, keys, preferred_element_type=jnp.float32
            ) / math.sqrt(dh)
            scores = jnp.where(kmask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            ctx = jnp.einsum(
                "bhqk,bkhd->bqhd", probs, vals, preferred_element_type=jnp.float32
            ).astype(cfg.dtype).reshape(b, 1, cfg.d_model)
            attn_out = jnp.einsum(
                "bsd,de->bse", ctx, block["o"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
            x = x + attn_out
        x = x + _ffn(_rmsnorm(x, block["ln2_scale"]), block, cfg)
    hline = _rmsnorm(x, params["ln_f_scale"])
    with jax.named_scope("logits"):
        lg = jnp.einsum(
            "bsd,vd->bsv", hline, params["tok_embed"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
    return lg[:, 0, :], cache


def prefill(
    params: Params,
    prompt_ids: Array,
    cache: Params,
    cfg: TransformerConfig,
    prompt_mask: Array | None = None,
) -> tuple[Array, Params]:
    """One batched causal forward over the whole prompt, writing every
    layer's K/V into the cache. Returns (last-position logits [b, vocab],
    cache). This is ONE XLA program over [b, p] — prefill cost does not
    serialize over prompt length the way per-token decode would.

    With `prompt_mask` the batch is LEFT-padded (pad tokens first, real
    tokens end at p-1 so the last-position logits are every row's next-
    token logits): real tokens take positions 0..len-1 via the mask
    cumsum and pad keys are masked out, so a padded row's outputs equal
    an unpadded single-prompt run.
    """
    b, p = prompt_ids.shape
    h, dh = cfg.n_heads, cfg.head_dim
    x = params["tok_embed"].astype(cfg.dtype)[prompt_ids]
    if prompt_mask is None:
        x = x + params["pos_embed"].astype(cfg.dtype)[None, :p, :]
        mask = _build_mask(jnp.ones((b, p), jnp.int32), causal=True)
    else:
        pos_idx = jnp.clip(jnp.cumsum(prompt_mask, axis=1) - 1, 0, None)
        x = x + params["pos_embed"].astype(cfg.dtype)[pos_idx]
        mask = _build_mask(prompt_mask, causal=True)
    for li, block in enumerate(params["blocks"]):
        xin = _rmsnorm(x, block["ln1_scale"])
        with jax.named_scope("attn"):
            qkv = jnp.einsum(
                "bsd,de->bse", xin, block["qkv"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, p, h, dh)
            k = k.reshape(b, p, h, dh)
            v = v.reshape(b, p, h, dh)
        with jax.named_scope("cache_write"):
            cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], k[None], (li, 0, 0, 0, 0)
            )
            cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], v[None], (li, 0, 0, 0, 0)
            )
        with jax.named_scope("attn"):
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
            ) / math.sqrt(dh)
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            ctx = jnp.einsum(
                "bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32
            ).astype(cfg.dtype).reshape(b, p, cfg.d_model)
            attn_out = jnp.einsum(
                "bsd,de->bse", ctx, block["o"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
            x = x + attn_out
        x = x + _ffn(_rmsnorm(x, block["ln2_scale"]), block, cfg)
    hlast = _rmsnorm(x[:, -1:, :], params["ln_f_scale"])
    with jax.named_scope("logits"):
        lg = jnp.einsum(
            "bsd,vd->bsv", hlast, params["tok_embed"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
    return lg[:, 0, :], cache


def generate(
    params: Params,
    prompt_ids: Array,  # [b, p]
    n_steps: int,
    cfg: TransformerConfig,
    temperature: float = 0.0,
    rng: Array | None = None,
    prompt_mask: Array | None = None,  # [b, p] 1/0, LEFT-padded batches
) -> Array:
    """Batched prefill + `lax.scan` decode. Returns [b, p + n_steps].

    `prompt_mask` enables serving-style batching of heterogeneous
    prompts: left-pad every prompt to a common length, pass the validity
    mask, and each row generates exactly what an unpadded single-prompt
    run would (mask-cumsum positions; pad slots never attend)."""
    toks, _cache = generate_serving(
        params, prompt_ids, init_kv_cache(cfg, prompt_ids.shape[0]),
        n_steps, cfg, temperature=temperature, rng=rng,
        prompt_mask=prompt_mask,
    )
    return toks


def generate_serving(
    params: Params,
    prompt_ids: Array,  # [b, p]
    cache: Params,  # KV cache for batch b (init_kv_cache shape)
    n_steps: int,
    cfg: TransformerConfig,
    temperature: float = 0.0,
    rng: Array | None = None,
    prompt_mask: Array | None = None,
) -> tuple[Array, Params]:
    """`generate` for the serving loop: the KV cache is an ARGUMENT and
    is returned, so a dispatch site can keep one persistent cache buffer
    per batch bucket and jit with `donate_argnums` on it — XLA then
    reuses the (hundreds of MB at Gemma shapes) allocation in place
    across dispatches instead of re-allocating per call. Stale cache
    contents from a previous wave are harmless: prefill rewrites
    positions 0..p-1, decode writes p..p+n-1, and the attention masks
    never read past the current position."""
    b, p = prompt_ids.shape
    if p + n_steps > cfg.max_len:
        raise ValueError(
            f"prompt ({p}) + n_steps ({n_steps}) exceeds max_len ({cfg.max_len})"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("sampled generation (temperature > 0) requires rng")
    first_logits, cache = prefill(params, prompt_ids, cache, cfg, prompt_mask)
    pad_len = (
        None
        if prompt_mask is None
        else (p - jnp.sum(prompt_mask, axis=1)).astype(jnp.int32)
    )

    def pick(lg: Array, key):
        if temperature > 0.0:
            key, sub = jax.random.split(key)
            return jax.random.categorical(sub, lg / temperature).astype(jnp.int32), key
        return jnp.argmax(lg, -1).astype(jnp.int32), key

    key = rng
    first_tok, key = pick(first_logits, key)

    def body(carry, i):
        cache, tok, key = carry
        lg, cache = decode_step(params, cache, tok, p + i, cfg, pad_len=pad_len)
        nxt, key = pick(lg, key)
        # emit the token being consumed this step; the carry holds the next
        return (cache, nxt, key), tok

    (cache, _last_tok, _), toks = jax.lax.scan(
        body, (cache, first_tok, key), jnp.arange(n_steps)
    )
    return jnp.concatenate([prompt_ids, toks.T], axis=1), cache


def prefill_into_slot(
    params: Params,
    prompt_ids: Array,  # [1, P] LEFT-padded (pad_left_rows convention)
    prompt_mask: Array,  # [1, P] 1/0
    cache: Params,  # multi-slot serving cache (init_kv_cache shape)
    slot: Array,  # scalar int32 — which cache row this request owns
    cfg: TransformerConfig,
) -> tuple[Array, Params]:
    """Prefill ONE request into row `slot` of a multi-slot serving cache
    (continuous batching). Runs the standard b=1 left-padded prefill into
    a scratch single-row cache and scatters that row into `cache` at the
    slot. `slot` is a traced scalar, so one compiled program serves every
    slot of the bucket — a request joining an in-flight batch costs zero
    new XLA compilations once its prompt bucket is warm. Returns (first
    decoded token [1] int32, cache); argmax decoding, matching the
    temperature-0 `generate_serving` path bit for bit per row."""
    lg, mini = prefill(params, prompt_ids, init_kv_cache(cfg, 1), cfg, prompt_mask)
    with jax.named_scope("cache_write"):
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], mini["k"], (0, slot, 0, 0, 0)
        )
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], mini["v"], (0, slot, 0, 0, 0)
        )
    with jax.named_scope("logits"):
        return jnp.argmax(lg, -1).astype(jnp.int32), cache


def decode_step_slots(
    params: Params,
    cache: Params,
    token: Array,  # [b] int32 — the token each slot consumes this step
    pos: Array,  # [b] int32 — per-slot physical write position
    pad_len: Array,  # [b] int32 — per-slot left-pad length
    cfg: TransformerConfig,
) -> tuple[Array, Params]:
    """One decode step where every batch row is an INDEPENDENT request at
    its own sequence position (continuous batching). Unlike
    :func:`decode_step`, which advances a wave-aligned batch at one shared
    scalar position, here `token`/`pos`/`pad_len` are per-row vectors: row
    i consumes ``token[i]``, writes its K/V at physical position
    ``pos[i]`` of its own cache slot, and attends over
    ``[pad_len[i], pos[i]]`` — its left-padded prompt plus the tokens it
    has decoded so far. Rows never read each other's slots, so a freshly
    prefilled request is correct from its first step even though its
    neighbours are mid-generation. Returns (next token [b] int32, cache);
    argmax decoding, bit-identical per row to the wave-aligned path."""
    b = token.shape[0]
    h, dh = cfg.n_heads, cfg.head_dim
    x = params["tok_embed"].astype(cfg.dtype)[token][:, None, :]
    x = x + params["pos_embed"].astype(cfg.dtype)[pos - pad_len][:, None, :]
    mask_len = cfg.max_len
    kmask = (
        (jnp.arange(mask_len)[None, :] <= pos[:, None])
        & (jnp.arange(mask_len)[None, :] >= pad_len[:, None])
    )[:, None, None, :]
    rows = jnp.arange(b)
    for li, block in enumerate(params["blocks"]):
        xin = _rmsnorm(x, block["ln1_scale"])
        with jax.named_scope("attn"):
            qkv = jnp.einsum(
                "bsd,de->bse", xin, block["qkv"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, 1, h, dh)
            k = k.reshape(b, h, dh)
            v = v.reshape(b, h, dh)
        with jax.named_scope("cache_write"):
            cache["k"] = cache["k"].at[li, rows, pos].set(k)
            cache["v"] = cache["v"].at[li, rows, pos].set(v)
        with jax.named_scope("attn"):
            keys, vals = cache["k"][li], cache["v"][li]  # [b, S, h, dh]
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, keys, preferred_element_type=jnp.float32
            ) / math.sqrt(dh)
            scores = jnp.where(kmask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            ctx = jnp.einsum(
                "bhqk,bkhd->bqhd", probs, vals, preferred_element_type=jnp.float32
            ).astype(cfg.dtype).reshape(b, 1, cfg.d_model)
            attn_out = jnp.einsum(
                "bsd,de->bse", ctx, block["o"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
            x = x + attn_out
        x = x + _ffn(_rmsnorm(x, block["ln2_scale"]), block, cfg)
    hline = _rmsnorm(x, params["ln_f_scale"])
    with jax.named_scope("logits"):
        lg = jnp.einsum(
            "bsd,vd->bsv", hline, params["tok_embed"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
        return jnp.argmax(lg[:, 0, :], -1).astype(jnp.int32), cache


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
