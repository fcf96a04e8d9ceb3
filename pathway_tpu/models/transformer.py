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
- The causal decode path keeps a KV cache laid out head-major,
  [layers, B, kv heads, S, Dh] (the decoding section says why).
- The decoder is a list of layers (`LayerSpec`): attention over every
  earlier position or over a window whose cache rows are a ring, learned,
  rotary or no positions, a dense GELU or SwiGLU feed-forward or routed
  ReGLU experts, with key/value heads shared by groups of query heads; a
  layer's mixer is softmax attention, softmax attention over blocks of keys
  it chooses by a score over pooled keys (`sparse`), or a linear recurrence
  with a decay a head and a state instead of rows of keys (`linear`), or
  attention whose keys and values are products of one low-rank row a
  position, which is all the cache keeps (`latent`). A routed expert branch
  may start beside one layer's feed-forward and land beside the next's
  (`shortcut`), and an expert layer may hold a share of the experts its
  router chooses among. The default list is the plain block above; see the
  decoding section.
"""

from __future__ import annotations

import contextlib
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
class LayerSpec:
    """One decoder layer's kinds. The default is the block every layer
    ran before there was a list: attention over every earlier position,
    positions from the learned table, a dense GELU feed-forward."""

    window: int | None = None  # None: every earlier position; W: the last W
    pos: str = "learned"  # learned (the table, added to the embedding) | rotary | none
    # gelu (dense) | swiglu (dense, silu(gate) * up) | experts (routed ReGLU,
    # top n_active of n_experts)
    ff: str = "gelu"
    # softmax (attention over the keys `window` allows) | sparse (over the
    # blocks of keys `SparseSpec` chooses for each query) | linear (no
    # softmax: a decayed sum of k^T v, kept as a state) | latent (softmax
    # attention whose keys and values are products of one low-rank row a
    # position, `LatentSpec`, which is all the cache keeps)
    mixer: str = "softmax"
    # a routed expert branch beside the layer's own feed-forward, over two
    # layers: "start" computes it from this layer's normed rows (the ones
    # its feed-forward reads) and hands it on, "land" adds what the last
    # "start" handed on where its own feed-forward's output goes
    shortcut: str | None = None


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """What a `sparse` layer chooses its keys by (InfLLM v2's sizes). Keys
    are pooled by their mean over `kernel` positions every `stride`; a
    query scores each pooled key it can see whole, a block of `block`
    positions scores the best of the pooled keys that overlap it, and the
    query attends block 0 .. `init_blocks` - 1, the blocks that hold its
    last `window` positions and the best others up to `topk` blocks, one
    set for the query heads that share a key head. A row of `dense_len`
    positions or fewer attends every earlier position."""

    topk: int = 64
    block: int = 64
    kernel: int = 32
    stride: int = 16
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192

    @property
    def local_blocks(self) -> int:
        return self.window // self.block


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """What a `latent` layer's attention is made of (multi-head latent
    attention). The query of a head is `nope_dim` lanes without positions
    and `rope_dim` rotary lanes, from a normed row of `q_rank`; a position
    keeps one normed row of `kv_rank`, from which every head's `nope_dim`
    key lanes and `v_dim` value lanes are products, and one rotary key of
    `rope_dim` that all heads share. `q_scale` and `kv_scale` multiply the
    two normed rows. Scores are over nope_dim + rope_dim lanes and scaled
    by their root."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    q_scale: float = 1.0
    kv_scale: float = 1.0

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim


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
    # The decoder's per-layer list (None: n_layers of LayerSpec()), and what
    # the kinds in it need. Key/value heads fewer than the query heads are
    # shared by n_heads / n_kv_heads query heads each (None: one each);
    # head_size is the width of a head where it is not d_model / n_heads;
    # d_ff is one expert's width in an `experts` layer; an untied model has
    # an output matrix `lm_head` of its own.
    layers: tuple[LayerSpec, ...] | None = None
    n_kv_heads: int | None = None
    head_size: int | None = None
    n_experts: int = 0
    n_active: int = 0
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    # What the `sparse` and `linear` mixers read, and the parts a block may
    # have around any mixer. qk_norm: q and k are RMS-normed over each
    # head's width with a learned scale; out_gate: every mixer's output
    # times sigmoid(h W_gate), element by element, before W_o;
    # linear_out_norm: a linear layer's output RMS-normed over each
    # head's width first. A linear layer has `linear_heads` heads (None:
    # n_heads) of head_dim, keys and values as many, and head h decays its
    # state by exp(-linear_slopes[h]) a position. The three scales are
    # MiniCPM's: the embedding times embed_scale, every residual branch
    # times residual_scale, the last norm's output times logit_scale.
    sparse: SparseSpec | None = None
    qk_norm: bool = False
    out_gate: bool = False
    linear_out_norm: bool = False
    linear_heads: int | None = None
    linear_slopes: tuple[float, ...] | None = None
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # What a `latent` layer reads, and what an expert layer may be beside
    # the first kind (a softmax over the chosen logits of the layer's
    # input, ReGLU experts, all of them here). router "all": the scores are
    # a softmax over every output of the router, read from the normed rows
    # the experts read; the n_active largest of score + `router_bias` (a
    # float32 leaf) are chosen, and a chosen expert's weight is its score
    # without the bias times router_scale, not renormalised. The router has
    # n_experts + n_zero_experts outputs: an index past n_experts is an
    # identity expert, whose output is its input. experts_held (first,
    # count): the experts whose matrices are here, of the n_experts the
    # router chooses among; a pair whose expert lies elsewhere adds nothing
    # here. expert_act: relu | silu, the gate's activation. norm_eps: the
    # epsilon of every RMS norm of the decoder.
    latent: LatentSpec | None = None
    router: str = "chosen"
    router_bias: bool = False
    router_scale: float = 1.0
    n_zero_experts: int = 0
    experts_held: tuple[int, int] | None = None
    expert_act: str = "relu"
    d_expert: int | None = None  # an expert's width where it is not d_ff
    norm_eps: float = 1e-6

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def layer_specs(self) -> tuple[LayerSpec, ...]:
        return self.layers or (LayerSpec(),) * self.n_layers

    @property
    def learned_positions(self) -> bool:
        return any(sp.pos == "learned" for sp in self.layer_specs)

    @property
    def window(self) -> int | None:
        """Rows a window layer keeps of a sequence (its ring's length)."""
        ws = {sp.window for sp in self.layer_specs if sp.window is not None}
        return min(ws.pop(), self.max_len) if ws else None

    @property
    def n_expert_layers(self) -> int:
        return sum(_has_experts(sp) for sp in self.layer_specs)

    @property
    def held(self) -> tuple[int, int]:
        """(first, count) of the experts whose matrices are here."""
        return self.experts_held or (0, self.n_experts)

    def n_mixer_layers(self, mixer: str) -> int:
        return sum(sp.mixer == mixer for sp in self.layer_specs)

    @property
    def lin_heads(self) -> int:
        return self.linear_heads or self.n_heads

    @property
    def plain(self) -> bool:
        """The one block the encoder and the training step run."""
        return (
            all(sp == LayerSpec() for sp in self.layer_specs)
            and self.kv_heads == self.n_heads
            and self.head_size is None
            and self.tie_embeddings
            and not (self.qk_norm or self.out_gate)
            and self.embed_scale == self.residual_scale == self.logit_scale == 1.0
            and self.latent is None
            and self.router == "chosen" and not self.router_bias
            and self.router_scale == 1.0 and not self.n_zero_experts
            and self.experts_held is None and self.expert_act == "relu"
            and self.d_expert is None and self.norm_eps == 1e-6
        )

    def __post_init__(self) -> None:
        if self.pool not in ("mean", "cls", "last"):
            raise ValueError(f"pool must be mean|cls|last, got {self.pool!r}")
        if self.head_size is None and self.d_model % self.n_heads != 0:
            raise ValueError("d_model must be divisible by n_heads")
        if self.n_heads % self.kv_heads != 0:
            raise ValueError("n_heads must be divisible by n_kv_heads")
        specs = self.layer_specs
        if len(specs) != self.n_layers:
            raise ValueError(
                f"layers lists {len(specs)} layers, n_layers is {self.n_layers}"
            )
        for sp in specs:
            if sp.pos not in ("learned", "rotary", "none"):
                raise ValueError(f"pos must be learned|rotary|none, got {sp.pos!r}")
            if sp.ff not in ("gelu", "swiglu", "experts"):
                raise ValueError(f"ff must be gelu|swiglu|experts, got {sp.ff!r}")
            if sp.mixer not in ("softmax", "sparse", "linear", "latent"):
                raise ValueError(
                    f"mixer must be softmax|sparse|linear|latent, got {sp.mixer!r}"
                )
            if sp.shortcut not in (None, "start", "land"):
                raise ValueError(f"shortcut must be start|land, got {sp.shortcut!r}")
            if sp.shortcut is not None and sp.ff == "experts":
                raise ValueError(
                    "a shortcut's branch lies beside a dense feed-forward"
                )
            if sp.mixer != "softmax" and sp.window is not None:
                raise ValueError("a window is a softmax layer's")
            if sp.mixer == "linear" and sp.pos == "learned":
                raise ValueError("a linear layer's positions are rotary or none")
        if len({sp.window for sp in specs if sp.window is not None}) > 1:
            # the window layers' rows are one stacked ring
            raise ValueError("the window layers of one decoder share one window")
        marks = [sp.shortcut for sp in specs if sp.shortcut is not None]
        if marks != ["start", "land"] * (len(marks) // 2):
            raise ValueError("every shortcut that starts lands before the next")
        if self.n_expert_layers and not (
            0 < self.n_active <= self.n_experts + self.n_zero_experts
        ):
            raise ValueError("experts layers need 0 < n_active <= n_experts")
        if self.router not in ("chosen", "all"):
            raise ValueError(f"router must be chosen|all, got {self.router!r}")
        if self.expert_act not in ("relu", "silu"):
            raise ValueError(f"expert_act must be relu|silu, got {self.expert_act!r}")
        if self.router == "chosen" and (
            self.router_bias or self.router_scale != 1.0 or self.n_zero_experts
        ):
            raise ValueError(
                "a selection bias, a scaling factor and identity experts are "
                "router \"all\"'s"
            )
        first, count = self.held
        if self.n_expert_layers and not (
            0 <= first and 0 < count and first + count <= self.n_experts
        ):
            raise ValueError("experts_held (first, count) lies inside n_experts")
        if self.n_mixer_layers("latent") and self.latent is None:
            raise ValueError("latent layers need `latent` (a LatentSpec)")
        if self.latent is not None and self.latent.rope_dim % 2:
            raise ValueError("rotary lanes come in pairs")
        if self.n_mixer_layers("sparse"):
            sq = self.sparse
            if sq is None:
                raise ValueError("sparse layers need `sparse` (a SparseSpec)")
            if (
                sq.block % sq.stride or sq.kernel % sq.stride
                or sq.window % sq.block
                or self.max_len % sq.block
                or -(-sq.kernel // sq.stride) - 1 > sq.block // sq.stride
                or sq.init_blocks + sq.local_blocks > sq.topk
            ):
                raise ValueError(
                    "sparse: stride divides kernel and block, block divides window and "
                    "max_len, a pooled key overlaps two blocks at most, and "
                    "topk holds the init and local blocks"
                )
        if self.n_mixer_layers("linear") and (
            self.linear_slopes is None
            or len(self.linear_slopes) != self.lin_heads
        ):
            raise ValueError("linear layers need a slope for each linear head")
        if not self.plain and not self.causal:
            raise ValueError("the encoder runs the plain block only")


def embedder_config(**kw) -> TransformerConfig:
    """SBERT-class text encoder."""
    return TransformerConfig(causal=False, **kw)


def lm_config(**kw) -> TransformerConfig:
    """Gemma-class causal decoder."""
    kw.setdefault("pool", "last")
    return TransformerConfig(causal=True, **kw)


# ------------------------------------------------------------------ params


def _has_experts(spec: LayerSpec) -> bool:
    """Whether a layer holds a router and experts: as its feed-forward, or
    as the branch a shortcut starts beside it."""
    return spec.ff == "experts" or spec.shortcut == "start"


def _mixer_heads(cfg: TransformerConfig, spec: LayerSpec) -> tuple[int, int]:
    """A layer's query heads and its key/value heads."""
    if spec.mixer == "linear":
        return cfg.lin_heads, cfg.lin_heads
    return cfg.n_heads, cfg.kv_heads


def _init_block(
    rng: Array, cfg: TransformerConfig, dtype: Any = jnp.float32,
    spec: LayerSpec = LayerSpec(),
) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    h, hk = _mixer_heads(cfg, spec)
    hd = h * cfg.head_dim  # the query heads' width: d in the plain block
    kv = hk * cfg.head_dim
    ks = jax.random.split(rng, 6)
    s = 1.0 / math.sqrt(d)

    def leaf(key: Array, shape: tuple, scale: float) -> Array:
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    block = {"ln1_scale": jnp.ones((d,), dtype), "ln2_scale": jnp.ones((d,), dtype)}
    if spec.mixer == "latent":
        lt = cfg.latent
        kl = jax.random.split(ks[0], 4)
        block["q_a"] = leaf(kl[0], (d, lt.q_rank), s)
        block["q_a_norm"] = jnp.ones((lt.q_rank,), dtype)
        block["q_b"] = leaf(
            kl[1], (lt.q_rank, h * lt.qk_dim), 1.0 / math.sqrt(lt.q_rank)
        )
        block["kv_a"] = leaf(kl[2], (d, lt.kv_rank + lt.rope_dim), s)
        block["kv_a_norm"] = jnp.ones((lt.kv_rank,), dtype)
        block["kv_b"] = leaf(
            kl[3], (lt.kv_rank, h * (lt.nope_dim + lt.v_dim)),
            1.0 / math.sqrt(lt.kv_rank),
        )
        block["o"] = leaf(ks[1], (h * lt.v_dim, d), 1.0 / math.sqrt(h * lt.v_dim))
    else:
        block["qkv"] = leaf(ks[0], (d, hd + 2 * kv), s)
        block["o"] = leaf(ks[1], (hd, d), 1.0 / math.sqrt(hd))
    if cfg.qk_norm:
        block["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        block["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
    if cfg.out_gate:
        block["gate"] = leaf(jax.random.fold_in(ks[0], 1), (d, hd), s)
    if spec.mixer == "linear" and cfg.linear_out_norm:
        block["o_norm"] = jnp.ones((cfg.head_dim,), dtype)
    if _has_experts(spec):
        # the matrices of the experts held here, [count, ...]; the router
        # has an output for every expert there is, and the identity ones
        e, fe = cfg.held[1], cfg.d_expert or f
        n_out = cfg.n_experts + cfg.n_zero_experts
        kg, ku, kd = ks[2], ks[5], ks[3]
        if spec.shortcut == "start":  # the layer's own feed-forward draws from those
            kg, ku, kd = (jax.random.fold_in(key, 1) for key in (kg, ku, kd))
        block["router"] = leaf(ks[4], (d, n_out), s)
        if cfg.router_bias:
            block["router_bias"] = jnp.zeros((n_out,), jnp.float32)
        block["expert_gate"] = leaf(kg, (e, d, fe), s)
        block["expert_up"] = leaf(ku, (e, d, fe), s)
        block["expert_down"] = leaf(kd, (e, fe, d), 1.0 / math.sqrt(fe))
    if spec.ff == "swiglu":
        block["ff_gate"] = leaf(ks[2], (d, f), s)
        block["ff_up"] = leaf(ks[5], (d, f), s)
        block["ff_out"] = leaf(ks[3], (f, d), 1.0 / math.sqrt(f))
    elif spec.ff == "gelu":
        block["ff_in"] = leaf(ks[2], (d, f), s)
        block["ff_out"] = leaf(ks[3], (f, d), 1.0 / math.sqrt(f))
    return block


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
        "ln_f_scale": jnp.ones((cfg.d_model,), dtype),
        "head": (
            jax.random.normal(ks[2], (cfg.d_model, e), jnp.float32)
            * (1.0 / math.sqrt(cfg.d_model))
        ).astype(dtype),
        "blocks": [
            _init_block(ks[3 + i], cfg, dtype, spec)
            for i, spec in enumerate(cfg.layer_specs)
        ],
    }
    if cfg.learned_positions:
        params["pos_embed"] = (
            jax.random.normal(ks[1], (cfg.max_len, cfg.d_model), jnp.float32)
            * 0.02
        ).astype(dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(
                jax.random.fold_in(ks[2], 1), (cfg.d_model, cfg.vocab_size),
                jnp.float32,
            ) * (1.0 / math.sqrt(cfg.d_model))
        ).astype(dtype)
    return params


def param_specs(cfg: TransformerConfig) -> Params:
    """PartitionSpecs: tensor-parallel over the `model` mesh axis.

    qkv/ff_in are column-parallel (output dim sharded); o/ff_out are
    row-parallel (input dim sharded) so XLA places one psum per block half.
    Embeddings shard the vocab/feature dim; norms are replicated. An
    experts layer is expert-parallel: the expert axis is the sharded one.
    """
    def block(spec: LayerSpec) -> Params:
        out = {"o": P("model", None), "ln1_scale": P(None), "ln2_scale": P(None)}
        if spec.mixer == "latent":
            # the low-rank rows are whole on every chip; the heads are split
            out["q_a"] = out["kv_a"] = P(None, None)
            out["q_a_norm"] = out["kv_a_norm"] = P(None)
            out["q_b"] = out["kv_b"] = P(None, "model")
        else:
            out["qkv"] = P(None, "model")
        if cfg.qk_norm:
            out["q_norm"] = out["k_norm"] = P(None)
        if cfg.out_gate:
            out["gate"] = P(None, "model")
        if spec.mixer == "linear" and cfg.linear_out_norm:
            out["o_norm"] = P(None)
        if _has_experts(spec):
            out["router"] = P(None, None)
            if cfg.router_bias:
                out["router_bias"] = P(None)
            for name in ("expert_gate", "expert_up", "expert_down"):
                out[name] = P("model", None, None)
        if spec.ff == "swiglu":
            out["ff_gate"] = out["ff_up"] = P(None, "model")
            out["ff_out"] = P("model", None)
        elif spec.ff == "gelu":
            out["ff_in"] = P(None, "model")
            out["ff_out"] = P("model", None)
        return out

    specs = {
        "tok_embed": P("model", None),
        "ln_f_scale": P(None),
        "head": P(None, "model"),
        "blocks": [block(spec) for spec in cfg.layer_specs],
    }
    if cfg.learned_positions:
        specs["pos_embed"] = P(None, None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "model")
    return specs


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


def _rmsnorm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    with jax.named_scope("norm"):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


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
    elif (
        not cfg.causal and cfg.fused_attention
        and jax.default_backend() == "tpu"
    ):
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
        if "ff_gate" in block:  # swiglu: silu(x W_gate) * (x W_up), then W_out
            gate = jax.nn.silu(jnp.einsum(
                "bsd,df->bsf", x, block["ff_gate"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            )).astype(cfg.dtype)
            hline = (jnp.einsum(
                "bsd,df->bsf", x, block["ff_up"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            ) * gate).astype(cfg.dtype)
            return jnp.einsum(
                "bsf,fd->bsd", hline, block["ff_out"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
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
    if not cfg.plain:
        raise NotImplementedError(
            "forward (encode, logits, the training step) runs the plain "
            "block; a decoder of other kinds is served by prefill and "
            "decode_step"
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
#
# One decoder, a list of layers. Each layer's kinds (`LayerSpec`) choose, at
# trace time, which rows of the cache it writes and reads, whether rotary
# turns q and k, and which feed-forward runs; the default list is the plain
# block, whose two slot programs lower to what they always were. The
# wave-aligned step (`decode_step`) is the slot step with every row at one
# position.
#
# The cache is a dict of stacked leaves, a pair for each attention kind the
# list holds, laid out head-major: "k"/"v" [global layers, slots, kv heads,
# max_len, head] grow with the sequence; "k_win"/"v_win" [window layers,
# slots, kv heads, W, head] are rings: physical position t lives in row
# t mod W, so a window layer's rows stop growing at W. Physical positions
# count the left pad too; logical ones (physical less the pad) are what
# rotary turns by. Head-major, because a head's rows are then whole
# (rows, head) tiles whatever the number of heads: the step's kernel
# fetches blocks of them out of the leaf itself, and where the heads are
# narrower than a lane tile (64) the rows are what fills the other axis,
# not 25 heads padded to 32.
#
# Two attentions read it, each where a rule on what the code can see says
# so and nothing else does. A prefill's, over the whole prompt:
# ops/attention.py `prefill_attention` where `prefill_uses_kernel` holds.
# A step's, one query a slot: ops/attention.py `decode_attention` where
# `step_uses_kernel` holds; it takes the stacked leaf as its operand and
# fetches only the tiles that hold a live row of the slot. Everywhere else
# (the CPU, heads of 64, tensor-parallel parameters) the plain `_attend`.
#
# A layer's mixer need not be that attention. A `sparse` layer keeps rows
# of keys too, but at LOGICAL positions ("k_sparse"/"v_sparse": the prefill
# turns the left pad behind the prompt), and a pooled key every `stride`
# positions ("k_pool"); each query scores the pooled keys it sees whole,
# chooses blocks of rows by them and attends those (`select_blocks`, or
# its kernel `sparse_select`; ops/sparse_attention.py where
# `sparse_prefill_uses_kernel` / `sparse_step_uses_kernel` hold, `_attend`
# under the blocks' mask elsewhere; up to `dense_len` positions a row
# attends every earlier key through the attentions above). A `linear`
# layer keeps no rows at all: its leaf "state" is a float32
# [heads, dh, dh] sum a slot, which a prefill's
# chunked scan leaves after the last token (`linear_scan`;
# ops/linear_attention.py where `linear_prefill_uses_kernel` holds) and a
# step decays and adds to. A `latent` layer keeps rows without a head axis,
# "c_kv" and "k_rope", at physical positions: a prefill expands its own
# rows' keys and values from them, a step attends them as they lie
# (ops/latent_attention.py where `latent_prefill_uses_kernel` /
# `latent_step_uses_kernel` hold; the section "the latent mixer" below).

# what an experts decoder's two programs append to the tokens they return,
# in this order (ContinuousBatcher adds them into its `stats`); the pairs
# are those of the experts held here (`cfg.held`)
PREFILL_COUNTERS = ("routed_pairs", "expert_load_max")
STEP_COUNTERS = ("experts_touched", "moe_layers_run")
# and what a decoder with sparse or linear layers appends behind those, to
# both programs: summed over real queries, key heads and sparse layers the
# blocks a query attended and the blocks at or before it, and the real
# tokens a prefill's linear layers scanned, summed over those layers (a
# step sends 0 there)
MIXER_COUNTERS = ("sparse_blocks_read", "sparse_blocks_visible", "linear_tokens")
# what a prefill appends behind those where the router chooses among more
# than the experts held here (a share of them, or identity experts beside
# them), summed over the real tokens and the expert layers: every pair the
# router made (tokens x n_active), those that chose an identity expert, and
# those whose expert lies on another chip. `routed_pairs` are then the
# pairs computed here, and the three add up to `router_pairs`
SHARE_COUNTERS = ("router_pairs", "zero_pairs", "absent_pairs")
# and a step of a decoder with latent layers: the latent rows its occupied
# slots attended, summed over those layers
LATENT_COUNTERS = ("latent_rows_read",)


def _has_mixers(cfg: TransformerConfig) -> bool:
    return any(sp.mixer in ("sparse", "linear") for sp in cfg.layer_specs)


def _has_shares(cfg: TransformerConfig) -> bool:
    return bool(cfg.n_expert_layers) and (
        cfg.experts_held is not None or cfg.n_zero_experts > 0
    )


def prefill_counters(cfg: TransformerConfig) -> tuple[str, ...]:
    """The counters `prefill_into_slot` appends to its token, in order."""
    return (
        PREFILL_COUNTERS * bool(cfg.n_expert_layers)
        + MIXER_COUNTERS * _has_mixers(cfg)
        + SHARE_COUNTERS * _has_shares(cfg)
    )


def step_counters(cfg: TransformerConfig) -> tuple[str, ...]:
    """The counters `decode_step_slots` appends to its tokens, in order."""
    return (
        STEP_COUNTERS * bool(cfg.n_expert_layers)
        + MIXER_COUNTERS * _has_mixers(cfg)
        + LATENT_COUNTERS * bool(cfg.n_mixer_layers("latent"))
    )


# The slot cache's leaves, by the kind of layer that keeps them. Every leaf
# is stacked over the layers of its kind and has the slot second:
# [layers of the kind, slots, ...]. `k`/`v` (softmax over every earlier
# position) and `k_win`/`v_win` (a window's ring) hold rows at physical
# positions; `k_sparse`/`v_sparse` hold a sparse layer's rows at LOGICAL
# positions (the left pad taken off, so that a block of the selection is a
# block of rows) with `k_pool`, the pooled keys, one every `stride`
# positions; `state` is a linear layer's float32 sum, [heads, dh, dh];
# `c_kv` [.., rows, kv_rank] and `k_rope` [.., rows, rope lanes] are a latent
# layer's rows at physical positions, without a head axis: the normed (and
# scaled) low-rank row every head's keys and values are products of, and
# the one rotated key all heads share (`_rope_lanes`: its rope_dim lanes
# in a whole lane tile, zeros behind them).
_SLOT_AXIS = 1


def _layer_kind(spec: LayerSpec) -> str:
    if spec.mixer != "softmax":
        return spec.mixer
    return "global" if spec.window is None else "window"


_KIND_LEAVES = {
    "global": {"k": "k", "v": "v"},
    "window": {"k": "k_win", "v": "v_win"},
    "sparse": {"k": "k_sparse", "v": "v_sparse", "pool": "k_pool"},
    "linear": {"state": "state"},
    "latent": {"c": "c_kv", "rope": "k_rope"},
}


def init_kv_cache(cfg: TransformerConfig, batch: int) -> Params:
    kinds = [_layer_kind(sp) for sp in cfg.layer_specs]
    n = {kind: kinds.count(kind) for kind in _KIND_LEAVES}
    hk, dh = cfg.kv_heads, cfg.head_dim
    cache = {}
    if n["global"]:
        shape = (n["global"], batch, hk, cfg.max_len, dh)
        cache["k"] = jnp.zeros(shape, cfg.dtype)
        cache["v"] = jnp.zeros(shape, cfg.dtype)
    if n["window"]:
        ring = (n["window"], batch, hk, cfg.window, dh)
        cache["k_win"] = jnp.zeros(ring, cfg.dtype)
        cache["v_win"] = jnp.zeros(ring, cfg.dtype)
    if n["sparse"]:
        rows = (n["sparse"], batch, hk, cfg.max_len, dh)
        cache["k_sparse"] = jnp.zeros(rows, cfg.dtype)
        cache["v_sparse"] = jnp.zeros(rows, cfg.dtype)
        cache["k_pool"] = jnp.zeros(
            (n["sparse"], batch, hk, cfg.max_len // cfg.sparse.stride, dh),
            cfg.dtype,
        )
    if n["linear"]:
        cache["state"] = jnp.zeros(
            (n["linear"], batch, cfg.lin_heads, dh, dh), jnp.float32
        )
    if n["latent"]:
        lt = cfg.latent
        cache["c_kv"] = jnp.zeros(
            (n["latent"], batch, cfg.max_len, lt.kv_rank), cfg.dtype
        )
        cache["k_rope"] = jnp.zeros(
            (n["latent"], batch, cfg.max_len, _rope_lanes(cfg)), cfg.dtype
        )
    return cache


def _cache_rows(cfg: TransformerConfig) -> list[tuple[dict[str, str], int]]:
    """Per layer: its cache leaves by what they hold (`_KIND_LEAVES` of the
    layer's kind) and its index along their layer axis."""
    out, n = [], dict.fromkeys(_KIND_LEAVES, 0)
    for sp in cfg.layer_specs:
        kind = _layer_kind(sp)
        out.append((_KIND_LEAVES[kind], n[kind]))
        n[kind] += 1
    return out


def _qkv_product(xin: Array, block: Params, cfg: TransformerConfig) -> Array:
    """Normed rows times the layer's qkv matrix: [b, s, (heads + 2 kv
    heads) * dh], the heads of q, k and v side by side."""
    return jnp.einsum(
        "bsd,de->bse", xin, block["qkv"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    ).astype(cfg.dtype)


def _qkv(xin: Array, block: Params, cfg: TransformerConfig, spec: LayerSpec):
    """q [b, s, heads, dh] and k, v [b, s, kv heads, dh] of normed rows."""
    b, s, _ = xin.shape
    (h, hk), dh = _mixer_heads(cfg, spec), cfg.head_dim
    q, k, v = jnp.split(
        _qkv_product(xin, block, cfg), [h * dh, (h + hk) * dh], axis=-1
    )
    return (
        q.reshape(b, s, h, dh), k.reshape(b, s, hk, dh), v.reshape(b, s, hk, dh)
    )


def _qkv_rowwise(xin: Array, block: Params, cfg: TransformerConfig,
                 spec: LayerSpec, rope, live: Array):
    """`_qkv`, then `_rmsnorm` of q and k (`cfg.qk_norm`), `_rope` of both
    (a rotary layer; `rope` are ops/rowwise.py `rope_tables` of the rows'
    positions) and zeros for the keys of rows not `live` (a linear layer),
    where `rowwise_uses_kernel` holds: q and k each in one pass of
    ops/rowwise.py `rowwise_heads` over their lanes of the product, rounded
    where the program a TPU runs of those functions rounds (once, behind
    the rotation)."""
    # imported where it is traced: Pallas loads when a program first needs it
    from pathway_tpu.ops.rowwise import rowwise_heads

    b, s, _ = xin.shape
    (h, hk), dh = _mixer_heads(cfg, spec), cfg.head_dim
    qkv = _qkv_product(xin, block, cfg)
    rope = rope if spec.pos == "rotary" else None
    with jax.named_scope("rowwise"):
        q = rowwise_heads(
            qkv, block["q_norm"] if cfg.qk_norm else None, rope, None,
            first=0, heads=h, dh=dh,
        )
        k = rowwise_heads(
            qkv, block["k_norm"] if cfg.qk_norm else None, rope,
            live if spec.mixer == "linear" else None, first=h, heads=hk, dh=dh,
        )
    v = qkv[..., (h + hk) * dh:]
    return (
        q.reshape(b, s, h, dh), k.reshape(b, s, hk, dh), v.reshape(b, s, hk, dh)
    )


def _rope(x: Array, pos: Array, cfg: TransformerConfig) -> Array:
    """Rotary positions, rotate-half over the head (x's last axis: a
    latent layer's rotary lanes are a part of a head): x [b, s, heads, dh],
    pos [b, s] logical positions."""
    with jax.named_scope("rope"):
        half = x.shape[-1] // 2
        freq = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos.astype(jnp.float32)[:, :, None, None] * freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x32 = x.astype(jnp.float32)
        x1, x2 = x32[..., :half], x32[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)


def _attend(q: Array, keys: Array, vals: Array, ok: Array,
            cfg: TransformerConfig) -> Array:
    """softmax(q k^T / sqrt(dh)) v over the keys `ok` [b, 1, q, s] allows:
    q [b, q, heads, dh], keys and vals [b, kv heads, s, dh] (the cache's
    layout) -> [b, q, heads * dh]. Query heads that share a key head read
    it where it lies: no key or value is repeated in memory."""
    b, nq, h, dh = q.shape
    hk = keys.shape[1]
    scores = jnp.einsum(
        "bqkgd,bksd->bkgqs", q.reshape(b, nq, hk, h // hk, dh), keys,
        preferred_element_type=jnp.float32,
    ) / math.sqrt(dh)
    scores = jnp.where(ok[:, :, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    ctx = jnp.einsum(
        "bkgqs,bksd->bkgqd", probs, vals, preferred_element_type=jnp.float32
    )
    return ctx.astype(cfg.dtype).transpose(0, 3, 1, 2, 4).reshape(b, nq, h * dh)


def _route(x: Array, block: Params, cfg: TransformerConfig):
    """The router, in float32: per token its n_active experts and their
    weights. `cfg.router` "chosen": on the layer's input (before the
    attention's norm), the weights the softmax over the chosen logits.
    "all": on the normed rows the experts read, the scores a softmax over
    every output; the largest of score + `router_bias` are chosen, and a
    chosen expert's weight is its score (without the bias) times
    `router_scale`, not renormalised."""
    with jax.named_scope("router"):
        logits = jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32),
            block["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if cfg.router == "chosen":
            top, idx = jax.lax.top_k(logits, cfg.n_active)
            return idx, jax.nn.softmax(top, axis=-1)
        scores = jax.nn.softmax(logits, axis=-1)
        by = scores + block["router_bias"] if cfg.router_bias else scores
        _, idx = jax.lax.top_k(by, cfg.n_active)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, (w * cfg.router_scale if cfg.router_scale != 1.0 else w)


def _experts(u: Array, idx: Array, w: Array, live: Array, block: Params,
             cfg: TransformerConfig):
    """Routed ReGLU experts over normed rows u [b, s, d]: the token-expert
    pairs are sorted by expert and each expert multiplies its own run of
    rows (a grouped product), whatever the run's length, so no pair is ever
    dropped. `live` [b, s] marks the rows that count. Returns the layer's
    output and how many live pairs each expert got [n_experts].

    Where `experts_use_kernel` holds the products are ops/experts.py
    `grouped_experts` (gate and up in one kernel with the ReLU product, the
    pair's weight in the down kernel) and the combine is its
    `combine_experts` (a token's rows fetched by index and summed);
    elsewhere three `ragged_dot` and the weighted sum. Products accumulate
    in float32 and a token's pairs are summed in float32 on both.

    Where the router chooses among more than the experts held here
    (`_has_shares`), `_experts_held`: the same products over the pairs of
    the held experts alone, and what it counted beside them."""
    if _has_shares(cfg):
        return _experts_held(u, idx, w, live, block, cfg)
    act = _EXPERT_ACTS[cfg.expert_act]
    with jax.named_scope("experts"):
        b, s, d = u.shape
        k, e = cfg.n_active, cfg.n_experts
        flat = idx.reshape(-1)  # the pairs, token-major
        # the pairs by expert, and their weights carried along by the sort
        _, order, by_expert_w = jax.lax.sort(
            (flat, jnp.arange(flat.size, dtype=jnp.int32), w.reshape(-1)),
            num_keys=1, is_stable=True,
        )
        # each expert's pairs, and those of live rows: one comparison and
        # two sums, not two scatters of every pair into the bins
        hit = flat[:, None] == jnp.arange(e, dtype=flat.dtype)
        sizes = jnp.sum(hit, axis=0, dtype=jnp.int32)
        counts = jnp.sum(
            hit & jnp.repeat(live.reshape(-1), k)[:, None], axis=0,
            dtype=jnp.int32,
        )
        rows = u.reshape(-1, d)[order // k]  # each pair's token, by expert
        # each pair's place there, a token's k side by side in [k, tokens]:
        # rows gathered by it are k slabs of whole [tokens, d] tiles, where
        # [tokens, k, d] pads k to a sublane tile in a pass of its own
        back = jnp.argsort(order).reshape(b * s, k).T
        if experts_use_kernel(cfg, b * s * k):
            # imported where it is traced: Pallas loads when a program
            # first needs it
            from pathway_tpu.ops.experts import combine_experts, grouped_experts

            # leaves of the activations' dtype (a served decoder's) are the
            # kernels' own operands, read where they lie: the cast is none
            y = grouped_experts(
                rows, by_expert_w, sizes,
                *(block[name].astype(cfg.dtype)
                  for name in ("expert_gate", "expert_up", "expert_down")),
                act=cfg.expert_act,
            )  # [pairs, d / 128, 128] float32, weighted, by expert
            y = combine_experts(y, back, cfg.dtype)
        else:
            def grouped(x: Array, name: str) -> Array:
                return jax.lax.ragged_dot(
                    x, block[name].astype(cfg.dtype), sizes,
                    preferred_element_type=jnp.float32,
                )

            hidden = (
                act(grouped(rows, "expert_gate")) * grouped(rows, "expert_up")
            ).astype(cfg.dtype)
            y = grouped(hidden, "expert_down")  # [pairs, d], by expert
            y = jnp.einsum("ktd,tk->td", y[back], w.reshape(-1, k))
        return y.astype(cfg.dtype).reshape(b, s, d), counts


_EXPERT_ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu}
# pairs a pass of `_experts_held` multiplies: more than an even router
# sends to 16 of 768 outputs from a prompt of 10,240 tokens and 12 picks
# (2,560), so that the first pass, which stands outside the loop, is as a
# rule the only one; a step's few pairs all go through in it
_HELD_CHUNK = 4096


def _experts_held(u: Array, idx: Array, w: Array, live: Array, block: Params,
                  cfg: TransformerConfig):
    """This chip's share of a routed expert layer over normed rows u
    [b, s, d]: the router chose among `n_experts` experts, of which the
    matrices of `cfg.held` = (first, count) are here, and
    `n_zero_experts` identity experts (an index past n_experts). A pair
    whose expert is held is computed; an identity pick adds weight x u, with
    no product; a pair whose expert lies on another chip adds nothing here
    (that chip computes it), and nothing stands in for it.

    The pairs are sorted by held expert, the others behind them, and only
    the held ones are multiplied: in passes of `_HELD_CHUNK` pairs, one
    always and then as many more as the held pairs fill (a loop whose
    length is the router's, so no pair is dropped however uneven it is;
    the first pass stands outside it, where a trace names its products by
    the leaves they read). A pass is the grouped product of
    `_experts`, by the kernels where `experts_use_kernel` holds of its
    pairs; its rows, weighted, are added to their tokens in float32.

    Returns the output and the counts [count + 3] int32 of live pairs: each
    held expert's, then the router's pairs, the identity picks and the
    pairs of absent experts."""
    with jax.named_scope("experts"):
        b, s, d = u.shape
        k, (first, count) = cfg.n_active, cfg.held
        t = b * s
        flat = idx.reshape(-1)  # the pairs, token-major
        real = flat < cfg.n_experts
        here = real & (flat >= first) & (flat < first + count)
        local = jnp.where(here, flat - first, count)  # count: not held here
        _, order, by_expert_w = jax.lax.sort(
            (local, jnp.arange(flat.size, dtype=jnp.int32), w.reshape(-1)),
            num_keys=1, is_stable=True,
        )
        alive = jnp.repeat(live.reshape(-1), k)
        hit = local[:, None] == jnp.arange(count, dtype=local.dtype)
        sizes = jnp.sum(hit, axis=0, dtype=jnp.int32)
        counts = jnp.sum(hit & alive[:, None], axis=0, dtype=jnp.int32)
        n_held = jnp.sum(sizes)
        edges = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
        chunk = min(_HELD_CHUNK, t * k)
        kernel = experts_use_kernel(cfg, chunk)
        leaves = [
            block[name].astype(cfg.dtype)
            for name in ("expert_gate", "expert_up", "expert_down")
        ]
        act = _EXPERT_ACTS[cfg.expert_act]
        flat_u = u.reshape(t, d)
        # the sorted order, with a pass's room behind it: the last pass may
        # hang over the pairs there are
        order = jnp.pad(order, (0, chunk))
        by_expert_w = jnp.pad(by_expert_w, (0, chunk))

        def one(lo, acc):
            at = jax.lax.dynamic_slice(order, (lo,), (chunk,))
            token = at // k
            held = lo + jnp.arange(chunk, dtype=jnp.int32) < n_held
            wt = jnp.where(
                held, jax.lax.dynamic_slice(by_expert_w, (lo,), (chunk,)), 0.0
            )
            rows = flat_u[token]
            part = jnp.clip(edges[1:], lo, lo + chunk) - jnp.clip(
                edges[:-1], lo, lo + chunk
            )  # each held expert's rows of this pass
            if kernel:
                # imported where it is traced: Pallas loads when a program
                # first needs it
                from pathway_tpu.ops.experts import grouped_experts

                # the kernels' groups fill their rows: what hangs over the
                # held pairs goes to the last expert, at weight 0
                part = part.at[-1].add(chunk - jnp.sum(part))
                y = grouped_experts(
                    rows, wt, part, *leaves, act=cfg.expert_act
                ).reshape(chunk, d)  # float32, weighted
            else:
                def grouped(x: Array, leaf: Array) -> Array:
                    return jax.lax.ragged_dot(
                        x, leaf, part, preferred_element_type=jnp.float32
                    )

                hidden = (
                    act(grouped(rows, leaves[0])) * grouped(rows, leaves[1])
                ).astype(cfg.dtype)
                y = grouped(hidden, leaves[2]) * wt[:, None]
            # a token's pairs, summed in float32 where the token lies
            return acc.at[jnp.where(held, token, t)].add(y, mode="drop")

        y = one(jnp.zeros((), jnp.int32), jnp.zeros((t, d), jnp.float32))
        if chunk < t * k:  # more pairs than a pass holds
            _, y = jax.lax.while_loop(
                lambda carry: carry[0] < n_held,
                lambda carry: (carry[0] + chunk, one(*carry)),
                (jnp.full((), chunk, jnp.int32), y),
            )
        if cfg.n_zero_experts:
            with jax.named_scope("zero_experts"):
                w_zero = jnp.sum(
                    jnp.where(real.reshape(t, k), 0.0, w.reshape(t, k)), axis=1
                )
                y = y + w_zero[:, None] * flat_u.astype(jnp.float32)
        tail = jnp.stack([
            jnp.sum(alive, dtype=jnp.int32),
            jnp.sum(~real & alive, dtype=jnp.int32),
            jnp.sum(real & ~here & alive, dtype=jnp.int32),
        ])
        return (
            y.astype(cfg.dtype).reshape(b, s, d), jnp.concatenate([counts, tail])
        )


def _lm_logits(hline: Array, params: Params, cfg: TransformerConfig) -> Array:
    with jax.named_scope("logits"):
        if cfg.logit_scale != 1.0:
            hline = (hline * cfg.logit_scale).astype(hline.dtype)
        if cfg.tie_embeddings:
            return jnp.einsum(
                "bsd,vd->bsv", hline, params["tok_embed"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            )
        return jnp.einsum(
            "bsd,dv->bsv", hline, params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )


def _embed(params: Params, token: Array, cfg: TransformerConfig) -> Array:
    x = params["tok_embed"].astype(cfg.dtype)[token]
    if cfg.embed_scale != 1.0:
        x = (x * cfg.embed_scale).astype(cfg.dtype)
    return x


def _branch(x: Array, y: Array, cfg: TransformerConfig) -> Array:
    """The residual stream plus a branch's output."""
    if cfg.residual_scale != 1.0:
        y = (y * cfg.residual_scale).astype(y.dtype)
    return x + y


def _layer(x, block, spec, cfg, pos, live, attend, counters, fused=False,
           rope=None, branch=None):
    """One decoder layer over rows x [b, s, d]. `attend(q, k, v)` writes the
    layer's keys and values (or its state) where they belong and returns
    the mixer's output (a latent layer's `attend(xin)` makes its own
    projections of the normed rows); `pos` [b, s] are logical positions,
    `live` [b, s] the rows that count (not padding, not a free slot). An
    experts layer appends its per-expert counts of live pairs to
    `counters["experts"]`.
    `fused`: `rowwise_uses_kernel` of the program's width, which a prefill
    asks once, and `rope` then its `rope_tables` if it has a rotary layer.
    Returns the rows and the expert branch in flight: what a layer whose
    `shortcut` is "start" computed from its normed rows, which the next
    "land" adds beside its feed-forward (`branch` is what came in)."""
    eps = cfg.norm_eps
    if spec.ff == "experts" and cfg.router == "chosen":
        idx, w = _route(x, block, cfg)
    xin = _rmsnorm(x, block["ln1_scale"], eps)
    if spec.mixer == "latent":
        ctx = attend(xin)
    else:
        with jax.named_scope("attn"):
            if fused and _takes_rowwise(cfg, spec):
                q, k, v = _qkv_rowwise(xin, block, cfg, spec, rope, live)
            else:
                q, k, v = _qkv(xin, block, cfg, spec)
                if cfg.qk_norm:
                    q = _rmsnorm(q, block["q_norm"], eps)
                    k = _rmsnorm(k, block["k_norm"], eps)
                if spec.pos == "rotary":
                    q, k = _rope(q, pos, cfg), _rope(k, pos, cfg)
        ctx = attend(q, k, v)
    with jax.named_scope("attn"):
        if cfg.out_gate:
            with jax.named_scope("gate"):
                gate = jax.nn.sigmoid(jnp.einsum(
                    "bsd,de->bse", xin, block["gate"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32,
                ))
                ctx = (ctx * gate).astype(cfg.dtype)
        x = _branch(x, jnp.einsum(
            "bsd,de->bse", ctx, block["o"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        ).astype(cfg.dtype), cfg)
    u = _rmsnorm(x, block["ln2_scale"], eps)
    if _has_experts(spec):
        started = spec.shortcut == "start"  # the branch lands a layer on
        with jax.named_scope("shortcut") if started else contextlib.nullcontext():
            if cfg.router == "all":
                idx, w = _route(u, block, cfg)
            y, counts = _experts(u, idx, w, live, block, cfg)
        if _has_shares(cfg):  # the held experts' counts, then the router's
            counters["shares"].append(counts[cfg.held[1]:])
            counts = counts[:cfg.held[1]]
        counters["experts"].append(counts)
        if not started:
            return _branch(x, y, cfg), branch
        branch = y
    x = _branch(x, _ffn(u, block, cfg), cfg)
    if spec.shortcut == "land":
        with jax.named_scope("shortcut"):
            x, branch = _branch(x, branch, cfg), None
    return x, branch


def _new_counters() -> dict[str, list]:
    """What a program's layers append to as they are traced: an experts
    layer its per-expert counts, a sparse or linear layer its share of each
    of MIXER_COUNTERS."""
    return {
        "experts": [], "shares": [], "latent_rows_read": [],
        **{name: [] for name in MIXER_COUNTERS},
    }


def _mixer_counts(counters: dict[str, list]) -> list:
    """MIXER_COUNTERS of one program, from what its layers appended."""
    return [
        sum(counters[name], jnp.zeros((), jnp.int32)) for name in MIXER_COUNTERS
    ]


# ------------------------------------------------- the linear mixer
#
# o_t = (q_t / sqrt(dh)) S_t with S_t = lambda S_{t-1} + k_t^T v_t, lambda =
# exp(-slope) a head: no softmax and no normaliser, and instead of rows of
# keys a float32 state [dh, dh] a head. A prefill scans its prompt in
# chunks: inside a chunk the decay-masked product (q k^T * D) v with D_ij =
# lambda^(i-j) for j <= i, between chunks the carried state. Prompts are
# left-padded: a pad's key is zeroed before the scan, so it adds nothing,
# and a state of zeros decays to zeros, so the pads before the first real
# token do not count.

_LINEAR_CHUNK = 256  # ops/linear_attention.py's chunk, and the scan's below


def _slopes(cfg: TransformerConfig) -> Array:
    return jnp.asarray(cfg.linear_slopes, jnp.float32)


def linear_scan(q: Array, k: Array, v: Array, slopes: Array,
                chunk: int = _LINEAR_CHUNK):
    """The chunked scan in `jax.numpy`: q, k, v [b, p, heads, dh] (a pad's
    key zeroed) -> (o [b, p, heads, dh] float32, the state after the last
    position [b, heads, dh, dh] float32). Products of the inputs' dtype
    accumulate in float32; the state and what multiplies it stay float32."""
    b, p, h, dh = q.shape
    chunk = min(chunk, p)
    extra = -p % chunk
    if extra:  # zeros in front add nothing and decay nothing
        q, k, v = (jnp.pad(a, ((0, 0), (extra, 0), (0, 0), (0, 0))) for a in (q, k, v))
    n = (p + extra) // chunk
    # [chunks, b, heads, chunk, dh]
    qc, kc, vc = (
        a.reshape(b, n, chunk, h, dh).transpose(1, 0, 3, 2, 4) for a in (q, k, v)
    )
    at = jnp.arange(chunk, dtype=jnp.float32)
    ago = at[:, None] - at[None, :]
    rate = slopes[:, None, None]
    decay = jnp.where(ago >= 0, jnp.exp(-rate * jnp.maximum(ago, 0.0)), 0.0)
    into = jnp.exp(-slopes[:, None] * (at + 1.0))[..., None]  # the old state's share
    left = jnp.exp(-slopes[:, None] * (chunk - 1.0 - at))[..., None]  # a key's, at the end
    whole = jnp.exp(-slopes * chunk)[:, None, None]
    high = jax.lax.Precision.HIGHEST

    def one(state, qkv):
        qi, ki, vi = qkv
        pairs = jnp.einsum(
            "bhid,bhjd->bhij", qi, ki, preferred_element_type=jnp.float32
        ) * decay
        inner = jnp.einsum(
            "bhij,bhjd->bhid", pairs.astype(vi.dtype), vi,
            preferred_element_type=jnp.float32,
        )
        carried = jnp.einsum(
            "bhid,bhde->bhie", qi.astype(jnp.float32) * into, state, precision=high
        )
        state = whole * state + jnp.einsum(
            "bhjd,bhje->bhde", ki.astype(jnp.float32) * left,
            vi.astype(jnp.float32), precision=high,
        )
        return state, inner + carried

    state, out = jax.lax.scan(
        one, jnp.zeros((b, h, dh, dh), jnp.float32), (qc, kc, vc)
    )
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, n * chunk, h, dh)
    return out[:, extra:] / math.sqrt(dh), state


def linear_step(q: Array, k: Array, v: Array, state: Array, slopes: Array,
                live: Array | None = None):
    """One more position of the scan: q, k, v [b, heads, dh], state
    [b, heads, dh, dh] float32 -> (o [b, heads, dh] float32, state). A row
    that is not `live` [b] keeps its state: it decays by 1 and adds 0, so
    that what is written back is one plain update of the leaf."""
    decay = jnp.exp(-slopes)[None, :, None, None]
    added = k.astype(jnp.float32)[..., :, None] * v.astype(jnp.float32)[..., None, :]
    if live is not None:
        on = live[:, None, None, None]
        decay, added = jnp.where(on, decay, 1.0), jnp.where(on, added, 0.0)
    state = decay * state + added
    out = jnp.einsum(
        "bhd,bhde->bhe", q.astype(jnp.float32), state,
        precision=jax.lax.Precision.HIGHEST,
    )
    return out / math.sqrt(q.shape[-1]), state


def _linear_out(out: Array, block: Params, cfg: TransformerConfig) -> Array:
    """[b, s, heads, dh] float32 -> the layer's context [b, s, heads * dh]."""
    b, s, h, dh = out.shape
    if cfg.linear_out_norm:
        out = _rmsnorm(out, block["o_norm"].astype(jnp.float32), cfg.norm_eps)
    return out.astype(cfg.dtype).reshape(b, s, h * dh)


# ------------------------------------------------- the sparse mixer
#
# Which blocks of keys a query attends (`SparseSpec`). Everything here is
# in logical positions, counted from a row's first real token.


def pool_keys(k: Array, sq: SparseSpec) -> Array:
    """The pooled keys of rows k [b, kv heads, s, dh] that start at logical
    position 0: [b, kv heads, s / stride, dh] (s rounded up to whole
    blocks), pooled key i the mean of
    positions stride i .. stride i + kernel - 1 (a window that runs past
    the end takes zeros there: no query can see it yet). Summed in float32,
    kept in k's dtype."""
    b, hk, s, dh = k.shape
    k = jnp.pad(k, ((0, 0), (0, 0), (0, -s % sq.block), (0, 0)))
    n = k.shape[2] // sq.stride
    part = k.astype(jnp.float32).reshape(
        b, hk, n, sq.stride, dh
    ).sum(axis=3)
    whole = -(-sq.kernel // sq.stride)  # strides a window spans
    part = jnp.pad(part, ((0, 0), (0, 0), (0, whole - 1), (0, 0)))
    total = sum(part[:, :, j:j + n] for j in range(whole))
    return (total / sq.kernel).astype(k.dtype)


def select_blocks(q: Array, pooled: Array, t: Array, dense: Array,
                  sq: SparseSpec) -> Array:
    """The blocks each query attends: q [b, nq, kv heads, group, dh], pooled
    [b, kv heads, n_pool, dh], t [b, nq] the queries' logical positions,
    dense [b] or [b, nq] the rows that attend every earlier position ->
    [b, kv heads, nq, n_pool * stride / block] bool, one set for a group.

    A query sees pooled key i when the whole window lies at or before it;
    its relevance is the softmax over the pooled keys it sees, summed over
    the group's heads; a block's score is the largest relevance among the
    pooled keys that overlap it. The first `init_blocks` blocks and the
    `local_blocks` that end in the query's own are always taken, then the
    best others up to `topk`, or every block at or before the query where
    those are fewer. Scores from the inputs' dtype with float32
    accumulation, the softmax in float32."""
    b, nq, hk, g, dh = q.shape
    n_pool = pooled.shape[2]
    m = sq.block // sq.stride  # pooled keys that start in a block
    before = -(-sq.kernel // sq.stride) - 1  # and those that reach in from the last
    nb = n_pool // m
    scores = jnp.einsum(
        "bqkgd,bkid->bkgqi", q, pooled, preferred_element_type=jnp.float32
    ) / math.sqrt(dh)
    ends = sq.stride * jnp.arange(n_pool) + sq.kernel - 1
    seen = (ends[None, None, :] <= t[:, :, None])[:, None, None]  # [b, 1, 1, nq, n_pool]
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    relevance = jnp.sum(jnp.where(seen, probs, 0.0), axis=2)  # [b, kv heads, nq, n_pool]
    by_block = relevance.reshape(b, hk, nq, nb, m)
    score = by_block.max(axis=-1)
    if before:
        reach = by_block[..., m - before:].max(axis=-1)
        score = jnp.maximum(
            score, jnp.pad(reach[..., :-1], ((0, 0), (0, 0), (0, 0), (1, 0)))
        )
    own = (t // sq.block)[:, None, :, None]  # [b, 1, nq, 1]
    blk = jnp.arange(nb)
    visible = blk <= own
    forced = (blk < sq.init_blocks) | (blk > own - sq.local_blocks)
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(visible, score, -jnp.inf)
    # a block's rank among the scores, counted and not sorted (a `top_k` of
    # 384 scores a query was a sort of 80 ms a 24k-token prefill on a v5e);
    # of equal scores, which neighbouring blocks share with the pooled key
    # that reaches from one into the next, the lower block goes first
    mine, theirs = score[..., :, None], score[..., None, :]
    ahead = (theirs > mine) | ((theirs == mine) & (blk[None, :] < blk[:, None]))
    chosen = (jnp.sum(ahead, axis=-1, dtype=jnp.int32) < sq.topk) & visible
    every = jnp.reshape(dense, (b, 1, -1, 1))
    return jnp.where(every, visible, chosen)


def _keys_of_blocks(blocks: Array, at: Array, sq: SparseSpec) -> Array:
    """blocks [b, kv heads, nq, nb] -> whether each query may read the key at
    logical position at [b, s] (negative: no key): [b, kv heads, nq, s]."""
    nb = blocks.shape[-1]
    idx = jnp.clip(at // sq.block, 0, nb - 1)[:, None, None, :]
    idx = jnp.broadcast_to(idx, blocks.shape[:3] + idx.shape[-1:])
    return jnp.take_along_axis(blocks, idx, axis=-1) & (at >= 0)[:, None, None, :]


def _step_rows(
    params: Params, cache: Params, token: Array, pos: Array, pad_len: Array,
    cfg: TransformerConfig,
):
    """One token of every row, each row at its own physical position `pos`
    [b] behind its own left pad `pad_len` [b]: writes the row's key and
    value, attends over [pad_len, pos] (a window layer over its ring, a
    sparse layer over the blocks it chooses; a linear layer moves its state
    on by one position). Returns (logits [b, vocab], cache, what the layers
    counted: `_new_counters`)."""
    b = token.shape[0]
    x = _embed(params, token, cfg)[:, None, :]
    logical = (pos - pad_len)[:, None]
    if cfg.learned_positions:
        x = x + params["pos_embed"].astype(cfg.dtype)[pos - pad_len][:, None, :]
    kernel = step_uses_kernel(cfg)
    if not kernel:
        at = jnp.arange(cfg.max_len)[None, :]
        kmask = ((at <= pos[:, None]) & (at >= pad_len[:, None]))[:, None, None, :]
        if cfg.window is not None:
            # ring row j holds the newest physical position <= pos that is
            # j modulo W: before the pad (or before the sequence) it is no key
            ring = jnp.arange(cfg.window)[None, :]
            held = pos[:, None] - (pos[:, None] - ring) % cfg.window
            wmask = (held >= pad_len[:, None])[:, None, None, :]
        rows, heads = jnp.arange(b)[:, None], jnp.arange(cfg.kv_heads)[None, :]
    live = (pos > 0)[:, None]  # a free slot's vectors are zeros
    counters = _new_counters()
    branch = None  # a shortcut's expert branch, from its start to its landing
    for (names, li), spec, block in zip(
        _cache_rows(cfg), cfg.layer_specs, params["blocks"]
    ):
        def attend(q, k=None, v=None, names=names, li=li, spec=spec, block=block):
            if spec.mixer == "latent":
                return _step_latent(
                    q, block, spec, cache, names, li, pos, pad_len, live, cfg,
                    counters,
                )
            if spec.mixer == "linear":
                return _step_linear(q, k, v, cache, names, li, live, block, cfg)
            if spec.mixer == "sparse":
                return _step_sparse(
                    q, k, v, cache, names, li, logical[:, 0], live, cfg, counters
                )
            kname, vname = names["k"], names["v"]
            kind = "attn_global" if spec.window is None else "attn_window"
            if kernel:
                # imported where it is traced: Pallas loads when a
                # program first needs it
                from pathway_tpu.ops.attention import decode_attention

                with jax.named_scope("attn"), jax.named_scope(kind):
                    ctx, cache[kname], cache[vname] = decode_attention(
                        q[:, 0], k[:, 0], v[:, 0], cache[kname], cache[vname],
                        li, pos, pad_len,
                    )
                return ctx[:, None]
            at_row = (pos if spec.window is None else pos % cfg.window)[:, None]
            with jax.named_scope("cache_write"):
                # a head's row at a time, which is what lies together
                cache[kname] = cache[kname].at[li, rows, heads, at_row].set(k[:, 0])
                cache[vname] = cache[vname].at[li, rows, heads, at_row].set(v[:, 0])
            with jax.named_scope("attn"), jax.named_scope(kind):
                return _attend(
                    q, cache[kname][li], cache[vname][li],
                    kmask if spec.window is None else wmask, cfg,
                )

        x, branch = _layer(
            x, block, spec, cfg, logical, live, attend, counters, branch=branch
        )
    hline = _rmsnorm(x, params["ln_f_scale"], cfg.norm_eps)
    return _lm_logits(hline, params, cfg)[:, 0, :], cache, counters


def _step_linear(q, k, v, cache, names, li, live, block, cfg):
    """A linear layer's step: the state of every occupied row moves on by
    one position, in its leaf; a free row's stays as it is."""
    with jax.named_scope("attn"), jax.named_scope("attn_linear"):
        leaf = names["state"]
        out, new = linear_step(
            q[:, 0], k[:, 0], v[:, 0], cache[leaf][li], _slopes(cfg), live[:, 0]
        )
        with jax.named_scope("state_write"):
            cache[leaf] = jax.lax.dynamic_update_slice(
                cache[leaf], new[None], (li, 0, 0, 0, 0)
            )
        return _linear_out(out[:, None], block, cfg)


def _step_sparse(q, k, v, cache, names, li, t, live, cfg, counters):
    """A sparse layer's step, every row at its logical position t [b]: the
    key and value go into row t of the slot, the pooled key whose window
    the row completes (or has completed, up to stride - 1 steps ago: the
    same rows, the same mean) is written again, and the query attends the
    blocks it chooses among those at or before t."""
    sq = cfg.sparse
    b, hk = q.shape[0], cfg.kv_heads
    rows, heads = jnp.arange(b)[:, None], jnp.arange(hk)[None, :]
    kname, vname, pname = names["k"], names["v"], names["pool"]
    with jax.named_scope("attn"), jax.named_scope("attn_sparse"):
        with jax.named_scope("pool"):
            # the newest pooled key whose window ends at or before t; before
            # the first has ended this writes one nobody sees yet. The
            # window's rows from the leaf, the step's own key among them
            newest = jnp.maximum(t - (sq.kernel - 1), 0) // sq.stride
            first = newest * sq.stride
            # (a slice a slot out of the stacked leaf itself: batched, the
            # slices become a gather for which the compiler relays the leaf)
            window = jnp.concatenate([
                jax.lax.dynamic_slice(
                    cache[kname], (li, slot, 0, first[slot], 0),
                    (1, 1, hk, sq.kernel, cfg.head_dim),
                )[0] for slot in range(b)
            ])  # [b, kv heads, kernel, dh]
            own = (first[:, None] + jnp.arange(sq.kernel) == t[:, None])
            window = jnp.where(own[:, None, :, None], k[:, 0][:, :, None, :], window)
            mean = jnp.mean(window.astype(jnp.float32), axis=2).astype(cfg.dtype)
            cache[pname] = cache[pname].at[li, rows, heads, newest[:, None]].set(mean)
        with jax.named_scope("select"):
            blocks = select_blocks(
                q.reshape(b, 1, hk, -1, cfg.head_dim), cache[pname][li],
                t[:, None], t < sq.dense_len, sq,
            )
            seen = live[:, :, None, None]
            counters["sparse_blocks_read"].append(jnp.sum(blocks & seen, dtype=jnp.int32))
            counters["sparse_blocks_visible"].append(
                hk * jnp.sum(jnp.where(live[:, 0], t // sq.block + 1, 0), dtype=jnp.int32)
            )
        if sparse_step_uses_kernel(cfg):
            # imported where it is traced: Pallas loads when a program
            # first needs it
            from pathway_tpu.ops.sparse_attention import (
                sparse_decode_attention, sparse_decode_tile,
            )

            ctx, cache[kname], cache[vname] = sparse_decode_attention(
                q[:, 0], k[:, 0], v[:, 0], cache[kname], cache[vname], li, t,
                blocks[:, :, 0], block=sq.block,
                tile=sparse_decode_tile(sq.block, sq.topk, sq.dense_len),
                steps=sq.topk,
            )
            return ctx[:, None]
    with jax.named_scope("cache_write"):
        cache[kname] = cache[kname].at[li, rows, heads, t[:, None]].set(k[:, 0])
        cache[vname] = cache[vname].at[li, rows, heads, t[:, None]].set(v[:, 0])
    with jax.named_scope("attn"), jax.named_scope("attn_sparse"):
        at = jnp.broadcast_to(jnp.arange(cfg.max_len)[None, :], (b, cfg.max_len))
        ok = _keys_of_blocks(blocks, at, sq) & (at <= t[:, None])[:, None, None, :]
        return _attend(q, cache[kname][li], cache[vname][li], ok, cfg)


# ------------------------------------------------- the latent mixer
#
# Multi-head latent attention (`LatentSpec`). Of normed rows h: the query's
# low-rank row c_q = rms(h W_qa) x q_scale and each head's [nope | rope]
# lanes c_q W_qb; [c | k_r] = h W_kva, c_kv = rms(c) x kv_scale, and each
# head's [key nope lanes | value] = c_kv W_kvb; rotary on the rope lanes of
# q and on k_r, which all heads share. A position keeps c_kv and the rotated
# k_r and nothing else.
#
# A prefill expands its own rows' keys and values from c_kv and attends
# them as heads of nope + rope lanes against values of v_dim. A step never
# expands a cached row: with W_kvb,i = [W_uk,i | W_uv,i] a head's score
# against row j is (q_n,i W_uk,i^T) . c_kv,j + q_r,i . k_r,j, and its
# output (sum_j p_ij c_kv,j) W_uv,i: the same function, read from the
# latent rows as they lie.

# a prefill's scores [heads, queries, keys] are float32: 26.8 GB at 10,240
# tokens and 64 heads. Where no kernel keeps them in VMEM the queries go
# through in chunks whose scores stay under this
_LATENT_SCORE_BYTES = 256 << 20


def _latent_rows(xin: Array, block: Params, pos: Array, spec: LayerSpec,
                 cfg: TransformerConfig, *, for_kernel: bool = False):
    """Of normed rows xin [b, s, d] at logical positions pos [b, s]: the
    heads' queries (q_n [b, s, heads, nope], q_r [b, s, heads, rope], turned)
    and what a position keeps (c_kv [b, s, kv_rank] normed and scaled, k_r
    [b, s, rope] turned). `for_kernel` (a prefill that runs
    ops/latent_attention.py `latent_prefill_attention`): q_n and q_r are a
    product each, of W_qb's nope and rope columns, so that each leaves the
    MXU as whole heads side by side and nothing slices, pads or copies an
    array as large as the prompt: q_r [b, s, heads, rope lanes] then has
    zeros behind each head's rotary lanes and is NOT turned, which is the
    kernel's to do. A step's one row slices its product, which is small,
    and leaves the weight as it lies. Every element is the same dot product
    either way."""
    lt, eps = cfg.latent, cfg.norm_eps
    b, s, _ = xin.shape

    def product(x: Array, w: Array) -> Array:
        return jnp.einsum(
            "bsd,de->bse", x, w.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        ).astype(cfg.dtype)

    def gain(name: str, by: float) -> Array:
        # the norm's scale and the rank's factor meet the row in one float32 pass
        return block[name].astype(jnp.float32) * by

    with jax.named_scope("q_down"):
        c_q = _rmsnorm(product(xin, block["q_a"]), gain("q_a_norm", lt.q_scale), eps)
    with jax.named_scope("q_up"):
        if for_kernel:
            w = block["q_b"].reshape(lt.q_rank, cfg.n_heads, lt.qk_dim)
            # the two slices are made before the products: folded into them
            # (XLA's TPU compiler does, left alone) a product leaves with
            # the heads outermost and is copied, 168 MB, into rows. The
            # rotary columns go in with zero columns behind each head's, up
            # to a lane tile: the product then leaves the rotary lanes as
            # the kernel reads them, and nothing pads them afterwards
            w_n, w_r = jax.lax.optimization_barrier((
                w[..., :lt.nope_dim].reshape(lt.q_rank, -1),
                _in_rope_lanes(w[..., lt.nope_dim:], cfg).reshape(lt.q_rank, -1),
            ))
            q_n = product(c_q, w_n).reshape(b, s, cfg.n_heads, lt.nope_dim)
            q_r = product(c_q, w_r).reshape(b, s, cfg.n_heads, _rope_lanes(cfg))
        else:
            q = product(c_q, block["q_b"]).reshape(b, s, cfg.n_heads, lt.qk_dim)
            q_n, q_r = q[..., :lt.nope_dim], q[..., lt.nope_dim:]
    with jax.named_scope("kv_down"):
        kv = product(xin, block["kv_a"])
        c_kv = _rmsnorm(
            kv[..., :lt.kv_rank], gain("kv_a_norm", lt.kv_scale), eps
        )
        k_r = kv[..., lt.kv_rank:]
    if spec.pos == "rotary":
        if not for_kernel:
            q_r = _rope(q_r, pos, cfg)
        k_r = _rope(k_r[:, :, None, :], pos, cfg)[:, :, 0, :]
    return q_n, q_r, c_kv, k_r


def _rope_lanes(cfg: TransformerConfig) -> int:
    """The width of the `k_rope` leaf: the rotary key's lanes rounded up to
    a lane tile (64 -> 128). A tiled row of 64 lanes takes a tile's room in
    the chip's memory anyway, and a leaf left 64 wide is laid out rows-minor
    by the TPU's compiler: every row-major use of it (the step's kernel, a
    row's write) then copies the whole leaf there and back, twice its size
    a step (read in the compiled step, PR 43)."""
    return -(-cfg.latent.rope_dim // 128) * 128


def _in_rope_lanes(x: Array, cfg: TransformerConfig) -> Array:
    """x [..., rope_dim] with zeros behind it up to the leaf's width."""
    extra = _rope_lanes(cfg) - x.shape[-1]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, extra),))


def _kv_up(block: Params, cfg: TransformerConfig) -> Array:
    """W_kvb as [kv_rank, heads, nope + v]: a head's W_uk beside its W_uv."""
    lt = cfg.latent
    return block["kv_b"].astype(cfg.dtype).reshape(
        lt.kv_rank, cfg.n_heads, lt.nope_dim + lt.v_dim
    )


def _attend_latent(q: Array, k: Array, v: Array, ok: Array,
                   cfg: TransformerConfig) -> Array:
    """softmax(q k^T / sqrt(qk_dim)) v over the keys `ok` [b, 1, nq, s]
    allows: q [b, nq, heads, qk_dim], k [b, s, heads, qk_dim], v [b, s,
    heads, v_dim] -> [b, nq, heads * v_dim]."""
    b, nq, h, _ = q.shape
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(cfg.latent.qk_dim)
    probs = jax.nn.softmax(jnp.where(ok, scores, -1e30), axis=-1).astype(cfg.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32)
    return ctx.astype(cfg.dtype).reshape(b, nq, h * v.shape[-1])


def _prefill_latent(xin, block, spec, cache, names, li, valid, pos_idx, cfg):
    """A latent layer over whole prompts [b, p]: every row's c_kv and k_r
    into the layer's leaves at its physical position, and the prompt's own
    keys and values expanded from c_kv for its attention."""
    lt = cfg.latent
    b, p, _ = xin.shape
    h = cfg.n_heads
    with jax.named_scope("attn"), jax.named_scope("attn_latent"):
        kernel = latent_prefill_uses_kernel(cfg, p)
        q_n, q_r, c_kv, k_r = _latent_rows(
            xin, block, pos_idx, spec, cfg, for_kernel=kernel
        )
        kept = _in_rope_lanes(k_r, cfg)  # the rotary key as the leaf holds it
        with jax.named_scope("cache_write"):
            cache[names["c"]] = jax.lax.dynamic_update_slice(
                cache[names["c"]], c_kv[None], (li, 0, 0, 0)
            )
            cache[names["rope"]] = jax.lax.dynamic_update_slice(
                cache[names["rope"]], kept[None], (li, 0, 0, 0)
            )
        if kernel:
            # imported where they are traced: Pallas loads when a program
            # first needs it
            from pathway_tpu.ops.latent_attention import latent_prefill_attention
            from pathway_tpu.ops.rowwise import rope_tables

            with jax.named_scope("kv_up"):
                # a product each for the nope keys and the values, of
                # W_kvb's columns, with the heads outermost and the
                # positions along the lanes: the TPU's compiler computes
                # these products (512 deep) that way whatever is asked, and
                # copies them (168 MB each) if rows of heads were
                w = _kv_up(block, cfg)
                k_n, v = (
                    jnp.einsum(
                        "bsr,rhe->bhes", c_kv, part,
                        preferred_element_type=jnp.float32,
                    ).astype(cfg.dtype)
                    for part in (w[..., :lt.nope_dim], w[..., lt.nope_dim:])
                )
            with jax.named_scope("rope"):
                if spec.pos == "rotary":
                    cos, sin = rope_tables(pos_idx, cfg.rope_theta, lt.rope_dim)
                else:  # a turn by no angle
                    cos = jnp.ones((b, p, lt.rope_dim), jnp.float32)
                    sin = jnp.zeros_like(cos)
            # the one rotary key as the leaf's row: no head's copy of it
            return latent_prefill_attention(
                q_n, q_r, k_n, kept, v, valid, _in_rope_lanes(cos, cfg),
                _in_rope_lanes(sin, cfg), scale=1.0 / math.sqrt(lt.qk_dim),
                half=lt.rope_dim // 2,
            )
        with jax.named_scope("kv_up"):
            kv = jnp.einsum(
                "bsr,rhe->bshe", c_kv, _kv_up(block, cfg),
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
            k_n, v = kv[..., :lt.nope_dim], kv[..., lt.nope_dim:]
        shared = jnp.broadcast_to(k_r[:, :, None, :], (b, p, h, lt.rope_dim))
        q = jnp.concatenate([q_n, q_r], axis=-1)
        k = jnp.concatenate([k_n, shared], axis=-1)
        chunk = p
        while chunk > 128 and chunk % 2 == 0 and (
            4 * b * h * chunk * p > _LATENT_SCORE_BYTES
        ):
            chunk //= 2
        at = jnp.arange(p)
        real = valid.astype(bool)[:, None, None, :]

        def some(qa):  # a chunk of queries [b, chunk, heads, qk_dim], from row a
            qc, a = qa
            ok = real & (at[None, :] <= (a + at[:chunk])[:, None])[None, None]
            return _attend_latent(qc, k, v, ok, cfg)

        ctx = jax.lax.map(some, (
            q.reshape(b, p // chunk, chunk, h, lt.qk_dim).transpose(1, 0, 2, 3, 4),
            jnp.arange(0, p, chunk),
        ))  # [chunks, b, chunk, heads * v_dim]
        return ctx.transpose(1, 0, 2, 3).reshape(b, p, h * lt.v_dim)


def _step_latent(xin, block, spec, cache, names, li, pos, pad_len, live, cfg,
                 counters):
    """A latent layer's step, every row at its physical position pos [b]
    behind its pad: its c_kv and k_r go into row pos of its slot, and its
    heads attend the slot's latent rows pad_len .. pos in the absorbed
    form, no row of the cache expanded."""
    lt = cfg.latent
    b = xin.shape[0]
    cname, rname = names["c"], names["rope"]
    with jax.named_scope("attn"), jax.named_scope("attn_latent"):
        q_n, q_r, c_kv, k_r = _latent_rows(
            xin, block, (pos - pad_len)[:, None], spec, cfg
        )
        # the rotary lanes as the leaf holds them: zeros behind both
        q_r, k_r = _in_rope_lanes(q_r[:, 0], cfg), _in_rope_lanes(k_r, cfg)
        with jax.named_scope("cache_write"):
            # a row a slot into the stacked leaves themselves, where they
            # lie (a scatter of all slots' rows is the compiler's to place)
            for slot in range(b):
                at = (li, slot, pos[slot], 0)
                cache[cname] = jax.lax.dynamic_update_slice(
                    cache[cname], c_kv[slot][None, None], at
                )
                cache[rname] = jax.lax.dynamic_update_slice(
                    cache[rname], k_r[slot][None, None], at
                )
        w_kv = _kv_up(block, cfg)
        with jax.named_scope("absorb"):
            q_c = jnp.einsum(
                "bhn,rhn->bhr", q_n[:, 0], w_kv[..., :lt.nope_dim],
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
        counters["latent_rows_read"].append(
            jnp.sum(jnp.where(live[:, 0], pos - pad_len + 1, 0), dtype=jnp.int32)
        )
        if latent_step_uses_kernel(cfg):
            from pathway_tpu.ops.latent_attention import latent_decode_attention

            z = latent_decode_attention(
                q_c, q_r, cache[cname], cache[rname], li, pos, pad_len,
                scale=1.0 / math.sqrt(lt.qk_dim),
            )
        else:
            rows_c, rows_r = cache[cname][li], cache[rname][li]
            scores = (
                jnp.einsum("bhr,bjr->bhj", q_c, rows_c,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bhe,bje->bhj", q_r, rows_r,
                             preferred_element_type=jnp.float32)
            ) / math.sqrt(lt.qk_dim)
            at = jnp.arange(cfg.max_len)[None, :]
            ok = ((at <= pos[:, None]) & (at >= pad_len[:, None]))[:, None, :]
            probs = jax.nn.softmax(
                jnp.where(ok, scores, -1e30), axis=-1
            ).astype(cfg.dtype)
            z = jnp.einsum(
                "bhj,bjr->bhr", probs, rows_c, preferred_element_type=jnp.float32
            ).astype(cfg.dtype)
        with jax.named_scope("absorb"):
            ctx = jnp.einsum(
                "bhr,rhv->bhv", z, w_kv[..., lt.nope_dim:],
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
        return ctx.reshape(b, 1, cfg.n_heads * lt.v_dim)


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
    pad = jnp.zeros((b,), jnp.int32) if pad_len is None else pad_len
    lg, cache, _ = _step_rows(
        params, cache, token, jnp.full((b,), pos, jnp.int32), pad, cfg
    )
    return lg, cache


def step_uses_kernel(cfg: TransformerConfig) -> bool:
    """Whether a step's attention, one query a slot, runs ops/attention.py
    `decode_attention` (the stacked cache leaf read in place, only the
    tiles that hold a live row fetched) and not the plain `_attend` over
    every row the cache has room for: on a TPU, with heads of a multiple
    of 128 lanes (a head's rows are the leaf's (rows, head) tiles, and a
    lane tile is 128 wide: heads of 64 or 96 would be half-empty tiles).
    The width asked is `cfg.head_dim`, that of a softmax or sparse layer's
    heads, whose q, k and v are one width; a latent layer's rows have no
    head axis and two widths, and `latent_step_uses_kernel` asks for them.
    Read from the shapes and from where the process runs,
    as `prefill_uses_kernel`; nothing sets it, and `fused_attention` off
    keeps tensor-parallel parameters and a slot axis sharded over a mesh
    (the kernel has no partitioning rule) on `_attend`."""
    return (
        cfg.fused_attention
        and jax.default_backend() == "tpu"
        and cfg.head_dim % 128 == 0
    )


def prefill_uses_kernel(cfg: TransformerConfig, width: int) -> bool:
    """Whether a prefill of prompts `width` wide runs ops/attention.py
    `prefill_attention` (scores kept in VMEM) and not the plain `_attend`:
    on a TPU, with heads of a multiple of 128 lanes and a width of 128 at
    least (every rung of `BucketPolicy.seq_bucket` from there up; the
    kernel pads the cap's rung inside). The heads are `cfg.head_dim` wide,
    q, k and v alike; a latent layer's are `qk_dim` against `v_dim`, and
    `latent_prefill_uses_kernel` is their rule. Read from the shapes and from
    where the process runs; nothing sets it. Like the encoder's kernel it
    has no partitioning rule: `fused_attention` off (`TransformerLM.shard`)
    keeps tensor-parallel parameters on `_attend`."""
    return (
        cfg.fused_attention
        and jax.default_backend() == "tpu"
        and cfg.head_dim % 128 == 0
        and width >= 128
    )


def linear_prefill_uses_kernel(cfg: TransformerConfig, width: int) -> bool:
    """Whether the linear layers of a prefill `width` wide run
    ops/linear_attention.py `linear_prefill_attention` (a chunk's pairs and
    the state kept in VMEM) and not the `linear_scan` above: where
    `prefill_uses_kernel` would hold of such a width, for a decoder that has
    such layers. Read from the shapes and from where the process runs."""
    return bool(cfg.n_mixer_layers("linear")) and prefill_uses_kernel(cfg, width)


def rowwise_uses_kernel(cfg: TransformerConfig, width: int) -> bool:
    """Whether a prefill `width` wide has layers whose q and k take
    ops/rowwise.py `rowwise_heads` (norm, rotary positions and a pad's zero
    in one pass over the qkv product, the arithmetic of `_rmsnorm` and
    `_rope` as a TPU runs it) and not those functions one after the other: where
    `prefill_uses_kernel` would hold of such a width, for a decoder with q/k
    norms or a rotary layer. Read from the shapes and from where the process
    runs; nothing sets it. A step (one row a slot) never does."""
    return (
        cfg.qk_norm or any(sp.pos == "rotary" for sp in cfg.layer_specs)
    ) and prefill_uses_kernel(cfg, width) and (
        # the pass has `_rmsnorm`'s default epsilon written in
        not cfg.qk_norm or cfg.norm_eps == 1e-6
    )


def latent_prefill_uses_kernel(cfg: TransformerConfig, width: int) -> bool:
    """Whether the latent layers of a prefill `width` wide run
    ops/latent_attention.py `latent_prefill_attention` (the tile body of
    `prefill_attention` over a block of heads a grid step, scores kept in
    VMEM) and not `_attend_latent` over chunks of queries: on a TPU, for a
    decoder that has such layers, at a width of 128 at least, with nope
    lanes and values of a multiple of 128 lanes (a head's lanes are then
    whole lane tiles of the products that make them). The rotary lanes need
    not be one: the kernel reads them in the lane tile the `k_rope` leaf
    keeps them in (`_rope_lanes`), as a product of their own. Read from the
    shapes and from where the process runs; nothing sets it, and
    `fused_attention` off keeps tensor-parallel parameters on the plain
    path."""
    return (
        bool(cfg.n_mixer_layers("latent"))
        and cfg.fused_attention
        and jax.default_backend() == "tpu"
        and cfg.latent.nope_dim % 128 == 0
        and cfg.latent.v_dim % 128 == 0
        and width >= 128
    )


def latent_step_uses_kernel(cfg: TransformerConfig) -> bool:
    """Whether a step's latent layers run ops/latent_attention.py
    `latent_decode_attention`, which fetches only the tiles of `c_kv` and
    `k_rope` that hold a live row of the slot, and not products over every
    row the cache has room for: on a TPU, for a decoder with such layers,
    with a latent row of a multiple of 128 lanes and rows that are whole
    tiles of `latent_decode_tile` (`k_rope` is a lane tile wide:
    `_rope_lanes`). Read from the shapes and from where the process runs;
    nothing sets it."""
    if not (
        cfg.n_mixer_layers("latent") and cfg.fused_attention
        and jax.default_backend() == "tpu" and cfg.latent.kv_rank % 128 == 0
    ):
        return False
    from pathway_tpu.ops.latent_attention import latent_decode_tile

    return cfg.max_len % latent_decode_tile(cfg.max_len) == 0


def _takes_rowwise(cfg: TransformerConfig, spec: LayerSpec) -> bool:
    """Whether a layer of such a prefill is one of them: it has a norm or a
    rotation to make."""
    return cfg.qk_norm or spec.pos == "rotary"


def sparse_prefill_uses_kernel(cfg: TransformerConfig, width: int) -> bool:
    """Whether the sparse layers of a prefill `width` wide run
    ops/sparse_attention.py's two kernels, `sparse_select` for the blocks
    each query chooses (the set `select_blocks` gives, its scores kept in
    VMEM) and `sparse_prefill_attention` over those blocks: where
    `prefill_uses_kernel` holds, at a width past `dense_len` (up to it they
    run `prefill_attention` as every softmax layer does and choose
    nothing), for a decoder that has such layers. Elsewhere, and in every
    step, `select_blocks` chooses."""
    return (
        bool(cfg.n_mixer_layers("sparse"))
        and width > cfg.sparse.dense_len
        and prefill_uses_kernel(cfg, width)
    )


def sparse_step_uses_kernel(cfg: TransformerConfig) -> bool:
    """Whether a step's sparse layers run ops/sparse_attention.py
    `sparse_decode_attention`, which fetches only the tiles that hold a
    block the query chose and writes the step's row on its way, and not the
    plain `_attend` over all of the slot's rows under the selection's mask:
    where `step_uses_kernel` holds, for a decoder with such layers whose
    rows are whole tiles of `sparse_decode_tile` (a tile's rows a multiple
    of a packed sublane tile). Read from the shapes and from where the
    process runs; nothing sets it."""
    if not cfg.n_mixer_layers("sparse") or not step_uses_kernel(cfg):
        return False
    from pathway_tpu.ops.sparse_attention import sparse_decode_tile

    sq = cfg.sparse
    tile = sparse_decode_tile(sq.block, sq.topk, sq.dense_len)
    return cfg.max_len % tile == 0 and tile % 16 == 0


# a visit of ops/experts.py's kernels fetches an expert's matrices and runs
# whole blocks of 128 rows: with fewer pairs an expert than that most of a
# block is masked. Measured at a prefill's 960 an expert (twice as fast as
# `ragged_dot`); a decode step's 6 a slot stay on `ragged_dot`, which reads
# each touched expert once (PERF.md section 6, PR 36)
_EXPERT_KERNEL_PAIRS = 128
# the combine kernel's row indices, one int32 a pair, ride in the chip's
# scalar memory (1 MiB on a v5e; the compiler refuses 65,536 x 6)
_EXPERT_KERNEL_MAX_PAIRS = 196_608
# a visit holds one expert's gate and up matrices whole, double-buffered,
# in the chip's fast memory (128 MiB on a v5e, of which the kernels ask
# 100): 15.7 MB at widths of 2,560 x 768, 100.7 MB at 6,144 x 2,048, which
# the compiler refuses. Until the kernels tile an expert's width, experts
# that large stay on `ragged_dot`
_EXPERT_KERNEL_MATRIX_BYTES = 64 << 20


def experts_use_kernel(cfg: TransformerConfig, pairs: int) -> bool:
    """Whether a grouped product over `pairs` token-expert pairs of the
    experts held here runs ops/experts.py `grouped_experts` (and, where
    every expert is held, `combine_experts`) and not three `ragged_dot` and
    a weighted sum: on a TPU, with model and expert widths of a multiple of
    128 lanes, `_EXPERT_KERNEL_PAIRS` pairs an expert HELD at least
    (`cfg.held`: all of them, or this chip's share), which a prefill has
    and a decode step has not, no more than `_EXPERT_KERNEL_MAX_PAIRS`
    in all, and an expert's gate and up matrices that fit the chip's fast
    memory twice over (`_EXPERT_KERNEL_MATRIX_BYTES`). Read from the shapes and from where the process runs, as
    `prefill_uses_kernel`; nothing sets it, and `fused_attention` off keeps
    tensor-parallel parameters and a pool that spans a mesh on `ragged_dot`
    (the kernels have no partitioning rule)."""
    return (
        cfg.fused_attention
        and jax.default_backend() == "tpu"
        and cfg.d_model % 128 == 0
        and (cfg.d_expert or cfg.d_ff) % 128 == 0
        and _EXPERT_KERNEL_PAIRS * cfg.held[1] <= pairs <= _EXPERT_KERNEL_MAX_PAIRS
        and 4 * cfg.d_model * (cfg.d_expert or cfg.d_ff)
        * jnp.dtype(cfg.dtype).itemsize <= _EXPERT_KERNEL_MATRIX_BYTES
    )


def prefill_experts_use_kernel(cfg: TransformerConfig, width: int) -> bool:
    """Whether the experts layers of a prefill of prompts `width` wide, one
    row, run the kernel: `experts_use_kernel` of the pairs one grouped
    product sees, for a decoder that has such layers. Where every expert is
    held that is all the prefill's pairs; where a share is held
    (`_experts_held`) a pass of `_HELD_CHUNK` pairs of the held experts."""
    pairs = width * cfg.n_active
    if _has_shares(cfg):
        pairs = min(_HELD_CHUNK, pairs)
    return bool(cfg.n_expert_layers) and experts_use_kernel(cfg, pairs)


def _prefill(
    params: Params, prompt_ids: Array, cache: Params, cfg: TransformerConfig,
    prompt_mask: Array | None,
):
    """`prefill`, and what the layers counted (`_new_counters`) beside its
    results."""
    b, p = prompt_ids.shape
    x = _embed(params, prompt_ids, cfg)
    if prompt_mask is None:
        valid = jnp.ones((b, p), jnp.int32)
        pos_idx = jnp.broadcast_to(jnp.arange(p)[None, :], (b, p))
        if cfg.learned_positions:
            x = x + params["pos_embed"].astype(cfg.dtype)[None, :p, :]
    else:
        valid = prompt_mask
        pos_idx = jnp.clip(jnp.cumsum(prompt_mask, axis=1) - 1, 0, None)
        if cfg.learned_positions:
            x = x + params["pos_embed"].astype(cfg.dtype)[pos_idx]
    window = cfg.window
    banded = window is not None and window < p  # else a window layer sees all
    kernel = prefill_uses_kernel(cfg, p)
    if not kernel:
        mask = wmask = _build_mask(valid, causal=True)
        if banded:
            at = jnp.arange(p)
            wmask = mask & (at[None, :] > at[:, None] - window)[None, None]
    live = valid.astype(bool)
    fused, rope = rowwise_uses_kernel(cfg, p), None
    if fused and any(sp.pos == "rotary" for sp in cfg.layer_specs):
        from pathway_tpu.ops.rowwise import rope_tables

        with jax.named_scope("rope"):  # once, for every rotary layer
            rope = rope_tables(pos_idx, cfg.rope_theta, cfg.head_dim)
    counters = _new_counters()
    branch = None  # a shortcut's expert branch, from its start to its landing
    for (names, li), spec, block in zip(
        _cache_rows(cfg), cfg.layer_specs, params["blocks"]
    ):
        def attend(q, k=None, v=None, names=names, li=li, spec=spec, block=block):
            if spec.mixer == "latent":
                return _prefill_latent(
                    q, block, spec, cache, names, li, valid, pos_idx, cfg
                )
            if spec.mixer == "linear":
                return _prefill_linear(
                    q, k, v, cache, names, li, live, block, cfg, counters,
                    zeroed=fused and _takes_rowwise(cfg, spec),
                )
            if spec.mixer == "sparse":
                return _prefill_sparse(
                    q, k, v, cache, names, li, valid, pos_idx, cfg, counters,
                    None if kernel else mask,
                )
            kname, vname = names["k"], names["v"]
            with jax.named_scope("cache_write"):
                # head-major, as the cache lies
                kept_k = kt = k.transpose(0, 2, 1, 3)
                kept_v = vt = v.transpose(0, 2, 1, 3)
                if spec.window is not None and p > window:
                    # a prompt longer than the window leaves its last W
                    # keys, each in the ring's row of its physical position
                    turn = (p - window) % window
                    kept_k = jnp.roll(kt[:, :, p - window:], turn, axis=2)
                    kept_v = jnp.roll(vt[:, :, p - window:], turn, axis=2)
                cache[kname] = jax.lax.dynamic_update_slice(
                    cache[kname], kept_k[None], (li, 0, 0, 0, 0)
                )
                cache[vname] = jax.lax.dynamic_update_slice(
                    cache[vname], kept_v[None], (li, 0, 0, 0, 0)
                )
            kind = "attn_global" if spec.window is None else "attn_window"
            with jax.named_scope("attn"), jax.named_scope(kind):
                if kernel:
                    # imported where it is traced, as `_attention` does:
                    # Pallas loads when a program first needs it
                    from pathway_tpu.ops.attention import prefill_attention

                    return prefill_attention(
                        q, k, v, valid,
                        window if banded and spec.window is not None else None,
                    )
                return _attend(
                    q, kt, vt, mask if spec.window is None else wmask, cfg
                )

        x, branch = _layer(
            x, block, spec, cfg, pos_idx, live, attend, counters, fused, rope,
            branch,
        )
    hlast = _rmsnorm(x[:, -1:, :], params["ln_f_scale"], cfg.norm_eps)
    return _lm_logits(hlast, params, cfg)[:, 0, :], cache, counters


def _prefill_linear(q, k, v, cache, names, li, live, block, cfg, counters,
                    zeroed=False):
    """A linear layer over whole prompts: the chunked scan, and the state
    it leaves after the last token into the layer's leaf. `zeroed`: the
    pads' keys are zeros already (`_qkv_rowwise`)."""
    b, p, h, dh = q.shape
    normed = False
    with jax.named_scope("attn"), jax.named_scope("attn_linear"):
        if not zeroed:  # a pad adds nothing
            k = jnp.where(live[:, :, None, None], k, jnp.zeros_like(k))
        with jax.named_scope("scan"):
            if linear_prefill_uses_kernel(cfg, p):
                # imported where it is traced: Pallas loads when a program
                # first needs it
                from pathway_tpu.ops.linear_attention import linear_prefill_attention

                # the output norm in the kernel's epilogue: `_linear_out`
                # before its cast, with nothing of the norm crossing HBM
                normed = cfg.linear_out_norm
                out, state = linear_prefill_attention(
                    q, k, v, _slopes(cfg), _LINEAR_CHUNK,
                    block["o_norm"] if normed else None,
                )
            else:
                out, state = linear_scan(q, k, v, _slopes(cfg))
        with jax.named_scope("state_write"):
            cache[names["state"]] = jax.lax.dynamic_update_slice(
                cache[names["state"]], state[None], (li, 0, 0, 0, 0)
            )
        counters["linear_tokens"].append(jnp.sum(live, dtype=jnp.int32))
        if normed:
            return out.astype(cfg.dtype).reshape(b, p, h * dh)
        return _linear_out(out, block, cfg)


# a prefill's selection scores [heads, queries, pooled keys] are float32:
# at 24,576 tokens 4.8 GB for the whole prompt. The queries go through in
# chunks whose scores stay under this; ops/sparse_attention.py's kernel,
# which keeps them in VMEM, takes the same chunks, so that the selection is
# the prefill's one loop either way
_SELECT_SCORE_BYTES = 256 << 20


def _prefill_sparse(q, k, v, cache, names, li, valid, pos_idx, cfg, counters,
                    mask):
    """A sparse layer over whole prompts [b, p]: keys, values and pooled
    keys into the layer's leaves at their logical positions, and each query
    over the blocks it chooses (every earlier key where the prompt is no
    longer than `dense_len`, which a width under it settles when traced).
    `mask` is the causal mask of the plain path, None where the kernels
    run."""
    sq = cfg.sparse
    b, p, h, dh = q.shape
    hk = k.shape[2]
    kname, vname, pname = names["k"], names["v"], names["pool"]
    n = jnp.sum(valid, axis=1).astype(jnp.int32)  # real tokens of each row
    with jax.named_scope("cache_write"):
        # head-major, and each row's first real token in row 0: the pad
        # goes behind the prompt, where every step writes over it
        turn = jax.vmap(lambda a, by: jnp.roll(a, by, axis=1))
        kt = turn(k.transpose(0, 2, 1, 3), n - p)
        vt = turn(v.transpose(0, 2, 1, 3), n - p)
        cache[kname] = jax.lax.dynamic_update_slice(
            cache[kname], kt[None], (li, 0, 0, 0, 0)
        )
        cache[vname] = jax.lax.dynamic_update_slice(
            cache[vname], vt[None], (li, 0, 0, 0, 0)
        )
    real = valid.astype(bool)
    own = jnp.where(real, pos_idx // sq.block + 1, 0)  # blocks at or before each query
    with jax.named_scope("attn"), jax.named_scope("attn_sparse"):
        with jax.named_scope("pool"):
            pooled = pool_keys(kt, sq)
            cache[pname] = jax.lax.dynamic_update_slice(
                cache[pname], pooled[None], (li, 0, 0, 0, 0)
            )
        counters["sparse_blocks_visible"].append(hk * jnp.sum(own, dtype=jnp.int32))
        if p <= sq.dense_len:
            counters["sparse_blocks_read"].append(hk * jnp.sum(own, dtype=jnp.int32))
            if mask is None:
                from pathway_tpu.ops.attention import prefill_attention

                return prefill_attention(q, k, v, valid, None)
            return _attend(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), mask, cfg)
        with jax.named_scope("select"):
            select = select_blocks
            if mask is None:
                # the same set from ops/sparse_attention.py's kernel, which
                # keeps the scores in VMEM: imported where it is traced
                from pathway_tpu.ops.sparse_attention import sparse_select

                select = sparse_select
            chunk = p
            while chunk > 128 and chunk % 2 == 0 and (
                4 * b * h * chunk * pooled.shape[2] > _SELECT_SCORE_BYTES
            ):
                chunk //= 2
            qg = q.reshape(b, p // chunk, chunk, hk, h // hk, dh)
            tq = jnp.where(real, pos_idx, -1).reshape(b, p // chunk, chunk)
            blocks = jax.lax.map(
                lambda qt: select(qt[0], pooled, qt[1], n <= sq.dense_len, sq),
                (qg.transpose(1, 0, 2, 3, 4, 5), tq.transpose(1, 0, 2)),
            )  # [chunks, b, kv heads, chunk, blocks]
            blocks = blocks.transpose(1, 2, 0, 3, 4).reshape(b, hk, p, -1)
            counters["sparse_blocks_read"].append(
                jnp.sum(blocks & real[:, None, :, None], dtype=jnp.int32)
            )
        if mask is None:
            from pathway_tpu.ops.sparse_attention import sparse_prefill_attention

            return sparse_prefill_attention(q, k, v, valid, blocks, sq.block)
        at = jnp.where(real, pos_idx, -1)  # a key's logical position
        ok = _keys_of_blocks(blocks, at, sq) & mask
        return _attend(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), ok, cfg)


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
    lg, cache, _ = _prefill(params, prompt_ids, cache, cfg, prompt_mask)
    return lg, cache


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


def _with_counters(tokens: Array, counters: list) -> Array:
    """The tokens a program returns and, behind them, its counters: they
    ride to the host in the one array the loop reads anyway."""
    return jnp.concatenate([tokens, jnp.stack(counters).astype(jnp.int32)])


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
    temperature-0 `generate_serving` path bit for bit per row. A decoder
    with experts layers appends PREFILL_COUNTERS to the token: the
    token-expert pairs of the real tokens summed over its layers, and the
    fullest expert's pairs summed over its layers."""
    lg, mini, counts = _prefill(
        params, prompt_ids, init_kv_cache(cfg, 1), cfg, prompt_mask
    )
    with jax.named_scope("cache_write"):
        for name, row in mini.items():
            # the slot's whole row of every leaf, whatever its axes: nothing
            # of the slot's last request stays
            at = [0] * row.ndim
            at[_SLOT_AXIS] = slot
            cache[name] = jax.lax.dynamic_update_slice(cache[name], row, at)
    with jax.named_scope("logits"):
        first = jnp.argmax(lg, -1).astype(jnp.int32)
    tail = []
    if counts["experts"]:
        tail += [
            sum(c.sum() for c in counts["experts"]),
            sum(c.max() for c in counts["experts"]),
        ]
    if _has_mixers(cfg):
        tail += _mixer_counts(counts)
    if counts["shares"]:
        tail += list(sum(counts["shares"]))
    return (_with_counters(first, tail) if tail else first), cache


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
    argmax decoding, bit-identical per row to the wave-aligned path. A
    decoder with experts layers appends STEP_COUNTERS to the tokens: the
    distinct experts that the occupied rows (``pos`` > 0) hit, summed over
    its layers, and the layers so counted."""
    lg, cache, counts = _step_rows(params, cache, token, pos, pad_len, cfg)
    with jax.named_scope("logits"):
        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
    tail = []
    if counts["experts"]:
        tail += [
            sum((c > 0).sum() for c in counts["experts"]),
            len(counts["experts"]) * jnp.any(pos > 0).astype(jnp.int32),
        ]
    if _has_mixers(cfg):
        tail += _mixer_counts(counts)
    if counts["latent_rows_read"]:
        tail.append(sum(counts["latent_rows_read"]))
    return (_with_counters(nxt, tail) if tail else nxt), cache


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
