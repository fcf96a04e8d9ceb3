"""The decoder's and the encoder's configuration: `TransformerConfig`, the
per-layer list of `LayerSpec`, and what a `sparse` or `latent` layer reads
(`SparseSpec`, `LatentSpec`). Every field is validated here, once."""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp


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
        return sum(has_experts(sp) for sp in self.layer_specs)

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


def has_experts(spec: LayerSpec) -> bool:
    """Whether a layer holds a router and experts: as its feed-forward, or
    as the branch a shortcut starts beside it."""
    return spec.ff == "experts" or spec.shortcut == "start"
