"""What the encoder's block and every decoder layer are made of: the RMS
norm, the dense feed-forward, rotary positions, softmax attention over rows
of keys and the causal mask; how a parameter leaf is declared; the one rule
of where a Pallas kernel may run at all; and the seam every kind of decoder
layer (models/mixers/) stands behind: `Kind`, with the `Rows` of a program
that its layers share and the `Counters` they send back.

The parts run under `jax.named_scope` (norm, attn, ff, cache_write,
logits): operation metadata that a profiler trace shows per operation and
that changes nothing in the compiled program.
"""

from __future__ import annotations

import dataclasses
import math
import types
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array
Params = dict[str, Any]


def kernel_may_run(cfg: Any) -> bool:
    """Whether a Pallas kernel may run here at all: on a TPU, with
    `fused_attention` on. Every kernel's rule asks this and then its own
    shapes; nothing sets it. `fused_attention` off keeps tensor-parallel
    parameters and a slot axis sharded over a mesh on the plain paths: a
    kernel has no partitioning rule."""
    return cfg.fused_attention and jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class Leaf:
    """A parameter leaf, declared once for `init_params` and `param_specs`:
    its shape, its PartitionSpec (tensor-parallel over the mesh's `model`
    axis), and how it is drawn: normal(0, 1) from the key `key` picks of
    its block's keys (an index, or a function of them), times `scale`
    (None: one over the root of the leaf's fan-in, its axis before the
    last); where `key` is None, `fill` everywhere. `dtype` where it is not
    the tree's."""

    shape: tuple[int, ...]
    spec: P
    key: int | Callable[[Array], Array] | None = None
    scale: float | None = None
    fill: float = 1.0
    dtype: Any = None

    def draw(self, keys: Array, dtype: Any) -> Array:
        dtype = self.dtype or dtype
        if self.key is None:
            return jnp.full(self.shape, self.fill, dtype)
        key = keys[self.key] if isinstance(self.key, int) else self.key(keys)
        scale = 1.0 / math.sqrt(self.shape[-2]) if self.scale is None else self.scale
        return (jax.random.normal(key, self.shape, jnp.float32) * scale).astype(dtype)


def ffn_leaves(cfg: Any, spec: Any) -> dict[str, Leaf]:
    """A dense feed-forward's leaves (none for an `experts` layer): column-
    parallel into the hidden width, row-parallel out of it."""
    if spec.ff == "experts":
        return {}
    d, f = cfg.d_model, cfg.d_ff
    out = {"ff_out": Leaf((f, d), P("model", None), 3)}
    if spec.ff == "swiglu":
        out["ff_gate"] = Leaf((d, f), P(None, "model"), 2)
        out["ff_up"] = Leaf((d, f), P(None, "model"), 5)
    else:
        out["ff_in"] = Leaf((d, f), P(None, "model"), 2)
    return out


def rmsnorm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    with jax.named_scope("norm"):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def ffn(x: Array, block: Params, cfg: Any) -> Array:
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


def build_mask(token_mask: Array, causal: bool) -> Array:
    # token_mask: [b, s] 1/0 valid; returns [b, 1, q, k] bool
    b, s = token_mask.shape
    attend = token_mask[:, None, None, :].astype(bool)
    if causal:
        tri = jnp.tril(jnp.ones((s, s), bool))
        attend = attend & tri[None, None, :, :]
    return attend


def rope(x: Array, pos: Array, cfg: Any) -> Array:
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


def attend(q: Array, keys: Array, vals: Array, ok: Array, cfg: Any) -> Array:
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


class Rows(types.SimpleNamespace):
    """What the layers of one program share while it is traced. Both
    programs: `cfg`, the slot `cache` the layers write, `pos` [b, s] the
    rows' logical positions, `live` [b, s] the rows that count (not
    padding, not a free slot), `counters` (a name -> what each layer
    appended under it) and `fused` (softmax.py `project`). A prefill's:
    `valid` [b, p] 1/0, `width` p, `mask` the causal mask of the plain
    attention and `rope` (`project`'s). A step's: `at` [b] the physical
    positions and `pad` [b] the left pads. A kind's setup adds its own."""


class Counters(NamedTuple):
    """Device counters a program sends back behind its tokens: their names
    and the function that gives their values, in that order, once its
    layers are traced, from what they appended (`Rows.counters`) and the
    slots' physical positions (a step's `at`; None in a prefill)."""

    names: tuple[str, ...]
    values: Callable[[dict, Array | None], list]


def summed(*names: str) -> Counters:
    """Counters each of which is what the layers appended under its name,
    summed: 0 where none did."""
    return Counters(names, lambda counters, at: [
        sum(counters[name], jnp.zeros((), jnp.int32)) for name in names
    ])


class Kind:
    """One kind of decoder layer. A kind's leaves in the slot cache are
    stacked over the layers of the kind and have the slot second: [layers
    of the kind, slots, ...]; `li` is a layer's index along that axis."""

    name: str
    # what the kind's layers add to each program's counters
    prefill_counters = step_counters = summed()

    def heads(self, cfg: Any) -> tuple[int, int]:
        """A layer's query heads and its key/value heads."""
        return cfg.n_heads, cfg.kv_heads

    def leaves(self, cfg: Any, spec: Any) -> dict[str, Leaf]:
        """A layer's mixer leaves: here the q/k/v product of its heads,
        column-parallel, and W_o, row-parallel, so that XLA places one psum
        a block half."""
        d = cfg.d_model
        h, hk = self.heads(cfg)
        hd = h * cfg.head_dim  # the query heads' width: d in the plain block
        return {
            "qkv": Leaf((d, hd + 2 * hk * cfg.head_dim), P(None, "model"), 0),
            "o": Leaf((hd, d), P("model", None), 1),
        }

    def cache(self, cfg: Any, n: int, batch: int) -> dict[str, jax.ShapeDtypeStruct]:
        """The slot-cache leaves of `n` layers of the kind and `batch` slots."""
        raise NotImplementedError

    # A setup is the class's, and runs once a program for all of its kinds
    # (softmax's two), before the first layer: what its layers share.
    @staticmethod
    def prefill_setup(rows: Rows) -> None:
        pass

    @staticmethod
    def step_setup(rows: Rows) -> None:
        pass

    def prefill(self, xin: Array, block: Params, spec: Any, li: int,
                rows: Rows) -> Array:
        """A layer over whole prompts: normed rows xin [b, p, d] -> the
        mixer's output before W_o, the layer's cache leaves written."""
        raise NotImplementedError

    def step(self, xin: Array, block: Params, spec: Any, li: int,
             rows: Rows) -> Array:
        """A layer over one token a slot: xin [b, 1, d], likewise."""
        raise NotImplementedError
