"""The `linear` kind: no softmax, a decayed sum of k^T v kept as a state.

o_t = (q_t / sqrt(dh)) S_t with S_t = lambda S_{t-1} + k_t^T v_t, lambda =
exp(-slope) a head: no softmax and no normaliser, and instead of rows of
keys a float32 state [dh, dh] a head. A prefill scans its prompt in
chunks: inside a chunk the decay-masked product (q k^T * D) v with D_ij =
lambda^(i-j) for j <= i, between chunks the carried state. Prompts are
left-padded: a pad's key is zeroed before the scan, so it adds nothing,
and a state of zeros decays to zeros, so the pads before the first real
token do not count.

A linear layer keeps no rows at all: its leaf "state" is a float32 [heads,
dh, dh] sum a slot, which a prefill's chunked scan leaves after the last
token and a step decays and adds to.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pathway_tpu.models.layers import Array, Kind, Leaf, Params, rmsnorm, summed
from pathway_tpu.models.mixers import softmax
from pathway_tpu.models.mixers.softmax import fused, project

_LINEAR_CHUNK = 256  # ops/linear_attention.py's chunk, and the scan's below


def _slopes(cfg: Any) -> Array:
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


def _linear_out(out: Array, block: Params, cfg: Any) -> Array:
    """[b, s, heads, dh] float32 -> the layer's context [b, s, heads * dh]."""
    b, s, h, dh = out.shape
    if cfg.linear_out_norm:
        out = rmsnorm(out, block["o_norm"].astype(jnp.float32), cfg.norm_eps)
    return out.astype(cfg.dtype).reshape(b, s, h * dh)


def linear_prefill_uses_kernel(cfg: Any, width: int) -> bool:
    """Whether the linear layers of a prefill `width` wide run
    ops/linear_attention.py `linear_prefill_attention` (a chunk's pairs and
    the state kept in VMEM) and not `linear_scan`: where softmax.py
    `prefill_uses_kernel` would hold of such a width, for a decoder that
    has such layers."""
    return bool(cfg.n_mixer_layers("linear")) and softmax.prefill_uses_kernel(cfg, width)


class Linear(Kind):
    name = "linear"
    # the real tokens a prefill's linear layers scanned, summed over those
    # layers; a step sends 0 there
    prefill_counters = step_counters = summed("linear_tokens")

    def heads(self, cfg):
        return cfg.lin_heads, cfg.lin_heads

    def leaves(self, cfg, spec):
        out = super().leaves(cfg, spec)
        if cfg.linear_out_norm:
            out["o_norm"] = Leaf((cfg.head_dim,), P(None))
        return out

    def cache(self, cfg, n, batch):
        dh = cfg.head_dim
        return {
            "state": jax.ShapeDtypeStruct((n, batch, cfg.lin_heads, dh, dh), jnp.float32)
        }

    def prefill(self, xin, block, spec, li, rows):
        """The chunked scan, and the state it leaves after the last token
        into the layer's leaf."""
        cfg, cache, live = rows.cfg, rows.cache, rows.live
        q, k, v = project(xin, block, spec, rows, self.heads(cfg), zero_pads=True)
        b, p, h, dh = q.shape
        normed = False
        with jax.named_scope("attn"), jax.named_scope("attn_linear"):
            if not fused(rows, spec):  # a pad adds nothing
                k = jnp.where(live[:, :, None, None], k, jnp.zeros_like(k))
            with jax.named_scope("scan"):
                if linear_prefill_uses_kernel(cfg, p):
                    # imported where it is traced: Pallas loads when a
                    # program first needs it
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
                cache["state"] = jax.lax.dynamic_update_slice(
                    cache["state"], state[None], (li, 0, 0, 0, 0)
                )
            rows.counters["linear_tokens"].append(jnp.sum(live, dtype=jnp.int32))
            if normed:
                return out.astype(cfg.dtype).reshape(b, p, h * dh)
            return _linear_out(out, block, cfg)

    def step(self, xin, block, spec, li, rows):
        """The state of every occupied row moves on by one position, in its
        leaf; a free row's stays as it is."""
        cfg, cache = rows.cfg, rows.cache
        q, k, v = project(xin, block, spec, rows, self.heads(cfg))
        with jax.named_scope("attn"), jax.named_scope("attn_linear"):
            out, new = linear_step(
                q[:, 0], k[:, 0], v[:, 0], cache["state"][li], _slopes(cfg),
                rows.live[:, 0],
            )
            with jax.named_scope("state_write"):
                cache["state"] = jax.lax.dynamic_update_slice(
                    cache["state"], new[None], (li, 0, 0, 0, 0)
                )
            return _linear_out(out[:, None], block, cfg)


LINEAR = Linear()
