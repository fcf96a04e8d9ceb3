"""Softmax attention over every earlier position (`global`) or over the
last `window` positions (`window`), and the q/k/v projection that the
`sparse` and `linear` kinds make as these do.

The cache keeps a layer's rows head-major: "k"/"v" [global layers, slots,
kv heads, max_len, head] grow with the sequence; "k_win"/"v_win" [window
layers, slots, kv heads, W, head] are rings: physical position t lives in
row t mod W, so a window layer's rows stop growing at W. Physical positions
count the left pad too; logical ones (physical less the pad) are what
rotary turns by. Head-major, because a head's rows are then whole (rows,
head) tiles whatever the number of heads: the step's kernel fetches blocks
of them out of the leaf itself, and where the heads are narrower than a
lane tile (64) the rows are what fills the other axis, not 25 heads padded
to 32.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from pathway_tpu.models.layers import (
    Array, Kind, Params, Rows, attend, kernel_may_run, rmsnorm, rope,
)


def step_uses_kernel(cfg: Any) -> bool:
    """Whether a step's attention, one query a slot, runs ops/attention.py
    `decode_attention` (the stacked cache leaf read in place, only the
    tiles that hold a live row fetched) and not the plain `attend` over
    every row the cache has room for: where `kernel_may_run`, with heads of
    a multiple of 128 lanes (a head's rows are the leaf's (rows, head)
    tiles, and a lane tile is 128 wide: heads of 64 or 96 would be
    half-empty tiles). The width asked is `cfg.head_dim`, that of a softmax
    or sparse layer's heads, whose q, k and v are one width; a latent
    layer's rows have no head axis and two widths, and latent.py
    `latent_step_uses_kernel` asks for them."""
    return kernel_may_run(cfg) and cfg.head_dim % 128 == 0


def prefill_uses_kernel(cfg: Any, width: int) -> bool:
    """Whether a prefill of prompts `width` wide runs ops/attention.py
    `prefill_attention` (scores kept in VMEM) and not the plain `attend`:
    where `kernel_may_run`, with heads of a multiple of 128 lanes and a
    width of 128 at least (every rung of `BucketPolicy.seq_bucket` from
    there up; the kernel pads the cap's rung inside). The heads are
    `cfg.head_dim` wide, q, k and v alike; a latent layer's are `qk_dim`
    against `v_dim`, and latent.py `latent_prefill_uses_kernel` is their
    rule."""
    return kernel_may_run(cfg) and cfg.head_dim % 128 == 0 and width >= 128


def rowwise_uses_kernel(cfg: Any, width: int) -> bool:
    """Whether a prefill `width` wide has layers whose q and k take
    ops/rowwise.py `rowwise_heads` (norm, rotary positions and a pad's zero
    in one pass over the qkv product, the arithmetic of `rmsnorm` and
    `rope` as a TPU runs it) and not those functions one after the other:
    where `prefill_uses_kernel` would hold of such a width, for a decoder
    with q/k norms or a rotary layer. A step (one row a slot) never
    does."""
    return (
        cfg.qk_norm or any(sp.pos == "rotary" for sp in cfg.layer_specs)
    ) and prefill_uses_kernel(cfg, width) and (
        # the pass has `rmsnorm`'s default epsilon written in
        not cfg.qk_norm or cfg.norm_eps == 1e-6
    )


def _takes_rowwise(cfg: Any, spec: Any) -> bool:
    """Whether a layer of such a prefill is one of them: it has a norm or a
    rotation to make."""
    return cfg.qk_norm or spec.pos == "rotary"


def fused(rows: Rows, spec: Any) -> bool:
    """Whether `project` makes this layer's q and k in the one pass."""
    return rows.fused and _takes_rowwise(rows.cfg, spec)


def _qkv_product(xin: Array, block: Params, cfg: Any) -> Array:
    """Normed rows times the layer's qkv matrix: [b, s, (heads + 2 kv
    heads) * dh], the heads of q, k and v side by side."""
    return jnp.einsum(
        "bsd,de->bse", xin, block["qkv"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    ).astype(cfg.dtype)


def _qkv(xin: Array, block: Params, cfg: Any, heads: tuple[int, int]):
    """q [b, s, heads, dh] and k, v [b, s, kv heads, dh] of normed rows."""
    b, s, _ = xin.shape
    (h, hk), dh = heads, cfg.head_dim
    q, k, v = jnp.split(
        _qkv_product(xin, block, cfg), [h * dh, (h + hk) * dh], axis=-1
    )
    return (
        q.reshape(b, s, h, dh), k.reshape(b, s, hk, dh), v.reshape(b, s, hk, dh)
    )


def _qkv_rowwise(xin: Array, block: Params, cfg: Any, spec: Any,
                 heads: tuple[int, int], tables, live: Array | None):
    """`_qkv`, then `rmsnorm` of q and k (`cfg.qk_norm`), `rope` of both
    (a rotary layer; `tables` are ops/rowwise.py `rope_tables` of the rows'
    positions) and zeros for the keys of rows not `live` (where given),
    as q and k each in one pass of ops/rowwise.py `rowwise_heads` over
    their lanes of the product, rounded where the program a TPU runs of
    those functions rounds (once, behind the rotation)."""
    # imported where it is traced: Pallas loads when a program first needs it
    from pathway_tpu.ops.rowwise import rowwise_heads

    b, s, _ = xin.shape
    (h, hk), dh = heads, cfg.head_dim
    qkv = _qkv_product(xin, block, cfg)
    tables = tables if spec.pos == "rotary" else None
    with jax.named_scope("rowwise"):
        q = rowwise_heads(
            qkv, block["q_norm"] if cfg.qk_norm else None, tables, None,
            first=0, heads=h, dh=dh,
        )
        k = rowwise_heads(
            qkv, block["k_norm"] if cfg.qk_norm else None, tables, live,
            first=h, heads=hk, dh=dh,
        )
    v = qkv[..., (h + hk) * dh:]
    return (
        q.reshape(b, s, h, dh), k.reshape(b, s, hk, dh), v.reshape(b, s, hk, dh)
    )


def project(xin: Array, block: Params, spec: Any, rows: Rows,
            heads: tuple[int, int], zero_pads: bool = False):
    """q [b, s, heads, dh] and k, v [b, s, kv heads, dh] of a layer's
    normed rows, q and k normed (`cfg.qk_norm`) and turned (a rotary
    layer): in one pass of ops/rowwise.py where `rows.fused`, which a
    prefill asks once (`rowwise_uses_kernel` of its width, and `rows.rope`
    then its `rope_tables` if it has a rotary layer). `zero_pads`: that
    pass also zeroes the keys of rows not `live` (a linear layer's)."""
    cfg = rows.cfg
    with jax.named_scope("attn"):
        if fused(rows, spec):
            return _qkv_rowwise(
                xin, block, cfg, spec, heads, rows.rope,
                rows.live if zero_pads else None,
            )
        q, k, v = _qkv(xin, block, cfg, heads)
        if cfg.qk_norm:
            q = rmsnorm(q, block["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, block["k_norm"], cfg.norm_eps)
        if spec.pos == "rotary":
            q, k = rope(q, rows.pos, cfg), rope(k, rows.pos, cfg)
    return q, k, v


class Softmax(Kind):
    """Attention over every earlier position, or over a window's ring."""

    def __init__(self, name: str, k: str, v: str):
        self.name, self.k, self.v = name, k, v
        self.window = name == "window"

    def cache(self, cfg, n, batch):
        rows = cfg.window if self.window else cfg.max_len
        shape = (n, batch, cfg.kv_heads, rows, cfg.head_dim)
        return {
            self.k: jax.ShapeDtypeStruct(shape, cfg.dtype),
            self.v: jax.ShapeDtypeStruct(shape, cfg.dtype),
        }

    @staticmethod
    def prefill_setup(rows):
        """The window layers' mask: the causal mask within the window,
        where the prompt is longer than it (else a window layer sees all)."""
        window, p = rows.cfg.window, rows.width
        rows.wmask = rows.mask
        if window is not None and window < p:
            at = jnp.arange(p)
            rows.wmask = rows.mask & (at[None, :] > at[:, None] - window)[None, None]

    @staticmethod
    def step_setup(rows):
        """The plain step's masks (each slot's rows from its pad to `at`,
        and a window's ring rows that hold such a position) and the slot
        and head indices of a row's write."""
        cfg, pos, pad = rows.cfg, rows.at, rows.pad
        at = jnp.arange(cfg.max_len)[None, :]
        rows.kmask = ((at <= pos[:, None]) & (at >= pad[:, None]))[:, None, None, :]
        if cfg.window is not None:
            # ring row j holds the newest physical position <= pos that is
            # j modulo W: before the pad (or before the sequence) it is no key
            ring = jnp.arange(cfg.window)[None, :]
            held = pos[:, None] - (pos[:, None] - ring) % cfg.window
            rows.wmask = (held >= pad[:, None])[:, None, None, :]
        rows.index = jnp.arange(pos.shape[0])[:, None], jnp.arange(cfg.kv_heads)[None, :]

    def prefill(self, xin, block, spec, li, rows):
        cfg, cache, p = rows.cfg, rows.cache, rows.width
        q, k, v = project(xin, block, spec, rows, self.heads(cfg))
        window = cfg.window
        with jax.named_scope("cache_write"):
            # head-major, as the cache lies
            kept_k = kt = k.transpose(0, 2, 1, 3)
            kept_v = vt = v.transpose(0, 2, 1, 3)
            if self.window and p > window:
                # a prompt longer than the window leaves its last W keys,
                # each in the ring's row of its physical position
                turn = (p - window) % window
                kept_k = jnp.roll(kt[:, :, p - window:], turn, axis=2)
                kept_v = jnp.roll(vt[:, :, p - window:], turn, axis=2)
            cache[self.k] = jax.lax.dynamic_update_slice(
                cache[self.k], kept_k[None], (li, 0, 0, 0, 0)
            )
            cache[self.v] = jax.lax.dynamic_update_slice(
                cache[self.v], kept_v[None], (li, 0, 0, 0, 0)
            )
        with jax.named_scope("attn"), jax.named_scope(f"attn_{self.name}"):
            if prefill_uses_kernel(cfg, p):
                # imported where it is traced: Pallas loads when a program
                # first needs it
                from pathway_tpu.ops.attention import prefill_attention

                return prefill_attention(
                    q, k, v, rows.valid,
                    window if self.window and window < p else None,
                )
            return attend(q, kt, vt, rows.wmask if self.window else rows.mask, cfg)

    def step(self, xin, block, spec, li, rows):
        cfg, cache, pos = rows.cfg, rows.cache, rows.at
        q, k, v = project(xin, block, spec, rows, self.heads(cfg))
        if step_uses_kernel(cfg):
            # imported where it is traced: Pallas loads when a program
            # first needs it
            from pathway_tpu.ops.attention import decode_attention

            with jax.named_scope("attn"), jax.named_scope(f"attn_{self.name}"):
                ctx, cache[self.k], cache[self.v] = decode_attention(
                    q[:, 0], k[:, 0], v[:, 0], cache[self.k], cache[self.v],
                    li, pos, rows.pad,
                )
            return ctx[:, None]
        at_row = (pos % cfg.window if self.window else pos)[:, None]
        slots, heads = rows.index
        with jax.named_scope("cache_write"):
            # a head's row at a time, which is what lies together
            cache[self.k] = cache[self.k].at[li, slots, heads, at_row].set(k[:, 0])
            cache[self.v] = cache[self.v].at[li, slots, heads, at_row].set(v[:, 0])
        with jax.named_scope("attn"), jax.named_scope(f"attn_{self.name}"):
            return attend(
                q, cache[self.k][li], cache[self.v][li],
                rows.wmask if self.window else rows.kmask, cfg,
            )


GLOBAL = Softmax("global", "k", "v")
WINDOW = Softmax("window", "k_win", "v_win")
