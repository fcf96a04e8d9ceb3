"""The `latent` kind: multi-head latent attention (`LatentSpec`). Of
normed rows h: the query's
low-rank row c_q = rms(h W_qa) x q_scale and each head's [nope | rope]
lanes c_q W_qb; [c | k_r] = h W_kva, c_kv = rms(c) x kv_scale, and each
head's [key nope lanes | value] = c_kv W_kvb; rotary on the rope lanes of
q and on k_r, which all heads share. A position keeps c_kv and the rotated
k_r and nothing else.

A prefill expands its own rows' keys and values from c_kv and attends
them as heads of nope + rope lanes against values of v_dim. A step never
expands a cached row: with W_kvb,i = [W_uk,i | W_uv,i] a head's score
against row j is (q_n,i W_uk,i^T) . c_kv,j + q_r,i . k_r,j, and its
output (sum_j p_ij c_kv,j) W_uv,i: the same function, read from the
latent rows as they lie.

A latent layer's cache leaves hold rows without a head axis at physical
positions: "c_kv" [.., rows, kv_rank], the normed (and scaled) low-rank
row every head's keys and values are products of, and "k_rope" [.., rows,
rope lanes], the one rotated key all heads share (`_rope_lanes`: its
rope_dim lanes in a whole lane tile, zeros behind them).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pathway_tpu.models.layers import (
    Array, Kind, Leaf, Params, kernel_may_run, rmsnorm, rope, summed,
)


# a prefill's scores [heads, queries, keys] are float32: 26.8 GB at 10,240
# tokens and 64 heads. Where no kernel keeps them in VMEM the queries go
# through in chunks whose scores stay under this
_LATENT_SCORE_BYTES = 256 << 20


def _latent_rows(xin: Array, block: Params, pos: Array, spec: Any,
                 cfg: Any, *, for_kernel: bool = False):
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
        c_q = rmsnorm(product(xin, block["q_a"]), gain("q_a_norm", lt.q_scale), eps)
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
        c_kv = rmsnorm(
            kv[..., :lt.kv_rank], gain("kv_a_norm", lt.kv_scale), eps
        )
        k_r = kv[..., lt.kv_rank:]
    if spec.pos == "rotary":
        if not for_kernel:
            q_r = rope(q_r, pos, cfg)
        k_r = rope(k_r[:, :, None, :], pos, cfg)[:, :, 0, :]
    return q_n, q_r, c_kv, k_r


def _rope_lanes(cfg: Any) -> int:
    """The width of the `k_rope` leaf: the rotary key's lanes rounded up to
    a lane tile (64 -> 128). A tiled row of 64 lanes takes a tile's room in
    the chip's memory anyway, and a leaf left 64 wide is laid out rows-minor
    by the TPU's compiler: every row-major use of it (the step's kernel, a
    row's write) then copies the whole leaf there and back, twice its size
    a step (read in the compiled step)."""
    return -(-cfg.latent.rope_dim // 128) * 128


def _in_rope_lanes(x: Array, cfg: Any) -> Array:
    """x [..., rope_dim] with zeros behind it up to the leaf's width."""
    extra = _rope_lanes(cfg) - x.shape[-1]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, extra),))


def _kv_up(block: Params, cfg: Any) -> Array:
    """W_kvb as [kv_rank, heads, nope + v]: a head's W_uk beside its W_uv."""
    lt = cfg.latent
    return block["kv_b"].astype(cfg.dtype).reshape(
        lt.kv_rank, cfg.n_heads, lt.nope_dim + lt.v_dim
    )


def _attend_latent(q: Array, k: Array, v: Array, ok: Array,
                   cfg: Any) -> Array:
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


def latent_prefill_uses_kernel(cfg: Any, width: int) -> bool:
    """Whether the latent layers of a prefill `width` wide run
    ops/latent_attention.py `latent_prefill_attention` (the tile body of
    `prefill_attention` over a block of heads a grid step, scores kept in
    VMEM) and not `_attend_latent` over chunks of queries: where
    `kernel_may_run`, for a decoder that has such layers, at a width of 128
    at least, with nope lanes and values of a multiple of 128 lanes (a
    head's lanes are then whole lane tiles of the products that make them).
    The rotary lanes need not be one: the kernel reads them in the lane
    tile the `k_rope` leaf keeps them in (`_rope_lanes`), as a product of
    their own."""
    return (
        bool(cfg.n_mixer_layers("latent"))
        and kernel_may_run(cfg)
        and cfg.latent.nope_dim % 128 == 0
        and cfg.latent.v_dim % 128 == 0
        and width >= 128
    )


def latent_step_uses_kernel(cfg: Any) -> bool:
    """Whether a step's latent layers run ops/latent_attention.py
    `latent_decode_attention`, which fetches only the tiles of `c_kv` and
    `k_rope` that hold a live row of the slot, and not products over every
    row the cache has room for: where `kernel_may_run`, for a decoder with
    such layers, with a latent row of a multiple of 128 lanes and rows that
    are whole tiles of `latent_decode_tile` (`k_rope` is a lane tile wide:
    `_rope_lanes`)."""
    if not (
        cfg.n_mixer_layers("latent") and kernel_may_run(cfg)
        and cfg.latent.kv_rank % 128 == 0
    ):
        return False
    from pathway_tpu.ops.latent_attention import latent_decode_tile

    return cfg.max_len % latent_decode_tile(cfg.max_len) == 0


class Latent(Kind):
    name = "latent"
    # a step's latent rows that its occupied slots attended, summed over
    # the latent layers
    step_counters = summed("latent_rows_read")

    def leaves(self, cfg, spec):
        # the low-rank rows are whole on every chip; the heads are split
        lt, d, h = cfg.latent, cfg.d_model, cfg.n_heads

        def part(i):  # the four down and up products draw from the qkv's key
            return lambda ks: jax.random.split(ks[0], 4)[i]

        return {
            "q_a": Leaf((d, lt.q_rank), P(None, None), part(0)),
            "q_a_norm": Leaf((lt.q_rank,), P(None)),
            "q_b": Leaf((lt.q_rank, h * lt.qk_dim), P(None, "model"), part(1)),
            "kv_a": Leaf((d, lt.kv_rank + lt.rope_dim), P(None, None), part(2)),
            "kv_a_norm": Leaf((lt.kv_rank,), P(None)),
            "kv_b": Leaf(
                (lt.kv_rank, h * (lt.nope_dim + lt.v_dim)), P(None, "model"), part(3)
            ),
            "o": Leaf((h * lt.v_dim, d), P("model", None), 1),
        }

    def cache(self, cfg, n, batch):
        return {
            "c_kv": jax.ShapeDtypeStruct(
                (n, batch, cfg.max_len, cfg.latent.kv_rank), cfg.dtype
            ),
            "k_rope": jax.ShapeDtypeStruct(
                (n, batch, cfg.max_len, _rope_lanes(cfg)), cfg.dtype
            ),
        }

    def prefill(self, xin, block, spec, li, rows):
        """Every row's c_kv and k_r into the layer's leaves at its physical
        position, and the prompt's own keys and values expanded from c_kv
        for its attention."""
        cfg, cache, valid, pos_idx = rows.cfg, rows.cache, rows.valid, rows.pos
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
                cache["c_kv"] = jax.lax.dynamic_update_slice(
                    cache["c_kv"], c_kv[None], (li, 0, 0, 0)
                )
                cache["k_rope"] = jax.lax.dynamic_update_slice(
                    cache["k_rope"], kept[None], (li, 0, 0, 0)
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

    def step(self, xin, block, spec, li, rows):
        """Every row at its physical position `at` [b] behind its pad: its
        c_kv and k_r go into row `at` of its slot, and its heads attend the
        slot's latent rows pad .. at in the absorbed form, no row of the
        cache expanded."""
        cfg, cache, pos, pad_len, live, counters = (
            rows.cfg, rows.cache, rows.at, rows.pad, rows.live, rows.counters
        )
        lt = cfg.latent
        b = xin.shape[0]
        cname, rname = "c_kv", "k_rope"
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


LATENT = Latent()
