"""The LongCat-Flash decoder family (meituan-longcat/LongCat-Flash-Omni's
language model, key for key LongCat-Flash-Chat's, arXiv:2509.01322):
everything the benchmark knows of this decoder's block, and the one file
that reads its keys, under the names of its own published ``config.json``.
``pwbench/spec.py family()`` finds it by the ``family`` key of a
configuration's file. Text only: the audio and vision encoders and the codec
decoder are outside a RAG answerer's path and are not built.

The equations (a line marked + is the family's published form and not a key
of ``config.json``; each is listed under ``assumed`` in the configuration's
file; sources in PAPERS.md). d = hidden_size 6144, H = 64 heads, r_q =
q_lora_rank 1536, r_kv = kv_lora_rank 512, d_n = qk_nope_head_dim 128, d_r =
qk_rope_head_dim 64, d_v = v_head_dim 128, eps = rms_norm_eps 1e-5, no biases
(attention_bias false), rms(x, g) the RMS norm with a learned scale.

MLA sub-layer A(x; theta), h = rms(x, g_in):
  c_q = rms(h W_qa, g_q) x sqrt(d / r_q)   (mla_scale_q_lora: x 2.0)
  per head i: [q_n,i | q_r,i] = c_q W_qb,i   (128 | 64)
  [c_raw | k_r,raw] = h W_kva   (512 | 64)
  c_kv = rms(c_raw, g_kv) x sqrt(d / r_kv)   (mla_scale_kv_lora: x 3.464)
  per head i: [k_n,i | v_i] = c_kv W_kvb,i   (128 | 128); one k_r for all heads
  rotary (rope_theta 1e7, no scaling: the row has no rope_scaling) at the
      logical position on q_r,i and k_r only
+ rotate-half over the 64 lanes; the published pairing of neighbouring
      lanes is the same function under one fixed permutation of W_qb's and
      W_kva's rotary columns, which seeded weights make moot
  a_t,i = softmax_{j<=t}((q_n,i . k_n,i,j + q_r,i . k_r,j) / sqrt(192)) v_i,j
  A = concat_i(a_i) W_o   (8192 -> 6144)
  What a slot keeps a position and sub-layer: c_kv (512, normed and scaled)
  and k_r (64, rotated), bf16. A step of the PROGRAM never expands a cached
  row (q~_i = q_n,i W_uk,i^T against c_kv, z_i W_uv,i after the sum: the
  absorbed form, arXiv:2405.04434); the reference below expands every row.

Layer l (+ the order, from the family's report and modelling file), x the
stream:
  1. x1 = x + A(x; attention 0)
  2. u = rms(x1, g_post0) ; m = MoE(u) (the shortcut's branch starts here) ;
     x2 = x1 + F0(u), F(u) = (silu(u W_g) * (u W_u)) W_d, width 12,288
  3. x3 = x2 + A(x2; attention 1)
  4. x4 = x3 + F1(rms(x3, g_post1)) + m

MoE(u): s = softmax over all 768 of (u W_r), float32
+ the moe_topk 12 chosen are the largest of s + b (b the selection bias,
      e_score_correction_bias, float32 [768])
  w_k = routed_scaling_factor 6 x s_k of the chosen, not renormalised; an
      index e < 512 is a SwiGLU expert of width 2,048, an index >= 512 an
      identity expert (zero_expert_type identity) whose output is u itself:
  MoE(u) = sum_{k real} w_k E_k(u) + (sum_{k zero} w_k) u
  This chip's share: the real experts [first, first + count) of 512; the
  router, the choice and the weights are over all 768;
  MoE_here(u) = sum over the chosen k in the held range of w_k E_k(u)
      + (sum_{k zero} w_k) u
  What the absent experts would add is left out, here and in the program.

Ends: x_0 = E[token] (no scale); logits = rms(x_L, g_f) W_head, untied; ids,
logits and the served argmax over the vocabulary's slice held here.

What a family's file gives (bench/README.md, "Adding things"): ``sizes``,
``program_config``, ``make_params``, ``decoder_logits``, ``n_block``,
``token_flops``, ``prefill_flops``, ``decode_step_bytes``,
``decode_step_flops``, ``n_params``, ``STEP`` and ``compile_jobs``. Beside
them ``expert_matrix_elements`` for the readers of the expert layer,
``mla_prefill_flops`` and ``mla_step_seconds`` for the latent attention's two
readers, ``moe_reference`` for the test that adds the shares up, and the two
faults of this block that bench/tests plants: ``latent_scale_dropped`` and
``zero_experts_silent``.

By hand on the chip, at a cell's own size (bench/control.py ``--fault``
names only the faults of pwbench/faults.py): a short window of the cell's
load with one of the family's faults planted; one line, exit 0 when not
correct.

    python3 bench/families/longcat_flash.py --workload <cell> --seed 11 \\
        --seconds 12 --fault latent_scale_dropped
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import sys
from pathlib import Path
from typing import Any, Callable, ContextManager, Iterator

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parents[n]) for n in (1, 2)]
from pwbench import reference, weights  # noqa: E402

from pathway_tpu import models as _program  # noqa: E402  (the program's models)

_model = _program.transformer  # the module that holds the served decoder

# where the batcher binds the step program when it is built (module,
# attribute): pwbench/faults.py plants its broken steps there.
STEP = (_model.__name__, "decode_step_slots")


def sizes(config: dict) -> dict:
    """The decoder's sizes, from the top level of a configuration's file.
    ``family``, ``vocab``, ``positions``, ``layers`` and ``tag`` (the
    seed's key tag) are what the harness reads; the rest is this file's.
    ``experts`` are the experts HELD (``n_routed_experts`` of the file, from
    ``expert_first`` on) of the ``experts_all`` the router chooses among
    (``published.n_routed_experts``, or as many where the file cuts none)."""
    if config["attention_bias"] or config["attention_method"] != "MLA":
        raise ValueError("this family: latent attention, no bias")
    if config["zero_expert_type"] != "identity":
        raise ValueError("this family's zero-computation experts are identities")
    if "rope_scaling" in config:
        raise ValueError("this family's file is written for unscaled rotary positions")
    published = config.get("published", {})
    d = config["hidden_size"]
    held = config["n_routed_experts"]
    experts_all = published.get("n_routed_experts", held)
    first = config.get("expert_first", 0)
    if first + held > experts_all:
        raise ValueError("the experts held lie inside the published experts")
    r_q, r_kv = config["q_lora_rank"], config["kv_lora_rank"]
    return dict(
        family="longcat_flash", tag=5,
        vocab=config["vocab_size"], d=d, heads=config["num_attention_heads"],
        r_q=r_q, r_kv=r_kv, dn=config["qk_nope_head_dim"],
        dr=config["qk_rope_head_dim"], dv=config["v_head_dim"],
        q_scale=math.sqrt(d / r_q) if config["mla_scale_q_lora"] else 1.0,
        kv_scale=math.sqrt(d / r_kv) if config["mla_scale_kv_lora"] else 1.0,
        layers=config["num_layers"], ff=config["ffn_hidden_size"],
        expert_ff=config["expert_ffn_hidden_size"],
        experts=held, expert_first=first, experts_all=experts_all,
        zero=config["zero_expert_num"], active=config["moe_topk"],
        scaling=float(config["routed_scaling_factor"]),
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        positions=config["max_position_embeddings"],
    )


def program_config(config: dict, dtype: Any) -> Any:
    """The program's configuration object, as ``JaxLMChat(config=...)``
    takes it: two entries of the per-layer list a published layer, the
    first starting the expert branch and the second landing it."""
    sz = sizes(config)
    sub = _program.LayerSpec(mixer="latent", pos="rotary", ff="swiglu")
    return _program.TransformerConfig(
        causal=True, pool="last", dtype=dtype,
        vocab_size=sz["vocab"], d_model=sz["d"], n_heads=sz["heads"],
        n_layers=2 * sz["layers"], d_ff=sz["ff"], d_expert=sz["expert_ff"],
        rope_theta=sz["theta"], max_len=sz["positions"], tie_embeddings=False,
        norm_eps=sz["eps"],
        latent=_program.LatentSpec(
            q_rank=sz["r_q"], kv_rank=sz["r_kv"], nope_dim=sz["dn"],
            rope_dim=sz["dr"], v_dim=sz["dv"], q_scale=sz["q_scale"],
            kv_scale=sz["kv_scale"],
        ),
        router="all", router_bias=True, router_scale=sz["scaling"],
        n_experts=sz["experts_all"], n_zero_experts=sz["zero"],
        n_active=sz["active"],
        experts_held=(sz["expert_first"], sz["experts"]), expert_act="silu",
        layers=(
            dataclasses.replace(sub, shortcut="start"),
            dataclasses.replace(sub, shortcut="land"),
        ) * sz["layers"],
    )


# --------------------------------------------------------------- weights
# Drawn from the seed, bfloat16 (the selection bias float32); norm scales
# 1 + 0.1 normal. Matrices are normal / sqrt(fan_in) but for the scales
# below, chosen so that the random stack is conditioned as a trained one is
# (``assumed`` in the configuration's file; PERF.md, PR 43, has what each
# was read against):
#
# * the embedding has unit spread, so that the residual stream starts at
#   the spread its branches have;
# * W_qb is 1 / q_scale as wide: a head's query has unit spread behind the
#   published x sqrt(d / r_q). W_kvb keeps its width, so keys and values are
#   sqrt(d / r_kv) = 3.46 wide and the scores have a spread of about 3:
#   attention is peaked on a few keys, and a c_kv that lost its factor
#   (``latent_scale_dropped``) spreads it over thousands and returns another
#   vector;
# * W_o is 1 / kv_scale as wide, so that the attention's branch has the
#   stream's spread behind values 3.46 wide;
# * the router is twice as wide (logits of spread 2): the twelve chosen of
#   768 then hold about a third of the softmax's mass, and times
#   routed_scaling_factor the expert branch weighs about 2, of which the
#   identity experts' third (0.6 u a token) moves the logits although only
#   16 of the 512 real experts answer here;
# * the selection bias is normal x 0.5 / (experts + identity experts): of the
#   order of the gap between the twelfth and the thirteenth score, so it
#   moves some choices and "the bias is for the choice, not for the weight"
#   is tested.
# The program's tree and the reference both come from these functions; the
# reference draws its leaves again, a sub-layer at a time, and never reads
# the program's copy.

_EMBED_SPREAD = 1.0
_ROUTER_GAIN = 2.0
_BIAS_SPREAD = 0.5


def _n_out(sz: dict) -> int:
    return sz["experts_all"] + sz["zero"]


def _sub_leaves(key: Any, layer: Any, sub: int, sz: dict) -> dict:
    """The leaves of sub-layer ``sub`` (0 or 1) of published layer ``layer``
    (a traced or a Python integer): an MLA, a dense SwiGLU and, in sub-layer
    0, the router and the experts held here."""
    import jax
    import jax.numpy as jnp

    d, f, h = sz["d"], sz["ff"], sz["heads"]
    r_q, r_kv = sz["r_q"], sz["r_kv"]
    k = jax.random.fold_in(key, 1000 + 2 * layer + sub)
    s = 1.0 / math.sqrt(d)
    leaf = weights.leaf
    leaves = {
        "q_a": leaf(k, 0, (d, r_q), s, 0.0),
        "q_a_norm": leaf(k, 1, (r_q,), 0.1, 1.0),
        "q_b": leaf(
            k, 2, (r_q, h * (sz["dn"] + sz["dr"])),
            1.0 / (sz["q_scale"] * math.sqrt(r_q)), 0.0,
        ),
        "kv_a": leaf(k, 3, (d, r_kv + sz["dr"]), s, 0.0),
        "kv_a_norm": leaf(k, 4, (r_kv,), 0.1, 1.0),
        "kv_b": leaf(
            k, 5, (r_kv, h * (sz["dn"] + sz["dv"])), 1.0 / math.sqrt(r_kv), 0.0
        ),
        "o": leaf(
            k, 6, (h * sz["dv"], d),
            1.0 / (sz["kv_scale"] * math.sqrt(h * sz["dv"])), 0.0,
        ),
        "ln1_scale": leaf(k, 7, (d,), 0.1, 1.0),
        "ln2_scale": leaf(k, 8, (d,), 0.1, 1.0),
        "ff_gate": leaf(k, 9, (d, f), s, 0.0),
        "ff_up": leaf(k, 10, (d, f), s, 0.0),
        "ff_out": leaf(k, 11, (f, d), 1.0 / math.sqrt(f), 0.0),
    }
    if sub == 0:
        e, fe, n_out = sz["experts"], sz["expert_ff"], _n_out(sz)
        leaves["router"] = leaf(k, 12, (d, n_out), _ROUTER_GAIN * s, 0.0)
        leaves["router_bias"] = (_BIAS_SPREAD / n_out) * jax.random.normal(
            jax.random.fold_in(k, 13), (n_out,), jnp.float32
        )
        # the held experts' matrices are those of experts first .. first +
        # count of the layer: drawn an expert at a time, so that another
        # share of the same layer holds other matrices and the same router
        ke = jax.random.fold_in(k, 14)

        def expert(i: Any, index: int, shape: tuple, scale: float) -> Any:
            return leaf(jax.random.fold_in(ke, sz["expert_first"] + i), index,
                        shape, scale, 0.0)

        ids = jnp.arange(e)
        leaves["expert_gate"] = jax.vmap(lambda i: expert(i, 0, (d, fe), s))(ids)
        leaves["expert_up"] = jax.vmap(lambda i: expert(i, 1, (d, fe), s))(ids)
        leaves["expert_down"] = jax.vmap(
            lambda i: expert(i, 2, (fe, d), 1.0 / math.sqrt(fe))
        )(ids)
    return leaves


def _top_leaves(key: Any, sz: dict) -> dict:
    d = sz["d"]
    return {
        "tok_embed": weights.leaf(key, 0, (sz["vocab"], d), _EMBED_SPREAD, 0.0),
        "ln_f_scale": weights.leaf(key, 2, (d,), 0.1, 1.0),
        "lm_head": weights.leaf(key, 3, (d, sz["vocab"]), 1.0 / math.sqrt(d), 0.0),
    }


def _tree(kd: Any, sz_items: tuple) -> dict:
    import jax

    sz = dict(sz_items)
    key = jax.random.wrap_key_data(kd)
    params = _top_leaves(key, sz)
    params["blocks"] = [
        _sub_leaves(key, layer, sub, sz)
        for layer in range(sz["layers"]) for sub in (0, 1)
    ]
    return params


@functools.lru_cache(maxsize=None)
def _jitted_tree(sz_items: tuple):
    import jax

    return jax.jit(functools.partial(_tree, sz_items=sz_items))


def make_params(seed: int, sz: dict) -> dict:
    """The whole tree in the layout the program serves: bfloat16, one
    jitted call from the seed."""
    import jax.numpy as jnp

    fn = _jitted_tree(tuple(sorted(sz.items())))
    return fn(jnp.asarray(weights.key_data(seed, sz["tag"])))


# ------------------------------------------------------------- reference
# float32 at ``highest`` precision, no cache, no absorption, no kernels, no
# batching: a row at a time through a sub-layer at a time (sub-layer 0's
# float32 leaves are 3.7 GB at the published widths, sub-layer 1's 1.3 GB),
# EVERY position's keys and values expanded from its latent row, attention
# a block of queries at a time against every key so that the score array
# fits at 10,240 tokens. Every held expert multiplies every row, and the
# rows that did not choose it take weight 0. Rows are padded on the RIGHT;
# position is index.

_QUERY_BLOCK = 256


def _rms(x: Any, scale: Any, eps: float) -> Any:
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x: Any, theta: float) -> Any:
    """Rotary positions 0..s-1, rotate-half over the last axis: x [s, heads, dr]."""
    import jax.numpy as jnp

    s, _h, dr = x.shape
    half = dr // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
        axis=-1,
    )


def _attention(q: Any, k: Any, v: Any, mask: Any, fp8: bool) -> Any:
    """q and k [s, heads, dn + dr], v [s, heads, dv], mask [s] of the valid
    keys -> [s, heads * dv]: causal softmax(q k^T / sqrt(dn + dr)) v."""
    import jax
    import jax.numpy as jnp

    s, h, dk = q.shape
    blk = min(_QUERY_BLOCK, s)
    n = -(-s // blk)
    qpad = jnp.pad(q, ((0, n * blk - s), (0, 0), (0, 0)))
    kq, vq = reference.quant(k, fp8), reference.quant(v, fp8)
    kp = jnp.arange(s)[None, :]

    def one(i_qb):
        i, qb = i_qb
        qp = i * blk + jnp.arange(blk)[:, None]
        ok = (kp <= qp) & mask[None, :]
        scores = jnp.einsum(
            "qhd,shd->hqs", reference.quant(qb, fp8), kq,
            precision=jax.lax.Precision.HIGHEST,
        ) / math.sqrt(dk)
        probs = jax.nn.softmax(jnp.where(ok[None], scores, -1e30), axis=-1)
        return jnp.einsum(
            "hqs,shd->qhd", reference.quant(probs, fp8), vq,
            precision=jax.lax.Precision.HIGHEST,
        )

    ctx = jax.lax.map(one, (jnp.arange(n), qpad.reshape(n, blk, h, dk)))
    return ctx.reshape(n * blk, -1)[:s]


def _mla(x: Any, mask: Any, w: dict, sz: dict, fp8: bool) -> Any:
    """A(x; theta) of one sequence [s, d], in the expanded form."""
    import jax.numpy as jnp

    s, _d = x.shape
    h, dn, dr, dv = sz["heads"], sz["dn"], sz["dr"], sz["dv"]
    mm, eps = reference.mm, sz["eps"]
    hline = _rms(x, w["ln1_scale"], eps)
    c_q = _rms(mm(hline, w["q_a"], fp8), w["q_a_norm"], eps) * sz["q_scale"]
    q = mm(c_q, w["q_b"], fp8).reshape(s, h, dn + dr)
    kv = mm(hline, w["kv_a"], fp8)
    c_kv = _rms(kv[:, :sz["r_kv"]], w["kv_a_norm"], eps) * sz["kv_scale"]
    k_r = _rope(kv[:, None, sz["r_kv"]:], sz["theta"])  # one for all heads
    up = mm(c_kv, w["kv_b"], fp8).reshape(s, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], sz["theta"])], axis=-1)
    k = jnp.concatenate(
        [up[..., :dn], jnp.broadcast_to(k_r, (s, h, dr))], axis=-1
    )
    return mm(_attention(q, k, up[..., dn:], mask, fp8), w["o"], fp8)


def _dense(u: Any, w: dict, fp8: bool) -> Any:
    import jax

    mm = reference.mm
    hidden = jax.nn.silu(mm(u, w["ff_gate"], fp8)) * mm(u, w["ff_up"], fp8)
    return mm(hidden, w["ff_out"], fp8)


def route(u: Any, w: dict, sz: dict, fp8: bool = False):
    """Each token's moe_topk chosen outputs of the router and their weights:
    the largest of softmax(u W_r) + b, weighted by scaling x their softmax
    score without b, not renormalised. The control rounds this matrix
    product's operands like every other."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(reference.mm(u, w["router"], fp8), axis=-1)
    _, idx = jax.lax.top_k(scores + w["router_bias"], sz["active"])
    return idx, sz["scaling"] * jnp.take_along_axis(scores, idx, axis=-1)


def moe_reference(u: Any, w: dict, sz: dict, fp8: bool = False,
                  zero: bool = True) -> Any:
    """MoE_here(u) of rows u [s, d]: the chosen experts of the held range
    ``expert_first`` .. + ``experts`` (``w``'s expert leaves are theirs) and,
    with ``zero``, the identity experts' part. Every held expert multiplies
    every row; a row that did not choose it takes weight 0."""
    import jax
    import jax.numpy as jnp

    idx, wts = route(u, w, sz, fp8)
    mm = reference.mm

    def one(e, acc):
        mine = jnp.sum(jnp.where(idx == sz["expert_first"] + e, wts, 0.0), axis=1)
        hidden = jax.nn.silu(mm(u, w["expert_gate"][e], fp8)) * mm(
            u, w["expert_up"][e], fp8
        )
        return acc + mine[:, None] * mm(hidden, w["expert_down"][e], fp8)

    y = jax.lax.fori_loop(0, sz["experts"], one, jnp.zeros_like(u))
    if zero:
        share = jnp.sum(jnp.where(idx >= sz["experts_all"], wts, 0.0), axis=1)
        y = y + share[:, None] * u
    return y


def _sub_one(x: Any, m: Any, mask: Any, w: dict, sz: dict, sub: int,
             fp8: bool):
    """One sequence [s, d] through one sub-layer: (x, the expert branch in
    flight). Sub-layer 0 starts the branch from its post-attention norm;
    sub-layer 1 adds it where its feed-forward's output goes."""
    x = x + _mla(x, mask, w, sz, fp8)
    u = _rms(x, w["ln2_scale"], sz["eps"])
    if sub == 0:
        return x + _dense(u, w, fp8), moe_reference(u, w, sz, fp8)
    return x + _dense(u, w, fp8) + m, m


@functools.lru_cache(maxsize=None)
def _sub_fn(sz_items: tuple, sub: int, fp8: bool):
    """jit of: draw the sub-layer's weights, run one row through it."""
    import jax
    import jax.numpy as jnp

    sz = dict(sz_items)

    def fn(kd, li, x, m, mask):
        key = jax.random.wrap_key_data(kd)
        w = {
            k: v.astype(jnp.float32)
            for k, v in _sub_leaves(key, li, sub, sz).items()
        }
        return _sub_one(x, m, mask, w, sz, sub, fp8)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _top_fn(sz_items: tuple):
    import jax
    import jax.numpy as jnp

    sz = dict(sz_items)

    def fn(kd):
        key = jax.random.wrap_key_data(kd)
        return {
            k: v.astype(jnp.float32) for k, v in _top_leaves(key, sz).items()
        }

    return jax.jit(fn)


def decoder_logits(seed: int, sz: dict, rows: list[list[int]],
                   at: list[range], width: int,
                   fp8: bool = False) -> list[np.ndarray]:
    """For each row of token ids, the logits [len(at[i]), vocab] at the
    positions ``at[i]``, in float32 at ``highest`` precision from weights
    drawn again from the seed, sub-layer by sub-layer, a row at a time. Rows
    are padded on the right to one width (the longest row's, rounded up to
    512, at most ``width``), so that one program serves every seed of a
    cell. ``fp8`` is the control (reference.py): both operands of every
    matrix product rounded to fp8, the router's too."""
    import jax
    import jax.numpy as jnp

    width = min(width, -(-max(len(r) for r in rows) // 512) * 512)
    items = tuple(sorted(sz.items()))
    kd = jnp.asarray(weights.key_data(seed, sz["tag"]))
    top = _top_fn(items)(kd)
    logits = []
    for r, where in zip(rows, at):
        ids = np.zeros(width, np.int32)
        ids[:len(r)] = r
        x = top["tok_embed"][jnp.asarray(ids)]
        mask = jnp.arange(width) < len(r)
        m = jnp.zeros_like(x)
        for li in range(sz["layers"]):
            for sub in (0, 1):
                x, m = _sub_fn(items, sub, fp8)(
                    kd, jnp.asarray(li, jnp.int32), x, m, mask
                )
        hline = _rms(x[where.start:where.stop, :], top["ln_f_scale"], sz["eps"])
        lg = reference.mm(hline, top["lm_head"], fp8)
        logits.append(np.asarray(jax.device_get(lg), np.float32))
    return logits


# ---------------------------------------------------------------- counts
# Operations and bytes the algorithm needs, from shapes alone.
#
# * one token through a published layer multiplies with two MLAs (W_qa d x
#   r_q, W_qb r_q x H (dn + dr), W_kva d x (r_kv + dr), W_kvb r_kv x H (dn +
#   dv), W_o H dv x d), two dense SwiGLUs (3 d ff each), the router (d x 768)
#   and the experts it chose AMONG THOSE HELD HERE: of its moe_topk picks over
#   768 outputs an EXPECTED moe_topk x held / 768 (a quarter of an expert a
#   token at 16 of 512 + 256; an expectation under an even router, not a
#   count: the program's ``routed_pairs`` is the count). n_block counts those
#   and token_flops is twice it.
# * a prefill of p real tokens adds, a sub-layer, 2 H (dn + dr) + 2 H dv a
#   causal pair (the scores over 192 lanes, the sum over 128), and the logits
#   of the last position.
# * a decode step reads the matrices and the head once; of a layer's held
#   experts those that m rows' picks touch; c_kv and k_r of the live
#   positions, (r_kv + dr) x 2 bytes a position and sub-layer, and writes the
#   m new rows. In the absorbed form a row's attention is, a sub-layer, q~ =
#   q_n W_uk^T and z W_uv (together W_kvb's elements, as the expanded form's
#   product with the new row was) and 2 H (r_kv + dr) + 2 H r_kv a live row.

def _mla_matrix_elements(sz: dict) -> int:
    h = sz["heads"]
    return (
        sz["d"] * sz["r_q"] + sz["r_q"] * h * (sz["dn"] + sz["dr"])
        + sz["d"] * (sz["r_kv"] + sz["dr"])
        + sz["r_kv"] * h * (sz["dn"] + sz["dv"]) + h * sz["dv"] * sz["d"]
    )


def _layer_dense_elements(sz: dict) -> int:
    """A published layer outside its experts: 638.8 M at the published widths."""
    return (
        2 * _mla_matrix_elements(sz) + 2 * 3 * sz["d"] * sz["ff"]
        + sz["d"] * _n_out(sz)
    )


def expert_matrix_elements(sz: dict) -> int:
    """One expert's three matrices."""
    return 3 * sz["d"] * sz["expert_ff"]


def held_picks(sz: dict) -> float:
    """The picks of a token that an even router sends to the experts held."""
    return sz["active"] * sz["experts"] / _n_out(sz)


def n_block(sz: dict) -> int:
    return int(sz["layers"] * (
        _layer_dense_elements(sz) + held_picks(sz) * expert_matrix_elements(sz)
    ))


def token_flops(sz: dict) -> int:
    """2 x the matrices one token is multiplied with here (n_block)."""
    return 2 * n_block(sz)


def _pair_flops(sz: dict) -> int:
    """A causal pair of one sub-layer's prefill: scores and weighted sum."""
    return 2 * sz["heads"] * (sz["dn"] + sz["dr"] + sz["dv"])


def mla_prefill_flops(sz: dict, p: int) -> float:
    """The attention of every sub-layer of a prefill of p real tokens."""
    return 2 * sz["layers"] * _pair_flops(sz) * p * (p + 1) / 2


def prefill_flops(sz: dict, p: int) -> float:
    return token_flops(sz) * p + mla_prefill_flops(sz, p) + 2 * sz["d"] * sz["vocab"]


def _row_bytes(sz: dict) -> int:
    """A position's c_kv and k_r in one sub-layer, bf16: 1,152 B."""
    return (sz["r_kv"] + sz["dr"]) * 2


def _row_flops(sz: dict) -> int:
    """A live latent row of one sub-layer in a step's absorbed attention."""
    return 2 * sz["heads"] * (2 * sz["r_kv"] + sz["dr"])


def mla_step_seconds(sz: dict, rows: float, peaks: dict) -> float:
    """The roofline time of a step's latent attention over ``rows`` live
    latent rows (summed over slots and sub-layers): the larger of the
    absorbed products over them at the bf16 peak and their bytes at the HBM
    bandwidth."""
    return max(
        rows * _row_flops(sz) / peaks["bf16_flops_per_s"],
        rows * _row_bytes(sz) / peaks["hbm_bytes_per_s"],
    )


def experts_touched(sz: dict, m: float) -> float:
    """Of one layer's held experts, those that m rows' picks hit under an
    even router."""
    return sz["experts"] * (1.0 - (1.0 - 1.0 / _n_out(sz)) ** (m * sz["active"]))


def decode_step_bytes(sz: dict, contexts: list[float]) -> float:
    m = len(contexts)
    once = sz["layers"] * _layer_dense_elements(sz) + sz["d"] * sz["vocab"]
    experts = sz["layers"] * experts_touched(sz, m) * expert_matrix_elements(sz)
    rows = 2 * sz["layers"] * (sum(contexts) + m) * _row_bytes(sz)
    return 2 * (once + experts) + rows


def decode_step_flops(sz: dict, contexts: list[float]) -> float:
    per_row = token_flops(sz) + 2 * sz["d"] * sz["vocab"]
    return sum(
        per_row + 2 * sz["layers"] * _row_flops(sz) * c for c in contexts
    )


def n_params(sz: dict, *, embedding: bool) -> int:
    """Parameters held here: the blocks (the experts held, not the
    published 512), with or without the embedding and the head."""
    d = sz["d"]
    n = sz["layers"] * (
        _layer_dense_elements(sz) + _n_out(sz)
        + sz["experts"] * expert_matrix_elements(sz)
        + 2 * (2 * d + sz["r_q"] + sz["r_kv"])
    ) + d
    if embedding:
        n += 2 * sz["vocab"] * d
    return n


# ------------------------------------------------- rehearse.py --compile

def compile_jobs(config: dict, shaped: Callable, i32: Callable) -> dict:
    """The step and prefill programs at their real shapes, name -> a
    function that lowers it. ``shaped(tree)`` puts a tree of shapes on the
    described chip; ``i32(*shape)`` is an int32 argument there."""
    import jax
    import jax.numpy as jnp

    srv = config["server"]
    dsz = sizes(config)
    dec_cfg = program_config(config, jnp.bfloat16)
    params = shaped(jax.eval_shape(lambda: make_params(0, dsz)))
    cache = shaped(jax.eval_shape(
        lambda: _model.init_kv_cache(dec_cfg, srv["decode_slots"])
    ))
    n = srv["decode_slots"]
    budget = dsz["positions"] - srv["max_new_tokens"]
    jobs = {
        f"step slots={n}": lambda: jax.jit(
            functools.partial(_model.decode_step_slots, cfg=dec_cfg),
            donate_argnums=(1,),
        ).lower(params, cache, i32(n), i32(n), i32(n)),
    }
    # a short prompt, the width of the top-100 prompts, and the longest
    for p in sorted({min(1024, budget), min(10240, budget), budget}):
        jobs[f"prefill p={p}"] = lambda p=p: jax.jit(
            functools.partial(_model.prefill_into_slot, cfg=dec_cfg),
            donate_argnums=(3,),
        ).lower(params, i32(1, p), i32(1, p), cache, i32())
    return jobs


# ---------------------------------------------------------------- faults

@contextlib.contextmanager
def _broken_config(change: Callable[[Any], Any]) -> Iterator[None]:
    """While the server is built, the program's configuration object is
    ``change`` of the real one (the harness asks this module for it)."""
    real = globals()["program_config"]
    globals()["program_config"] = lambda config, dtype: change(real(config, dtype))
    try:
        yield
    finally:
        globals()["program_config"] = real


def latent_scale_dropped(config: dict) -> ContextManager:
    """A fault of this block, as ``pwbench.faults.Fault(program=...)`` takes
    it: c_kv is kept and read without ``mla_scale_kv_lora``'s factor."""
    return _broken_config(lambda cfg: dataclasses.replace(
        cfg, latent=dataclasses.replace(cfg.latent, kv_scale=1.0)
    ))


def zero_experts_silent(config: dict) -> ContextManager:
    """The other: an identity pick adds nothing (the router's outputs past
    the real experts are taken for experts that lie on another chip)."""
    return _broken_config(lambda cfg: dataclasses.replace(
        cfg, n_experts=cfg.n_experts + cfg.n_zero_experts, n_zero_experts=0
    ))


FAULTS = {
    "latent_scale_dropped": latent_scale_dropped,
    "zero_experts_silent": zero_experts_silent,
}


if __name__ == "__main__":
    import argparse
    import json
    import os
    import time

    t_start = time.monotonic()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from pwbench import faults, harness, spec

    ap = argparse.ArgumentParser(description="a fault of this block at a cell's size")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    a = ap.parse_args()
    # the harness asks the module that spec.family() loaded, not __main__
    fault = faults.Fault(program=getattr(spec.family("longcat_flash"), a.fault))
    result = harness.run_cell(
        Path(__file__).resolve().parents[2] / "BENCHMARK.json", a.workload,
        a.seed, a.seconds, False, t_start=t_start, fault=fault,
    )
    print(json.dumps({
        "workload": a.workload, "seed": a.seed, "fault": a.fault,
        "correct": result["correct"], "attempted": result["attempted"],
        "phases_s": result["phases_s"], "compared": result["compared"],
    }), flush=True)
    os._exit(0 if not result["correct"] else 1)
