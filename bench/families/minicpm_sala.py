"""The MiniCPM-SALA decoder family (openbmb/MiniCPM-SALA, ``model_type``
``minicpm_sala``): everything the benchmark knows of this decoder's block,
and the one file that reads its keys, under the names of its own published
``config.json``. ``pwbench/spec.py family()`` finds it by the ``family`` key
of a configuration's file.

The equations (a line marked + is an inference and is listed under
``assumed`` in the configuration's file; sources in PAPERS.md):

Common, from ``config.json``:
  x_0 = scale_emb (12) x E[token]
  every sub-block: x <- x + (scale_depth / sqrt(num_hidden_layers)) f(rms(x, g)),
      1.4 / sqrt(32): the PUBLISHED 32, whatever depth runs
  feed-forward f(u) = (silu(u W_gate) * (u W_up)) W_down, width 16,384
  logits = (rms(x_L, g_f) / (hidden_size / dim_model_base = 16)) W_head, untied
  rms_norm_eps 1e-6; no biases
+ mup_denominator (32) is an initialisation key with no part in the forward pass

``lightning-attn`` layer, h = rms(x, g1), H = lightning_nh = lightning_nkv
heads of lightning_head_dim:
  q = rmsnorm_head(h W_q), k = rmsnorm_head(h W_k) (qk_norm: over each head's
      128, + a learned scale per head dimension), v = h W_v
  lightning_use_rope: q and k turned by rotary at the logical position
      (theta 10,000, rotate-half over the head)
  state S_t = lambda_h S_{t-1} + k_t^T v_t (+ float32), o_t = (q_t / sqrt(dh)) S_t
      (lightning_scale); no softmax, no normaliser. Equivalently
      o_t = sum_{j<=t} lambda_h^(t-j) (q_t . k_j / sqrt(dh)) v_j, which is what
      the reference below computes
+ lambda_h = exp(-s_h), s_h = 2^(-8 h / H), h = 1..H (the Lightning Attention /
      TransNormer slopes; config.json has no key for the decay; no per-layer
      factor)
  use_output_norm: o'_t = rmsnorm(o_t) + over each head's 128 with a learned
      scale; use_output_gate: y_t = (o'_t * sigmoid(h_t W_g)) W_o

``minicpm4`` layer, num_attention_heads query heads over num_key_value_heads
of head_dim (groups of 16), attn_use_rope false: no positions at all:
  q, k as above with qk_norm, no rotary; v = h W_v
  attn_use_output_gate: y_t = (a_t * sigmoid(h_t W_g)) W_o
  a row of n <= dense_len tokens:
      a_t = softmax_{j<=t}(q_t . k_j / sqrt(dh)) v_j
  longer rows (InfLLM v2): pooled keys Kbar_i = mean(k_{16 i} .. k_{16 i + 31})
      a key head (kernel 32, stride 16), visible to query t when
      16 i + 31 <= t; relevance r_{t,i} = sum over the group's 16 heads of
      softmax_i(q_t . Kbar_i / sqrt(dh)) over the visible i; the score of block
      b (tokens 64 b .. 64 b + 63) is the max of r_{t,i} over the pooled
      windows that overlap it (i = 4 b - 1 .. 4 b + 3); the set B_t is block 0
      (init_blocks 1), the 32 blocks that hold the last window_size 2,048
      tokens (through t's own), and the highest-scoring others until
      |B_t| = topk 64; a_t = softmax over {j <= t, block(j) in B_t}, one B_t
      for all 16 heads of a group. All positions are logical (counted from a
      row's first real token)
+ kernel_size 32, kernel_stride 16, block_size 64, init_blocks 1, window_size
      2,048, dense_len 8,192 are the MiniCPM4 family's published
      ``sparse_config`` (openbmb/MiniCPM4-8B config.json, arXiv:2506.07900);
      this model's config.json confirms topk 64 only

What a family's file gives (bench/README.md, "Adding things"): ``sizes``,
``program_config``, ``make_params``, ``decoder_logits``, ``n_block``,
``token_flops``, ``prefill_flops``, ``decode_step_bytes``,
``decode_step_flops``, ``n_params``, ``STEP`` and ``compile_jobs``. Beside
them the two kernels' counts for their readers (``linear_scan_seconds``,
``sparse_prefill_flops``) and the two faults of this block that bench/tests
plants: ``selection_reads_every_block`` and ``state_not_decayed``.

By hand on the chip, at a cell's own size (bench/control.py ``--fault``
names only the faults of pwbench/faults.py): a short window of the cell's
load with one of the family's faults planted; one line, exit 0 when not
correct.

    python3 bench/families/minicpm_sala.py --workload <cell> --seed 11 \\
        --seconds 12 --fault selection_reads_every_block
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

_KINDS = {"minicpm4": "sparse", "lightning-attn": "linear"}
_SPARSE_KEYS = (
    "topk", "block_size", "kernel_size", "kernel_stride", "init_blocks",
    "window_size", "dense_len",
)


def sizes(config: dict) -> dict:
    """The decoder's sizes, from the top level of a configuration's file.
    ``family``, ``vocab``, ``positions``, ``layers`` and ``tag`` (the
    seed's key tag) are what the harness reads; the rest is this file's."""
    layers = config["num_hidden_layers"]
    # mixer_types lists the published model's layers: the first `layers` run
    kinds = tuple(_KINDS[m] for m in config["mixer_types"][:layers])
    if len(kinds) != layers:
        raise ValueError("mixer_types lists every layer")
    if config["tie_word_embeddings"] or config["attention_bias"]:
        raise ValueError("this family's head is its own matrix, and no bias")
    if config["hidden_act"] != "silu" or config["attn_use_rope"]:
        raise ValueError("this family: SwiGLU, no positions in minicpm4 layers")
    if config["lightning_head_dim"] != config["head_dim"]:
        raise ValueError("the two mixers' heads are as wide")
    if config["lightning_nh"] != config["lightning_nkv"]:
        raise ValueError("a lightning layer has a key head for every head")
    # (the program has one switch for both mixers' output gates)
    for key in ("qk_norm", "use_output_gate", "use_output_norm",
                "attn_use_output_gate", "lightning_use_rope"):
        if not config[key]:
            raise ValueError(f"this family is written for {key} true")
    sparse = config["sparse_config"]
    return dict(
        family="minicpm_sala", tag=4,
        vocab=config["vocab_size"], d=config["hidden_size"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], dh=config["head_dim"],
        lin_heads=config["lightning_nh"],
        layers=layers, ff=config["intermediate_size"], kinds=kinds,
        theta=float(config["rope_theta"]),
        positions=config["max_position_embeddings"],
        scale_emb=float(config["scale_emb"]),
        # the published depth, not the depth that runs
        branch=float(config["scale_depth"]) / math.sqrt(
            config.get("published", {}).get("num_hidden_layers", layers)
        ),
        logit_div=config["hidden_size"] / config["dim_model_base"],
        sparse=tuple(int(sparse[k]) for k in _SPARSE_KEYS),
    )


def _sparse(sz: dict) -> dict:
    return dict(zip(_SPARSE_KEYS, sz["sparse"]))


def slopes(sz: dict) -> tuple[float, ...]:
    """s_h = 2^(-8 h / H), h = 1..H: head h's state decays by exp(-s_h)."""
    n = sz["lin_heads"]
    return tuple(2.0 ** (-8.0 * h / n) for h in range(1, n + 1))


def program_config(config: dict, dtype: Any) -> Any:
    """The program's configuration object, as ``JaxLMChat(config=...)``
    takes it: the per-layer list written out from ``mixer_types``."""
    sz = sizes(config)
    sp = _sparse(sz)
    return _program.TransformerConfig(
        causal=True, pool="last", dtype=dtype,
        vocab_size=sz["vocab"], d_model=sz["d"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], head_size=sz["dh"], n_layers=sz["layers"],
        d_ff=sz["ff"], rope_theta=sz["theta"], max_len=sz["positions"],
        tie_embeddings=False,
        layers=tuple(
            _program.LayerSpec(
                pos="rotary" if kind == "linear" else "none", ff="swiglu",
                mixer=kind,
            )
            for kind in sz["kinds"]
        ),
        sparse=_model.SparseSpec(
            topk=sp["topk"], block=sp["block_size"], kernel=sp["kernel_size"],
            stride=sp["kernel_stride"], init_blocks=sp["init_blocks"],
            window=sp["window_size"], dense_len=sp["dense_len"],
        ),
        qk_norm=True, out_gate=True, linear_out_norm=True,
        linear_heads=sz["lin_heads"], linear_slopes=slopes(sz),
        embed_scale=sz["scale_emb"], residual_scale=sz["branch"],
        logit_scale=1.0 / sz["logit_div"],
    )


# --------------------------------------------------------------- weights
# Drawn from the seed, bfloat16; norm scales 1 + 0.1 normal. Matrices are
# normal / sqrt(fan_in) but for three scales, chosen so that the random
# stack is conditioned as a trained one is (``assumed`` in the
# configuration's file; PERF.md, PR 38, has what each was read against):
#
# * the embedding has spread 1 / scale_emb, so that the residual stream
#   starts at unit spread behind the published x 12;
# * the head has spread (hidden_size / dim_model_base) / sqrt(d), so that the
#   logits have unit spread behind the published / 16 (a trained head has
#   grown to make up for it; at 1 / sqrt(d) every logit gap is a sixteenth);
# * the q and k norm scales of the minicpm4 layers are 1.8 + 0.1 normal:
#   scores of spread about 3.2, attention peaked on a few keys, so which
#   blocks a query may read decides what the layer returns, and a layer that
#   reads every block is another function. (A lightning layer's output is
#   normed, so its q and k scales cancel: they stay at 1.)
# The program's tree and the reference both come from these two functions;
# the reference draws its leaves again, a layer at a time, and never reads
# the program's copy.

_QK_NORM_CENTRE = 1.8


def _block_leaves(key: Any, layer: Any, sz: dict, kind: str) -> dict:
    """The leaves of block ``layer`` (a traced or a Python integer) of
    ``kind``, the mixer's projections apart."""
    import jax

    d, f, dh = sz["d"], sz["ff"], sz["dh"]
    linear = kind == "linear"
    h = sz["lin_heads"] if linear else sz["heads"]
    hk = sz["lin_heads"] if linear else sz["kv_heads"]
    k = jax.random.fold_in(key, 1000 + layer)
    s = 1.0 / math.sqrt(d)
    centre = 1.0 if linear else _QK_NORM_CENTRE
    leaves = {
        "q": weights.leaf(k, 0, (d, h * dh), s, 0.0),
        "k": weights.leaf(k, 1, (d, hk * dh), s, 0.0),
        "v": weights.leaf(k, 2, (d, hk * dh), s, 0.0),
        "o": weights.leaf(k, 3, (h * dh, d), 1.0 / math.sqrt(h * dh), 0.0),
        "gate": weights.leaf(k, 4, (d, h * dh), s, 0.0),
        "ff_gate": weights.leaf(k, 5, (d, f), s, 0.0),
        "ff_up": weights.leaf(k, 6, (d, f), s, 0.0),
        "ff_out": weights.leaf(k, 7, (f, d), 1.0 / math.sqrt(f), 0.0),
        "ln1_scale": weights.leaf(k, 8, (d,), 0.1, 1.0),
        "ln2_scale": weights.leaf(k, 9, (d,), 0.1, 1.0),
        "q_norm": weights.leaf(k, 10, (dh,), 0.1, centre),
        "k_norm": weights.leaf(k, 11, (dh,), 0.1, centre),
    }
    if linear:
        leaves["o_norm"] = weights.leaf(k, 12, (dh,), 0.1, 1.0)
    return leaves


def _top_leaves(key: Any, sz: dict) -> dict:
    d = sz["d"]
    return {
        "tok_embed": weights.leaf(key, 0, (sz["vocab"], d), 1.0 / sz["scale_emb"], 0.0),
        "ln_f_scale": weights.leaf(key, 2, (d,), 0.1, 1.0),
        "lm_head": weights.leaf(
            key, 3, (d, sz["vocab"]), sz["logit_div"] / math.sqrt(d), 0.0
        ),
    }


def _tree(kd: Any, sz_items: tuple) -> dict:
    import jax
    import jax.numpy as jnp

    sz = dict(sz_items)
    key = jax.random.wrap_key_data(kd)
    params = _top_leaves(key, sz)
    params["blocks"] = []
    for i, kind in enumerate(sz["kinds"]):
        w = _block_leaves(key, i, sz, kind)
        # the program multiplies by the three projections side by side
        w["qkv"] = jnp.concatenate([w.pop("q"), w.pop("k"), w.pop("v")], axis=1)
        params["blocks"].append(w)
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
# float32 at ``highest`` precision, no cache, no state, no chunks, no
# batching: a row at a time through a layer at a time (a layer's weights are
# 1.1 GB in float32 at the published widths), the mixers a block of queries
# at a time against every key so that the score arrays fit at 24,576
# tokens, the feed-forward a block of rows at a time. It shares nothing
# with the program's mixers: the linear layer is the sum over j <= t written
# out, the pooled keys are gathered window by window, the chosen blocks come
# from a ranking. Rows are padded on the RIGHT; position is index.

_QUERY_BLOCK = 128
_ROW_BLOCK = 4096


def _rope(x: Any, theta: float) -> Any:
    """Rotary positions 0..s-1, rotate-half over the head: x [s, heads, dh]."""
    import jax.numpy as jnp

    s, _h, dh = x.shape
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
        axis=-1,
    )


def _query_blocks(s: int, fn: Callable, *arrays: Any) -> Any:
    """``fn(first position, block of each array)`` over blocks of
    ``_QUERY_BLOCK`` queries, put together again: arrays [s, ...]."""
    import jax
    import jax.numpy as jnp

    blk = min(_QUERY_BLOCK, s)
    n = -(-s // blk)
    padded = [
        jnp.pad(a, ((0, n * blk - s),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (n, blk) + a.shape[1:]
        )
        for a in arrays
    ]
    out = jax.lax.map(lambda xs: fn(xs[0] * blk, *xs[1:]), (jnp.arange(n), *padded))
    return out.reshape((n * blk,) + out.shape[2:])[:s]


def _lightning(q: Any, k: Any, v: Any, rates: Any, fp8: bool) -> Any:
    """o_t = sum_{j<=t} lambda^(t-j) (q_t . k_j / sqrt(dh)) v_j, every head:
    q, k, v [s, heads, dh], rates [heads] -> [s, heads, dh]."""
    import jax
    import jax.numpy as jnp

    s, _h, dh = q.shape
    kq, vq = reference.quant(k, fp8), reference.quant(v, fp8)
    kp = jnp.arange(s)[None, :]

    def one(q0, qb):
        ago = (q0 + jnp.arange(qb.shape[0])[:, None] - kp).astype(jnp.float32)
        weight = jnp.where(
            ago >= 0, jnp.exp(-rates[:, None, None] * jnp.maximum(ago, 0.0)), 0.0
        )  # [heads, q, s]
        pairs = jnp.einsum(
            "qhd,shd->hqs", reference.quant(qb, fp8), kq,
            precision=jax.lax.Precision.HIGHEST,
        ) / math.sqrt(dh)
        return jnp.einsum(
            "hqs,shd->qhd", reference.quant(pairs * weight, fp8), vq,
            precision=jax.lax.Precision.HIGHEST,
        )

    return _query_blocks(s, one, q)


def _pooled_windows(sp: dict, s: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """For s positions: the positions of each pooled window [n_pool, kernel]
    (windows that lie whole inside s), and for each block the first and the
    last pooled window that overlaps it [n blocks]."""
    ker, stride, blk = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    n_pool = max((s - ker) // stride + 1, 0)
    at = stride * np.arange(n_pool)[:, None] + np.arange(ker)[None, :]
    b0 = blk * np.arange(-(-s // blk))
    lo = -((ker - 1 - b0) // stride)  # least i with stride i + ker - 1 >= b0
    hi = (b0 + blk - 1) // stride  # greatest i with stride i <= b0 + blk - 1
    return at, np.maximum(lo, 0), hi


def _chosen_blocks(q: Any, pooled: Any, t: Any, dense: Any, sp: dict,
                   lo: np.ndarray, hi: np.ndarray, fp8: bool) -> Any:
    """B_t of a block of queries: q [nq, kv heads, group, dh] at positions t
    [nq], pooled [n_pool, kv heads, dh] -> [kv heads, nq, n blocks] bool."""
    import jax
    import jax.numpy as jnp

    ker, stride, blk = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    n_pool, nb = pooled.shape[0], len(lo)
    own = t // blk
    at_or_before = jnp.arange(nb)[None, :] <= own[:, None]  # [nq, nb]
    forced = (jnp.arange(nb)[None, :] < sp["init_blocks"]) | (
        jnp.arange(nb)[None, :] > own[:, None] - sp["window_size"] // blk
    )
    forced = forced & at_or_before
    if n_pool == 0:
        return jnp.broadcast_to(at_or_before[None], (q.shape[1],) + at_or_before.shape)
    seen = (stride * jnp.arange(n_pool) + ker - 1)[None, :] <= t[:, None]  # [nq, n_pool]
    scores = jnp.einsum(
        "qkgd,ikd->kgqi", reference.quant(q, fp8), reference.quant(pooled, fp8),
        precision=jax.lax.Precision.HIGHEST,
    ) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    relevance = jnp.sum(jnp.where(seen, probs, 0.0), axis=1)  # [kv heads, nq, n_pool]
    relevance = jnp.where(seen, relevance, -jnp.inf)
    # a block's score: the best of the pooled windows that overlap it
    score = jnp.full(relevance.shape[:2] + (nb,), -jnp.inf)
    for off in range(int((hi - lo).max()) + 1):
        i = lo + off
        inside = (i <= hi) & (i < n_pool)
        col = relevance[:, :, np.clip(i, 0, n_pool - 1)]
        score = jnp.maximum(score, jnp.where(jnp.asarray(inside), col, -jnp.inf))
    # the others, best first, until the set holds topk blocks
    free = at_or_before & ~forced
    room = sp["topk"] - jnp.sum(forced, axis=1)  # [nq]
    ranked = jnp.where(free, score, -jnp.inf)
    order = jnp.argsort(-ranked, axis=-1)  # stable: ties go to the lower block
    rank = jnp.argsort(order, axis=-1)
    chosen = forced | (free & (rank < room[None, :, None]))
    return jnp.where(dense, at_or_before[None], chosen)


def _minicpm4(q: Any, k: Any, v: Any, n: Any, sp: dict, fp8: bool) -> Any:
    """q [s, kv heads, group, dh], k and v [s, kv heads, dh], n the row's
    real tokens (the rest is padding behind them) -> [s, heads * dh]."""
    import jax
    import jax.numpy as jnp

    s, hk, g, dh = q.shape
    at, lo, hi = _pooled_windows(sp, s)
    pooled = jnp.mean(k[at], axis=1) if len(at) else jnp.zeros((0, hk, dh))
    kq, vq = reference.quant(k, fp8), reference.quant(v, fp8)
    kp = jnp.arange(s)[None, :]
    of_key = kp[0] // sp["block_size"]
    dense = n <= sp["dense_len"]

    def one(q0, qb):
        t = q0 + jnp.arange(qb.shape[0])
        blocks = _chosen_blocks(qb, pooled, t, dense, sp, lo, hi, fp8)
        ok = (kp <= t[:, None])[None] & blocks[:, :, of_key]  # [kv heads, nq, s]
        scores = jnp.einsum(
            "qkgd,skd->kgqs", reference.quant(qb, fp8), kq,
            precision=jax.lax.Precision.HIGHEST,
        ) / math.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(ok[:, None], scores, -1e30), axis=-1)
        return jnp.einsum(
            "kgqs,skd->qkgd", reference.quant(probs, fp8), vq,
            precision=jax.lax.Precision.HIGHEST,
        )

    return _query_blocks(s, one, q).reshape(s, hk * g * dh)


def _feed_forward(u: Any, w: dict, fp8: bool) -> Any:
    import jax
    import jax.numpy as jnp

    s, d = u.shape
    blk = min(_ROW_BLOCK, s)
    n = -(-s // blk)

    def one(rows):
        hidden = jax.nn.silu(reference.mm(rows, w["ff_gate"], fp8)) * (
            reference.mm(rows, w["ff_up"], fp8)
        )
        return reference.mm(hidden, w["ff_out"], fp8)

    rows = jnp.pad(u, ((0, n * blk - s), (0, 0))).reshape(n, blk, d)
    return jax.lax.map(one, rows).reshape(n * blk, d)[:s]


def _layer_one(x: Any, n: Any, w: dict, sz: dict, kind: str, fp8: bool) -> Any:
    """One sequence [s, d] of n real tokens through one layer."""
    import jax
    import jax.numpy as jnp

    s, _d = x.shape
    dh = sz["dh"]
    linear = kind == "linear"
    h = sz["lin_heads"] if linear else sz["heads"]
    hk = sz["lin_heads"] if linear else sz["kv_heads"]
    hline = reference.rms(x, w["ln1_scale"])
    # qk_norm: RMS over each head's width, one [dh] scale for all heads
    q = reference.rms(
        reference.mm(hline, w["q"], fp8).reshape(s, h, dh), w["q_norm"]
    )
    k = reference.rms(
        reference.mm(hline, w["k"], fp8).reshape(s, hk, dh), w["k_norm"]
    )
    v = reference.mm(hline, w["v"], fp8).reshape(s, hk, dh)
    if linear:
        q, k = _rope(q, sz["theta"]), _rope(k, sz["theta"])
        rates = jnp.asarray(slopes(sz), jnp.float32)
        out = reference.rms(_lightning(q, k, v, rates, fp8), w["o_norm"])
        out = out.reshape(s, h * dh)
    else:
        out = _minicpm4(q.reshape(s, hk, h // hk, dh), k, v, n, _sparse(sz), fp8)
    out = out * jax.nn.sigmoid(reference.mm(hline, w["gate"], fp8))
    x = x + sz["branch"] * reference.mm(out, w["o"], fp8)
    u = reference.rms(x, w["ln2_scale"])
    return x + sz["branch"] * _feed_forward(u, w, fp8)


@functools.lru_cache(maxsize=None)
def _layer_fn(sz_items: tuple, kind: str, fp8: bool):
    """jit of: draw layer ``li``'s weights, run one row through it."""
    import jax
    import jax.numpy as jnp

    sz = dict(sz_items)

    def fn(kd, li, x, n):
        key = jax.random.wrap_key_data(kd)
        w = {
            k: v.astype(jnp.float32)
            for k, v in _block_leaves(key, li, sz, kind).items()
        }
        return _layer_one(x, n, w, sz, kind, fp8)

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
    drawn again from the seed, layer by layer, a row at a time. Rows are
    padded on the right to one width (the longest row's, rounded up to 512,
    at most ``width``), so that one program serves every seed of a cell.
    ``fp8`` is the control (reference.py): both operands of every matrix
    product rounded to fp8, the selection's scores too."""
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
        x = sz["scale_emb"] * top["tok_embed"][jnp.asarray(ids)]
        n = jnp.asarray(len(r), jnp.int32)
        for li, kind in enumerate(sz["kinds"]):
            x = _layer_fn(items, kind, fp8)(kd, jnp.asarray(li, jnp.int32), x, n)
        hline = reference.rms(x[where.start:where.stop, :], top["ln_f_scale"])
        lg = reference.mm(hline / sz["logit_div"], top["lm_head"], fp8)
        logits.append(np.asarray(jax.device_get(lg), np.float32))
    return logits


# ---------------------------------------------------------------- counts
# Operations and bytes the algorithm needs, from shapes alone.
#
# * one token through the blocks multiplies with a lightning layer's q, k,
#   v, gate and o (5 d x H dh) or a minicpm4 layer's q, gate, o (3 d x heads
#   dh) and k, v (2 d x kv heads dh), and the SwiGLU's three matrices (3 d
#   ff): n_block counts those, token_flops is twice it.
# * a prefill of p real tokens adds, a minicpm4 layer, 4 heads dh a causal
#   pair that the query's blocks hold (every earlier key up to dense_len;
#   past it min(blocks at or before t, topk) blocks, less the part of t's
#   own block behind t) and 2 heads dh a pooled key the query can see; a
#   lightning layer, 4 H dh^2 a token (q S and k^T v); and the last
#   position's logits over the untied head.
# * a decode step with the contexts c of the occupied rows reads the
#   matrices and the head once; a minicpm4 layer, the rows of the blocks
#   query c chose and the pooled keys it sees; a lightning layer, its state
#   read and written (2 x H dh^2 x 4 bytes a row); and writes the new rows.

def _layer_counts(sz: dict) -> tuple[int, int]:
    return sz["kinds"].count("sparse"), sz["kinds"].count("linear")


def _mixer_matrix_elements(sz: dict, kind: str) -> int:
    d, dh = sz["d"], sz["dh"]
    if kind == "linear":
        return 5 * d * sz["lin_heads"] * dh
    return 3 * d * sz["heads"] * dh + 2 * d * sz["kv_heads"] * dh


def n_block(sz: dict) -> int:
    return sum(
        _mixer_matrix_elements(sz, kind) + 3 * sz["d"] * sz["ff"]
        for kind in sz["kinds"]
    )


def token_flops(sz: dict) -> int:
    """2 x the parameters of the blocks' matrices: one token through them."""
    return 2 * n_block(sz)


def chosen_keys(sz: dict, t: np.ndarray, n: int) -> np.ndarray:
    """Keys that the query at position t of a row of n tokens attends."""
    sp = _sparse(sz)
    if n <= sp["dense_len"]:
        return t + 1
    blk = sp["block_size"]
    blocks = np.minimum(t // blk + 1, sp["topk"])
    return blocks * blk - (blk - 1 - t % blk)


def pooled_seen(sz: dict, t: np.ndarray, n: int) -> np.ndarray:
    """Pooled keys that the query at position t of a row of n tokens scores."""
    sp = _sparse(sz)
    if n <= sp["dense_len"]:
        return 0 * t
    return np.maximum((t - (sp["kernel_size"] - 1)) // sp["kernel_stride"] + 1, 0)


def sparse_prefill_flops(sz: dict, p: int, blocks_read: float | None = None) -> float:
    """One minicpm4 layer's attention over a prompt of p tokens: 4 heads dh a
    pair attended and 2 heads dh a pooled key scored. ``blocks_read`` (the
    program's own count for this layer and prompt, summed over the key
    heads) puts the blocks really chosen in the formula's place."""
    t = np.arange(p)
    hd = sz["heads"] * sz["dh"]
    pairs = float(chosen_keys(sz, t, p).sum())
    if blocks_read is not None and p > _sparse(sz)["dense_len"]:
        blk = _sparse(sz)["block_size"]
        pairs = blocks_read / sz["kv_heads"] * blk - float((blk - 1 - t % blk).sum())
    return 4 * hd * pairs + 2 * hd * float(pooled_seen(sz, t, p).sum())


def linear_scan_seconds(sz: dict, tokens: float, layer_prefills: float,
                        peaks: dict) -> float:
    """The roofline time of the lightning layers' scans over ``tokens``
    (real tokens x layers) in ``layer_prefills`` (prefills x layers): the
    larger of 4 H dh^2 a token at the bf16 peak and, at the HBM bandwidth,
    q, k, v and o of every token (bf16) and one float32 state a layer."""
    hd = sz["lin_heads"] * sz["dh"]
    flops = 4 * hd * sz["dh"] * tokens
    moved = 4 * hd * 2 * tokens + layer_prefills * hd * sz["dh"] * 4
    return max(
        flops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"]
    )


def prefill_flops(sz: dict, p: int) -> float:
    n_sparse, n_linear = _layer_counts(sz)
    return (
        token_flops(sz) * p + n_sparse * sparse_prefill_flops(sz, p)
        + n_linear * 4 * sz["lin_heads"] * sz["dh"] ** 2 * p
        + 2 * sz["d"] * sz["vocab"]
    )


def _row_bytes(sz: dict) -> int:
    """A position's key and value in one minicpm4 layer, bf16."""
    return 2 * sz["kv_heads"] * sz["dh"] * 2


def _state_bytes(sz: dict) -> int:
    return sz["lin_heads"] * sz["dh"] ** 2 * 4


def decode_step_bytes(sz: dict, contexts: list[float]) -> float:
    n_sparse, n_linear = _layer_counts(sz)
    once = 2 * (n_block(sz) + sz["d"] * sz["vocab"])
    c = np.asarray(contexts, np.int64)
    rows = sum(
        float(chosen_keys(sz, np.asarray([t]), int(t) + 1)[0]) for t in c
    ) * _row_bytes(sz)
    pooled = sum(
        float(pooled_seen(sz, np.asarray([t]), int(t) + 1)[0]) for t in c
    ) * sz["kv_heads"] * sz["dh"] * 2
    states = len(contexts) * 2 * _state_bytes(sz)
    new = len(contexts) * _row_bytes(sz)
    return once + n_sparse * (rows + pooled + new) + n_linear * states


def decode_step_flops(sz: dict, contexts: list[float]) -> float:
    n_sparse, n_linear = _layer_counts(sz)
    hd = sz["heads"] * sz["dh"]
    per_row = (
        token_flops(sz) + 2 * sz["d"] * sz["vocab"]
        + n_linear * 4 * sz["lin_heads"] * sz["dh"] ** 2
    )
    total = 0.0
    for t in contexts:
        at = np.asarray([int(t)])
        total += per_row + n_sparse * (
            4 * hd * float(chosen_keys(sz, at, int(t) + 1)[0])
            + 2 * hd * float(pooled_seen(sz, at, int(t) + 1)[0])
        )
    return total


def n_params(sz: dict, *, embedding: bool) -> int:
    """Parameters of the blocks, with or without the embedding and the head."""
    d, dh = sz["d"], sz["dh"]
    n = d
    for kind in sz["kinds"]:
        n += _mixer_matrix_elements(sz, kind) + 3 * d * sz["ff"] + 2 * d + 2 * dh
        n += dh if kind == "linear" else 0
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
    # a short prompt (under dense_len), the width of the top-240 prompts,
    # and the longest
    for p in sorted({min(1024, budget), min(24576, budget), budget}):
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


def selection_reads_every_block(config: dict) -> ContextManager:
    """A fault of this block, as ``pwbench.faults.Fault(program=...)`` takes
    it: every minicpm4 layer attends every earlier key, whatever the row's
    length (``dense_len`` past every position)."""
    return _broken_config(lambda cfg: dataclasses.replace(
        cfg, sparse=dataclasses.replace(cfg.sparse, dense_len=cfg.max_len)
    ))


def state_not_decayed(config: dict) -> ContextManager:
    """The other: a lightning layer's state is never decayed (lambda = 1)."""
    return _broken_config(lambda cfg: dataclasses.replace(
        cfg, linear_slopes=(0.0,) * len(cfg.linear_slopes)
    ))


FAULTS = {
    "selection_reads_every_block": selection_reads_every_block,
    "state_not_decayed": state_not_decayed,
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
    fault = faults.Fault(program=getattr(spec.family("minicpm_sala"), a.fault))
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
