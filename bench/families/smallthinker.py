"""The SmallThinker decoder family (PowerInfer/SmallThinker-21BA3B-Instruct):
everything the benchmark knows of this decoder's block, and the one file
that reads its keys, under the names of its own published ``config.json``.
``pwbench/spec.py family()`` finds it by the ``family`` key of a
configuration's file.

The layer, for input x [T, d] of layer l (``assumed`` in the configuration
says which lines are inferences):
  r   = x W_r                     router, on the layer's INPUT, float32
  S   = top-k indices of r ;  w = softmax over those k logits
  h   = rms(x, g1) ; q = h W_q (heads x dh), k = h W_k, v = h W_v (kv heads
        x dh): heads / kv heads query heads share a key/value head; no bias
  rope_layout[l] = 1: q, k turned by rotary (theta, rotate-half over dh)
  a_i = softmax_j(q_i k_j / sqrt(dh)) v_j over j <= i, and where
        sliding_window_layout[l] = 1 over i - window < j <= i only
  x'  = x + a W_o
  u   = rms(x', g2) ; y = sum_{e in S} w_e (relu(u Wg_e) * (u Wu_e)) Wd_e
  out = x' + y
  logits = rms(x_L, g_f) W_head   (its own matrix; the embedding is untied)

What a family's file gives (bench/README.md, "Adding things"): ``sizes``,
``program_config``, ``make_params``, ``decoder_logits``, ``n_block``,
``token_flops``, ``prefill_flops``, ``decode_step_bytes``,
``decode_step_flops``, ``n_params``, ``STEP`` and ``compile_jobs``. Beside
them ``expert_matrix_elements`` for the readers of the expert layer, and
``window_reads_every_row``, the fault of this block that bench/tests plants.

By hand on the chip, at a cell's own size (bench/control.py ``--fault``
names only the faults of pwbench/faults.py): a short window of the cell's
load with the family's fault planted; one line, exit 0 when not correct.

    python3 bench/families/smallthinker.py --workload <cell> --seed 11 --seconds 12
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import sys
from pathlib import Path
from typing import Any, Callable, Iterator

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
    seed's key tag) are what the harness reads; the rest is this file's."""
    layers = config["num_hidden_layers"]
    # the layouts list the published model's layers: the first `layers` run
    rope = tuple(int(v) for v in config["rope_layout"][:layers])
    window = tuple(int(v) for v in config["sliding_window_layout"][:layers])
    if len(rope) != layers or len(window) != layers:
        raise ValueError("rope_layout and sliding_window_layout list every layer")
    if config["tie_word_embeddings"]:
        raise ValueError("this family's head is its own matrix")
    return dict(
        family="smallthinker", tag=3,
        vocab=config["vocab_size"], d=config["hidden_size"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], dh=config["head_dim"],
        layers=layers, ff=config["moe_ffn_hidden_size"],
        experts=config["moe_num_primary_experts"],
        active=config["moe_num_active_primary_experts"],
        theta=float(config["rope_theta"]), window=config["sliding_window_size"],
        rope_layout=rope, window_layout=window,
        positions=config["max_position_embeddings"],
    )


def program_config(config: dict, dtype: Any) -> Any:
    """The program's configuration object, as ``JaxLMChat(config=...)``
    takes it: the per-layer list written out from the two layouts."""
    sz = sizes(config)
    return _program.TransformerConfig(
        causal=True, pool="last", dtype=dtype,
        vocab_size=sz["vocab"], d_model=sz["d"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], head_size=sz["dh"], n_layers=sz["layers"],
        d_ff=sz["ff"], n_experts=sz["experts"], n_active=sz["active"],
        rope_theta=sz["theta"], max_len=sz["positions"], tie_embeddings=False,
        layers=tuple(
            _program.LayerSpec(
                window=sz["window"] if windowed else None,
                pos="rotary" if rotary else "none", ff="experts",
            )
            for rotary, windowed in zip(sz["rope_layout"], sz["window_layout"])
        ),
    )


# --------------------------------------------------------------- weights
# Drawn from the seed, bfloat16; norm scales 1 + 0.1 normal. Matrices are
# normal / sqrt(fan_in) but for three scales, chosen so that the random
# stack is conditioned as a trained one is (``assumed`` in the
# configuration's file; PERF.md, PR 30, has what each was read against):
#
# * the embedding has unit spread, so the residual the router reads is a
#   token's own vector from the first layer on. At 0.02 the first layer's
#   output buried it, the residual's common part grew with depth and by the
#   last layers half of all tokens chose one expert (the fullest expert at
#   5.3 times the mean);
# * q and k are twice as wide (scores of spread 4): attention is peaked on a
#   few keys, so which keys a layer may read (its window) decides what it
#   returns, and a window layer that reads every row is another function;
# * expert_down is 0.3 as wide: the experts move a token's vector by about a
#   tenth a layer. With 64 near-tied logits bfloat16 swaps the sixth expert
#   for the seventh in a few percent of tokens a layer; such a swap then
#   moves the token by a few percent, where at full width it moved it by
#   half and the largest logit gap of a sound run could not be told from
#   the fp8 control's.
# The program's tree and the reference both come from these two functions;
# the reference draws its leaves again, a layer at a time, and never reads
# the program's copy.

_EMBED_SPREAD = 1.0
_QK_GAIN = 2.0
_DOWN_GAIN = 0.3


def _block_leaves(key: Any, layer: Any, sz: dict) -> dict:
    """The leaves of block ``layer`` (a traced or a Python integer), the
    attention's three projections apart."""
    import jax

    d, f, e, dh = sz["d"], sz["ff"], sz["experts"], sz["dh"]
    k = jax.random.fold_in(key, 1000 + layer)
    s = 1.0 / math.sqrt(d)
    return {
        "q": weights.leaf(k, 0, (d, sz["heads"] * dh), _QK_GAIN * s, 0.0),
        "k": weights.leaf(k, 1, (d, sz["kv_heads"] * dh), _QK_GAIN * s, 0.0),
        "v": weights.leaf(k, 2, (d, sz["kv_heads"] * dh), s, 0.0),
        "o": weights.leaf(
            k, 3, (sz["heads"] * dh, d), 1.0 / math.sqrt(sz["heads"] * dh), 0.0
        ),
        "router": weights.leaf(k, 4, (d, e), s, 0.0),
        "expert_gate": weights.leaf(k, 5, (e, d, f), s, 0.0),
        "expert_up": weights.leaf(k, 6, (e, d, f), s, 0.0),
        "expert_down": weights.leaf(
            k, 7, (e, f, d), _DOWN_GAIN / math.sqrt(f), 0.0
        ),
        "ln1_scale": weights.leaf(k, 8, (d,), 0.1, 1.0),
        "ln2_scale": weights.leaf(k, 9, (d,), 0.1, 1.0),
    }


def _top_leaves(key: Any, sz: dict) -> dict:
    d = sz["d"]
    return {
        "tok_embed": weights.leaf(key, 0, (sz["vocab"], d), _EMBED_SPREAD, 0.0),
        "ln_f_scale": weights.leaf(key, 2, (d,), 0.1, 1.0),
        "lm_head": weights.leaf(key, 3, (d, sz["vocab"]), 1.0 / math.sqrt(d), 0.0),
    }


def _tree(kd: Any, sz_items: tuple) -> dict:
    import jax
    import jax.numpy as jnp

    sz = dict(sz_items)
    key = jax.random.wrap_key_data(kd)
    params = _top_leaves(key, sz)
    params["blocks"] = []
    for i in range(sz["layers"]):
        w = _block_leaves(key, i, sz)
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
# float32 at ``highest`` precision, no cache, no ring, no batching. One
# layer's weights at a time (1.6 GB in float32 at the published widths),
# every row through it one after another, attention a block of queries at a
# time against every key, so that the score array fits. Each expert gathers
# its own tokens, however many they are (``_experts``).

_QUERY_BLOCK = 256
_PAIR_CHUNK = 512


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


def _attention(q: Any, k: Any, v: Any, mask: Any, window: int | None,
               fp8: bool) -> Any:
    """q [s, kv heads, group, dh], k and v [s, kv heads, dh], mask [s] of
    the valid keys -> [s, heads * dh]."""
    import jax
    import jax.numpy as jnp

    s, hk, g, dh = q.shape
    blk = min(_QUERY_BLOCK, s)
    n = -(-s // blk)
    qpad = jnp.pad(q, ((0, n * blk - s), (0, 0), (0, 0), (0, 0)))
    kq, vq = reference.quant(k, fp8), reference.quant(v, fp8)
    kp = jnp.arange(s)[None, :]

    def one(i_qb):
        i, qb = i_qb
        qp = i * blk + jnp.arange(blk)[:, None]
        ok = (kp <= qp) & mask[None, :]
        if window is not None:
            ok = ok & (kp > qp - window)
        scores = jnp.einsum(
            "qkgd,skd->kgqs", reference.quant(qb, fp8), kq,
            precision=jax.lax.Precision.HIGHEST,
        ) / math.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(ok[None, None], scores, -1e30), axis=-1)
        return jnp.einsum(
            "kgqs,skd->qkgd", reference.quant(probs, fp8), vq,
            precision=jax.lax.Precision.HIGHEST,
        )

    ctx = jax.lax.map(one, (jnp.arange(n), qpad.reshape(n, blk, hk, g, dh)))
    return ctx.reshape(n * blk, hk * g * dh)[:s]


def _experts(u: Any, idx: Any, wts: Any, w: dict, fp8: bool) -> Any:
    """sum over a token's chosen experts of weight x ReGLU expert: u [s, d],
    idx and wts [s, k]. The token-expert pairs, sorted by expert, go through
    in chunks of ``_PAIR_CHUNK`` rows; a chunk is multiplied by each expert
    that has rows in it (its other rows zeroed), so every expert sees all
    its tokens however many they are, and none sees the tokens of another."""
    import jax
    import jax.numpy as jnp

    s, d = u.shape
    k, e_n = idx.shape[1], w["expert_gate"].shape[0]
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)  # the pairs, by expert
    n = -(-order.size // _PAIR_CHUNK)
    pad = n * _PAIR_CHUNK - order.size
    expert = jnp.pad(flat[order], (0, pad), constant_values=e_n)  # e_n: nobody
    rows = jnp.pad(u[order // k], ((0, pad), (0, 0)))

    def chunk(ex):
        ex_c, x = ex

        def one(e, acc):
            mine = (ex_c == e)[:, None]
            xe = jnp.where(mine, x, 0.0)
            hidden = jax.nn.relu(reference.mm(xe, w["expert_gate"][e], fp8)) * (
                reference.mm(xe, w["expert_up"][e], fp8)
            )
            out = reference.mm(hidden, w["expert_down"][e], fp8)
            return acc + jnp.where(mine, out, 0.0)

        last = jnp.minimum(jnp.max(jnp.where(ex_c < e_n, ex_c, 0)), e_n - 1)
        return jax.lax.fori_loop(ex_c[0], last + 1, one, jnp.zeros_like(x))

    outs = jax.lax.map(
        chunk, (expert.reshape(n, _PAIR_CHUNK), rows.reshape(n, _PAIR_CHUNK, d))
    ).reshape(n * _PAIR_CHUNK, d)
    back = jnp.argsort(order)  # each pair's place in the sorted order
    return jnp.einsum(
        "skd,sk->sd", outs[back].reshape(s, k, d), wts,
        precision=jax.lax.Precision.HIGHEST,
    )


def _route(x: Any, hline: Any, w: dict, sz: dict, fp8: bool):
    """Each token's chosen experts and their weights. The router reads the
    layer's input x (``hline``, the normed input, is what a router placed
    after the norm would read: an ``assumed`` line of the configuration).
    The control rounds this matrix product's operands like every other."""
    import jax

    top, idx = jax.lax.top_k(reference.mm(x, w["router"], fp8), sz["active"])
    return idx, jax.nn.softmax(top, axis=-1)


def _layer_one(x: Any, mask: Any, w: dict, sz: dict, rotary: bool,
               windowed: bool, fp8: bool) -> Any:
    """One sequence [s, d] through one layer; mask [s] marks valid keys."""
    s, _d = x.shape
    h, hk, dh = sz["heads"], sz["kv_heads"], sz["dh"]
    hline = reference.rms(x, w["ln1_scale"])
    idx, wts = _route(x, hline, w, sz, fp8)
    q = reference.mm(hline, w["q"], fp8).reshape(s, h, dh)
    k = reference.mm(hline, w["k"], fp8).reshape(s, hk, dh)
    v = reference.mm(hline, w["v"], fp8).reshape(s, hk, dh)
    if rotary:
        q, k = _rope(q, sz["theta"]), _rope(k, sz["theta"])
    ctx = _attention(
        q.reshape(s, hk, h // hk, dh), k, v, mask,
        sz["window"] if windowed else None, fp8,
    )
    x = x + reference.mm(ctx, w["o"], fp8)
    return x + _experts(reference.rms(x, w["ln2_scale"]), idx, wts, w, fp8)


@functools.lru_cache(maxsize=None)
def _layer_fn(sz_items: tuple, rotary: bool, windowed: bool, fp8: bool):
    """jit of: draw layer ``li``'s weights, run every row through it."""
    import jax
    import jax.numpy as jnp

    sz = dict(sz_items)

    def fn(kd, li, x, mask):
        key = jax.random.wrap_key_data(kd)
        w = {
            k: v.astype(jnp.float32)
            for k, v in _block_leaves(key, li, sz).items()
        }
        return jax.lax.map(
            lambda xm: _layer_one(xm[0], xm[1], w, sz, rotary, windowed, fp8),
            (x, mask),
        )

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
    drawn again from the seed, layer by layer. Rows are padded on the right
    to one width (the longest row's, rounded up to 512, at most ``width``),
    so that one program serves every seed of a cell. ``fp8`` is the control
    (reference.py): both operands of every matrix product rounded to fp8,
    the router's too."""
    import jax
    import jax.numpy as jnp

    n = len(rows)
    width = min(width, -(-max(len(r) for r in rows) // 512) * 512)
    ids = np.zeros((n, width), np.int32)
    mask = np.zeros((n, width), bool)
    for i, r in enumerate(rows):
        ids[i, :len(r)] = r
        mask[i, :len(r)] = True
    items = tuple(sorted(sz.items()))
    kd = jnp.asarray(weights.key_data(seed, sz["tag"]))
    top = _top_fn(items)(kd)
    x = top["tok_embed"][jnp.asarray(ids)]
    m = jnp.asarray(mask)
    for li in range(sz["layers"]):
        kinds = (bool(sz["rope_layout"][li]), bool(sz["window_layout"][li]))
        x = _layer_fn(items, *kinds, fp8)(kd, jnp.asarray(li, jnp.int32), x, m)
    logits = []
    for i in range(n):
        hline = reference.rms(x[i, at[i].start:at[i].stop, :], top["ln_f_scale"])
        lg = reference.mm(hline, top["lm_head"], fp8)
        logits.append(np.asarray(jax.device_get(lg), np.float32))
    return logits


# ---------------------------------------------------------------- counts
# Operations and bytes the algorithm needs, from shapes alone.
#
# * one token through the blocks multiplies with the attention's matrices
#   (q and o: d x heads dh; k and v: d x kv heads dh), the router (d x E)
#   and its k chosen experts (3 d ff each): n_block counts those, and
#   token_flops is twice it.
# * a prefill of p real tokens adds attention, 4 heads dh a pair that
#   attends (QK^T and PV): p^2 / 2 pairs in a global layer, in a window
#   layer sum_i min(i + 1, W); and the logits of the last position.
# * a decode step with m occupied rows reads the attention's matrices, the
#   router and the head once; of a layer's E experts the E (1 - ((E - k) /
#   E)^m) that m rows choosing k of E at random touch; the keys and values
#   of the live positions (a window layer keeps min(context, W) of them),
#   2 x kv heads x dh x 2 bytes a position and layer; the m new rows.

def _attn_matrix_elements(sz: dict) -> int:
    return 2 * sz["d"] * sz["dh"] * (sz["heads"] + sz["kv_heads"])


def expert_matrix_elements(sz: dict) -> int:
    """One expert's three matrices."""
    return 3 * sz["d"] * sz["ff"]


def _window_layers(sz: dict) -> int:
    return sum(sz["window_layout"])


def n_block(sz: dict) -> int:
    return sz["layers"] * (
        _attn_matrix_elements(sz) + sz["d"] * sz["experts"]
        + sz["active"] * expert_matrix_elements(sz)
    )


def token_flops(sz: dict) -> int:
    """2 x the active parameters of the blocks: one token through them."""
    return 2 * n_block(sz)


def _causal_pairs(p: float, window: int | None) -> float:
    if window is None or p <= window:
        return p * p / 2
    return window * window / 2 + (p - window) * window


def prefill_flops(sz: dict, p: int) -> float:
    n_win = _window_layers(sz)
    pairs = (sz["layers"] - n_win) * _causal_pairs(p, None) + n_win * _causal_pairs(
        p, sz["window"]
    )
    return (
        token_flops(sz) * p + 4 * sz["heads"] * sz["dh"] * pairs
        + 2 * sz["d"] * sz["vocab"]
    )


def _row_bytes(sz: dict) -> int:
    """A position's key and value in one layer, bf16."""
    return 2 * sz["kv_heads"] * sz["dh"] * 2


def _live_rows(sz: dict, context: float) -> float:
    """Cache rows of one slot that a step reads, over all layers."""
    n_win = _window_layers(sz)
    return (sz["layers"] - n_win) * context + n_win * min(context, sz["window"])


def experts_touched(sz: dict, m: float) -> float:
    """Of one layer's experts, those that m rows choosing at random hit."""
    e = sz["experts"]
    return e * (1.0 - ((e - sz["active"]) / e) ** m)


def decode_step_bytes(sz: dict, contexts: list[float]) -> float:
    m = len(contexts)
    once = sz["layers"] * (
        _attn_matrix_elements(sz) + sz["d"] * sz["experts"]
    ) + sz["d"] * sz["vocab"]
    experts = sz["layers"] * experts_touched(sz, m) * expert_matrix_elements(sz)
    kv = sum(_live_rows(sz, c) for c in contexts) * _row_bytes(sz)
    new = m * sz["layers"] * _row_bytes(sz)
    return 2 * (once + experts) + kv + new


def decode_step_flops(sz: dict, contexts: list[float]) -> float:
    per_row = token_flops(sz) + 2 * sz["d"] * sz["vocab"]
    return sum(
        per_row + 4 * sz["heads"] * sz["dh"] * _live_rows(sz, c) for c in contexts
    )


def n_params(sz: dict, *, embedding: bool) -> int:
    """Parameters of the blocks (every expert), with or without the
    embedding and the head."""
    d = sz["d"]
    n = sz["layers"] * (
        _attn_matrix_elements(sz) + d * sz["experts"]
        + sz["experts"] * expert_matrix_elements(sz) + 2 * d
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


# ----------------------------------------------------------------- fault

@contextlib.contextmanager
def window_reads_every_row(config: dict) -> Iterator[None]:
    """The fault of this block, as ``pwbench.faults.Fault(program=...)``
    takes it: while the server is built, every window layer is a global
    one (no ring: it keeps and reads every row)."""
    real = globals()["program_config"]

    def broken(config: dict, dtype: Any) -> Any:
        cfg = real(config, dtype)
        return dataclasses.replace(cfg, layers=tuple(
            dataclasses.replace(spec, window=None) for spec in cfg.layers
        ))

    globals()["program_config"] = broken  # the harness asks the module
    try:
        yield
    finally:
        globals()["program_config"] = real


if __name__ == "__main__":
    import argparse
    import json
    import os
    import time

    t_start = time.monotonic()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from pwbench import faults, harness, spec

    ap = argparse.ArgumentParser(description="window_reads_every_row at a cell's size")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    a = ap.parse_args()
    # the harness asks the module that spec.family() loaded, not __main__
    fault = faults.Fault(program=spec.family("smallthinker").window_reads_every_row)
    result = harness.run_cell(
        Path(__file__).resolve().parents[2] / "BENCHMARK.json", a.workload,
        a.seed, a.seconds, False, t_start=t_start, fault=fault,
    )
    print(json.dumps({
        "workload": a.workload, "seed": a.seed, "fault": "window_reads_every_row",
        "correct": result["correct"], "attempted": result["attempted"],
        "phases_s": result["phases_s"], "compared": result["compared"],
    }), flush=True)
    os._exit(0 if not result["correct"] else 1)
