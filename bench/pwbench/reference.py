"""The plain reference: the served block written out in ``jax.numpy`` and
float32 at ``highest`` matmul precision, with no kernel, no cache and no
batching tricks. It imports nothing of ``pathway_tpu``, and draws its own
weights from the seed (weights.py), one layer at a time.

Here are the encoder's embeddings and the helpers the references share:
the fp8 rounding of the control, the matrix product, the norm, and the
block that the encoder and the ``gpt2`` decoder family both run. A
decoder's logits are its family's (bench/families/<family>.py).

The block (the repository's, as the configurations' ``assumed`` say):
  x += attn(rms(x, ln1)) ; x += gelu_tanh(rms(x, ln2) @ ff_in) @ ff_out
  attn: qkv = h @ W_qkv, heads split in order, softmax(q k^T / sqrt(dh)) v,
  then @ W_o; learned positions. Decoder (gpt2): causal, logits =
  rms(x, ln_f) @ tok_embed^T. Encoder: every valid key, mean pool over
  valid tokens, @ head, L2-normalised.

``fp8`` puts the control in the reference's place: both operands of every
matrix product are rounded to float8_e4m3fn (scaled per tensor), the
nearest precision below the served bfloat16."""

from __future__ import annotations

import functools
import math
from typing import Any

import numpy as np

from . import weights


def quant(x: Any, fp8: bool) -> Any:
    import jax.numpy as jnp

    if not fp8:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a: Any, b: Any, fp8: bool) -> Any:
    import jax
    import jax.numpy as jnp

    return jnp.matmul(
        quant(a, fp8), quant(b, fp8), precision=jax.lax.Precision.HIGHEST
    )


def rms(x: Any, scale: Any) -> Any:
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * scale


def _gelu_tanh(x: Any) -> Any:
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def _block_one(x: Any, mask: Any, w: dict, heads: int, causal: bool,
               fp8: bool) -> Any:
    """One sequence [s, d] through one block; mask [s] marks valid keys."""
    import jax
    import jax.numpy as jnp

    s, d = x.shape
    dh = d // heads
    qkv = mm(rms(x, w["ln1_scale"]), w["qkv"], fp8)
    q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(s, heads, dh) for i in range(3))
    scores = jnp.einsum(
        "qhd,khd->hqk", quant(q, fp8), quant(k, fp8),
        precision=jax.lax.Precision.HIGHEST,
    ) / math.sqrt(dh)
    attend = mask[None, None, :]
    if causal:
        attend = attend & jnp.tril(jnp.ones((s, s), bool))[None]
    probs = jax.nn.softmax(jnp.where(attend, scores, -1e30), axis=-1)
    ctx = jnp.einsum(
        "hqk,khd->qhd", quant(probs, fp8), quant(v, fp8),
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(s, d)
    x = x + mm(ctx, w["o"], fp8)
    hidden = _gelu_tanh(mm(rms(x, w["ln2_scale"]), w["ff_in"], fp8))
    return x + mm(hidden, w["ff_out"], fp8)


@functools.lru_cache(maxsize=None)
def _layer_fn(sz_items: tuple, causal: bool, fp8: bool):
    """jit of: draw layer ``li``'s weights, run every row through it."""
    import jax
    import jax.numpy as jnp

    sz = dict(sz_items)

    def fn(kd, li, x, mask):
        key = jax.random.wrap_key_data(kd)
        w = {
            k: v.astype(jnp.float32)
            for k, v in weights.block_leaves(key, li, sz).items()
        }
        # a long causal row's scores fill the memory alone; short
        # encoder rows go 256 at a time
        return jax.lax.map(
            lambda xm: _block_one(xm[0], xm[1], w, sz["heads"], causal, fp8),
            (x, mask), batch_size=1 if causal else 256,
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
            k: v.astype(jnp.float32)
            for k, v in weights.top_leaves(key, sz).items()
        }

    return jax.jit(fn)


def hidden(seed: int, sz: dict, ids: np.ndarray, mask: np.ndarray,
            causal: bool, fp8: bool) -> tuple[Any, dict]:
    """Final hidden states [n, s, d] before the last norm, and the top
    leaves. Rows are right-padded; positions run from 0."""
    import jax.numpy as jnp

    items = tuple(sorted(sz.items()))
    kd = jnp.asarray(weights.key_data(seed, sz["tag"]))
    top = _top_fn(items)(kd)
    s = ids.shape[1]
    x = top["tok_embed"][jnp.asarray(ids)] + top["pos_embed"][None, :s, :]
    m = jnp.asarray(mask.astype(bool))
    layer = _layer_fn(items, causal, fp8)
    for li in range(sz["layers"]):
        x = layer(kd, jnp.asarray(li, jnp.int32), x, m)
    return x, top


def encoder_embed(seed: int, sz: dict, rows: list[list[int]],
                  block_rows: int = 2048,
                  fp8: bool = False) -> np.ndarray:
    """Unit embeddings [n, embed] of token rows, ``block_rows`` at a time,
    every row padded to the encoder's positions."""
    import jax
    import jax.numpy as jnp

    width = sz["positions"]
    out = np.zeros((len(rows), sz["embed"]), np.float32)
    for lo in range(0, len(rows), block_rows):
        part = rows[lo:lo + block_rows]
        ids = np.zeros((block_rows, width), np.int32)
        mask = np.zeros((block_rows, width), np.int32)
        for i, r in enumerate(part):
            r = r[:width]
            ids[i, :len(r)] = r
            mask[i, :len(r)] = 1
        mask[len(part):, 0] = 1  # rows of padding: one token, dropped below
        x, top = hidden(seed, sz, ids, mask, False, fp8)
        h = rms(x, top["ln_f_scale"])
        m = jnp.asarray(mask, jnp.float32)[:, :, None]
        pooled = jnp.sum(h * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
        e = mm(pooled, top["head"], fp8)
        e = e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True), 1e-12)
        out[lo:lo + len(part)] = np.asarray(jax.device_get(e))[:len(part)]
    return out
