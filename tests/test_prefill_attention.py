"""The prefill's attention kernel (ops/attention.py `prefill_attention`) in
Pallas interpret mode on the CPU against the plain `attend` with the
explicit mask, and the rule that chooses between the two
(models/mixers/softmax.py `prefill_uses_kernel`). The tile is capped at 128
here so that a width of a few hundred has a diagonal, a band and a padded
tile to skip; the chip's own compiler sees the real shapes in
tests/test_prefill_kernel_v5e.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import lm_config
from pathway_tpu.models import layers as LY
from pathway_tpu.models import transformer as T
from pathway_tpu.models.mixers import softmax as SM
from pathway_tpu.ops import attention as A
from pathway_tpu.ops.sparse_attention import sparse_prefill_attention

DH = 128
TILE = 128


@pytest.fixture(autouse=True)
def _small_tiles(monkeypatch):
    monkeypatch.setattr(A, "_PREFILL_TILE_MAX", TILE)


def _inputs(p, heads, kv_heads, pad, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + p + heads), 3)
    q, k, v = (
        jax.random.normal(key, (1, p, n, DH), jnp.float32).astype(dtype)
        for key, n in zip(ks, (heads, kv_heads, kv_heads))
    )
    valid = (jnp.arange(p)[None, :] >= pad).astype(jnp.int32)
    return q, k, v, valid


def _plain(q, k, v, valid, window):
    """`attend` over the [heads, p, p] square with the mask `_prefill`
    builds off the chip."""
    p = q.shape[1]
    ok = LY.build_mask(valid, causal=True)
    if window is not None:
        at = jnp.arange(p)
        ok = ok & (at[None, :] > at[:, None] - window)[None, None]
    # `attend` reads keys as the cache lies: [b, kv heads, p, dh]
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    return LY.attend(q, k, v, ok, lm_config(dtype=q.dtype))


# width, query heads, key heads, left padding, window. One tile, several,
# a width that 128 does not divide (the ladder's cap, 2016 of 2048, in
# small: 224 of 256); heads one to one and seven to a key head; padding
# of none, of one, of most of a tile and of more than a tile; no window,
# one of which the width is 2.45, one as long as the width and one longer.
SHAPES = [
    (128, 2, 2, 0, None),
    (128, 2, 2, 1, None),
    (128, 2, 2, 100, None),
    (384, 2, 2, 0, None),
    (384, 2, 2, 130, None),
    (384, 7, 1, 1, None),
    (384, 2, 2, 3, 157),
    (384, 7, 1, 100, 157),
    (256, 2, 2, 1, 256),
    (256, 2, 1, 0, 1000),
    (224, 2, 2, 5, None),
    (224, 2, 1, 100, 64),
]
# bfloat16 against `attend` in bfloat16: both round the weights of the
# value product to 8 bits, the kernel before its division by the sum and
# `attend` after it, over values of unit spread: the largest difference
# stays under 0.05 (read: 0.008-0.016)
CASES = [(*s, jnp.float32, 1e-4) for s in SHAPES] + [
    (*s, jnp.bfloat16, 0.05) for s in SHAPES[2::2]
]


@pytest.mark.parametrize("p, heads, kv_heads, pad, window, dtype, tol", CASES)
def test_the_kernel_agrees_with_the_plain_attention(
    p, heads, kv_heads, pad, window, dtype, tol
):
    q, k, v, valid = _inputs(p, heads, kv_heads, pad, dtype)
    got = A.prefill_attention(q, k, v, valid, window, interpret=True)
    want = _plain(q, k, v, valid, window)
    assert got.shape == want.shape == (1, p, heads * DH) and got.dtype == dtype
    # a row of the padding attends nothing real: any finite vector will do
    assert np.isfinite(np.asarray(got, np.float32)).all()
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert diff[:, pad:].max() < tol


def test_any_set_of_valid_keys_is_held_to():
    """`valid` is a mask, not a length: keys switched off in the middle of
    the prompt (a whole tile of them, and a few more) are not attended."""
    q, k, v, valid = _inputs(384, 2, 2, 3, jnp.float32)
    valid = valid.at[:, 120:260].set(0)
    got = A.prefill_attention(q, k, v, valid, None, interpret=True)
    want = _plain(q, k, v, valid, None)
    rows = np.asarray(valid[0], bool)
    assert np.abs(np.asarray(got - want))[:, rows].max() < 1e-4


# a tile the kernel must not read, a NaN in every key and value of it, and
# the query rows that have no business with that tile: the plain path
# multiplies the NaN by a weight of 0 and returns NaN there
@pytest.mark.parametrize("where, pad, window, tile, rows", [
    ("above_the_diagonal", 0, None, 2, slice(0, 256)),
    ("in_the_padding", 130, None, 0, slice(130, 384)),
    ("before_the_band", 0, 100, 0, slice(256, 384)),
])
def test_a_skipped_tile_is_never_read(where, pad, window, tile, rows):
    q, k, v, valid = _inputs(384, 2, 1, pad, jnp.float32)
    clean = A.prefill_attention(q, k, v, valid, window, interpret=True)
    at = slice(tile * TILE, (tile + 1) * TILE)
    k, v = k.at[:, at].set(jnp.nan), v.at[:, at].set(jnp.nan)
    got = A.prefill_attention(q, k, v, valid, window, interpret=True)
    assert np.array_equal(np.asarray(got[:, rows]), np.asarray(clean[:, rows]))
    assert np.isnan(np.asarray(_plain(q, k, v, valid, window)[:, rows])).all()


def test_keys_that_came_masked_are_wiped_by_the_first_allowed():
    """Rows of a window layer's query tile whose band begins past the
    tile's first key tile meet that tile wholly masked: their maximum
    stays at the mask's value and they sum exponentials of 0 over values
    made huge here, until their first allowed key moves the maximum and
    the rescale wipes what was summed."""
    q, k, v, valid = _inputs(384, 7, 1, 0, jnp.float32)
    window = 157
    v = v.at[:, :TILE].multiply(1e4)  # the first key tile of query tile 2
    got = A.prefill_attention(q, k, v, valid, window, interpret=True)
    want = _plain(q, k, v, valid, window)
    # rows 157 + 128 - 1 = 284 .. 383 attend no key of tile 0
    late = np.asarray(got[:, 284:] - want[:, 284:])
    assert np.abs(late).max() < 1e-4
    assert np.abs(np.asarray(got[:, 284:])).max() < 10


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("p, heads, kv_heads, pad", [(384, 4, 2, 0), (224, 7, 1, 37)])
def test_every_block_chosen_is_the_dense_kernel_bit_for_bit(
    p, heads, kv_heads, pad, dtype
):
    """ops/sparse_attention.py `sparse_prefill_attention` runs the same
    tile body under one more mask: with every block chosen it returns what
    `prefill_attention` returns, to the bit in bfloat16, which the cells
    run. In float32 the interpreter makes two XLA programs of the two
    kernels, whose fused row sums round their own way: the last bit."""
    q, k, v, valid = _inputs(p, heads, kv_heads, pad, dtype)
    block = 16
    blocks = jnp.ones((1, kv_heads, p, -(-p // block)), jnp.bool_)
    dense = A.prefill_attention(q, k, v, valid, None, interpret=True)
    sparse = sparse_prefill_attention(q, k, v, valid, blocks, block, interpret=True)
    dense, sparse = (np.asarray(a[:, pad:], np.float32) for a in (dense, sparse))
    if dtype == jnp.bfloat16:
        assert np.array_equal(dense, sparse)
    else:
        assert np.abs(dense - sparse).max() < 1e-6


def test_the_tile_is_a_function_of_the_shapes(monkeypatch):
    monkeypatch.setattr(A, "_PREFILL_TILE_MAX", 896)  # the module's own
    assert A.prefill_tile(1280, 128, 1) == 640  # rag-cerebras-6b7
    assert A.prefill_tile(10240, 128, 7) == 512  # rag-smallthinker-21b-a3b
    assert A.prefill_tile(2048, 128, 1) == 512  # the cap's rung 2016, padded
    assert A.prefill_tile(16384, 128, 7) == 512
    assert A.prefill_tile(24576, 128, 16) == 256  # rag-minicpm-sala
    assert A.prefill_tile(128, 128, 1) == 128
    assert A.prefill_tile(896, 128, 1) == 896
    assert A.prefill_tile(1024, 128, 64) == 128  # nothing fits: the least
    narrow = jnp.zeros((1, 128, 2, 64))  # heads of 64
    with pytest.raises(ValueError, match="multiple of 128"):
        A.prefill_attention(narrow, narrow, narrow, jnp.ones((1, 128), jnp.int32))


CEREBRAS = dict(d_model=4096, n_heads=32, n_layers=1, d_ff=64, max_len=2048)
SMALLTHINKER = dict(d_model=2560, n_heads=28, n_kv_heads=4, head_size=128,
                    n_layers=1, d_ff=64, max_len=16384)
GPT2_XL = dict(d_model=1600, n_heads=25, n_layers=1, d_ff=64, max_len=1024)


@pytest.mark.parametrize("keys, width, backend, want", [
    (CEREBRAS, 1280, "tpu", True),  # rag-cerebras-6b7.backlog's rung
    (SMALLTHINKER, 10240, "tpu", True),  # rag-smallthinker-21b-a3b.backlog's
    (CEREBRAS, 2016, "tpu", True),  # the caps' rungs, no multiples of 128
    (SMALLTHINKER, 16352, "tpu", True),
    (CEREBRAS, 128, "tpu", True),
    (CEREBRAS, 64, "tpu", False),  # a rung under 128
    (GPT2_XL, 896, "tpu", False),  # heads of 64
    (CEREBRAS, 1280, "cpu", False),  # off the TPU
    (SMALLTHINKER, 10240, "gpu", False),
    ({**CEREBRAS, "fused_attention": False}, 1280, "tpu", False),  # sharded
])
def test_the_rule_that_chooses_the_path(keys, width, backend, want, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = lm_config(vocab_size=64, **keys)
    assert SM.prefill_uses_kernel(cfg, width) is want
