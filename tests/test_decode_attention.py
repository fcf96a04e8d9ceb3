"""The decode step's attention kernel (ops/attention.py `decode_attention`)
in Pallas interpret mode on the CPU against the plain path of
models/transformer.py `_step_rows` on the same cache: the row write
(`.at[...].set`) and `attend` under the masks the step builds. And the
rule that chooses between the two (`step_uses_kernel`). The block is cut to
one key head and 128 rows here, so that a leaf of a few hundred rows has
tiles to skip and a tile that hangs over its end; the chip's own compiler
sees the real shapes in tests/test_prefill_kernel_v5e.py.
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

DH = 128
TILE = 128


@pytest.fixture
def small_blocks(monkeypatch):
    """A block of one key head and 128 rows, whatever the dtype."""
    monkeypatch.setattr(A, "_DECODE_BLOCK", TILE * DH * 2)


def _inputs(rows, heads, kv_heads, slots, dtype, layers=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + rows + heads), 5)
    leaf = (layers, slots, kv_heads, rows, DH)
    q, kn, vn, kc, vc = (
        jax.random.normal(key, shape, jnp.float32).astype(dtype)
        for key, shape in zip(ks, (
            (slots, heads, DH), (slots, kv_heads, DH), (slots, kv_heads, DH),
            leaf, leaf,
        ))
    )
    return q, kn, vn, kc, vc


def _plain(q, kn, vn, kc, vc, li, pos, pad, ring: bool):
    """What `_step_rows` does off the chip: the rows written by index, then
    `attend` over every row of the layer under the step's own mask."""
    slots, rows = q.shape[0], kc.shape[3]
    at_row = (pos % rows if ring else pos)[:, None]
    b, hd = jnp.arange(slots)[:, None], jnp.arange(kc.shape[2])[None, :]
    kc = kc.at[li, b, hd, at_row].set(kn)
    vc = vc.at[li, b, hd, at_row].set(vn)
    at = jnp.arange(rows)[None, :]
    if ring:
        held = pos[:, None] - (pos[:, None] - at) % rows
        ok = held >= pad[:, None]
    else:
        ok = (at <= pos[:, None]) & (at >= pad[:, None])
    ctx = LY.attend(
        q[:, None], kc[li], vc[li], ok[:, None, None, :], lm_config(dtype=q.dtype)
    )
    return ctx[:, 0], kc, vc


# rows of the leaf, whether they are a ring, and each slot's (position,
# left pad). A global layer of 320 rows (no multiple of the tile: the third
# tile hangs over the end): a free slot beside live ones, a position on a
# tile's last row and on the next tile's first, a pad of none, inside the
# first tile and past it. A ring of 256: not yet wrapped, wrapped once,
# wrapped 2.45 times (627 = 2.45 x 256), a pad that lies inside the ring's
# reach (400 > 627 - 256) and one that has left it.
SLOTS = {
    "global": (320, False, [(0, 0), (127, 0), (128, 5), (300, 130)]),
    "global_full": (320, False, [(319, 1), (129, 128), (255, 127), (256, 0)]),
    "ring_not_wrapped": (256, True, [(0, 0), (100, 3), (255, 130), (128, 0)]),
    "ring_wrapped_once": (256, True, [(256, 5), (300, 130), (511, 0), (0, 0)]),
    "ring_wrapped_2.45": (256, True, [(627, 5), (627, 400), (640, 0), (383, 130)]),
}
# query heads over key heads: one to one, and seven to a key head
GROUPS = [(2, 2), (7, 1)]
# bfloat16 against `attend` in bfloat16: both round the weights of the
# value product to 8 bits, the kernel before its division by the sum and
# `attend` after it, over values of unit spread (as
# tests/test_prefill_attention.py: read 0.004-0.016)
CASES = [
    (name, heads, kv_heads, dtype, tol)
    for name in SLOTS for heads, kv_heads in GROUPS
    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 0.05))
]


@pytest.mark.parametrize("name, heads, kv_heads, dtype, tol", CASES)
def test_the_kernel_agrees_with_the_plain_step(
    name, heads, kv_heads, dtype, tol, small_blocks
):
    rows, ring, slots = SLOTS[name]
    pos, pad = (jnp.asarray(x, jnp.int32) for x in zip(*slots))
    q, kn, vn, kc, vc = _inputs(rows, heads, kv_heads, len(slots), dtype)
    assert A.decode_block(rows, kv_heads, DH, q.dtype.itemsize) == (1, TILE)
    got, got_k, got_v = A.decode_attention(
        q, kn, vn, kc, vc, 1, pos, pad, interpret=True
    )
    want, want_k, want_v = _plain(q, kn, vn, kc, vc, 1, pos, pad, ring)
    assert got.shape == want.shape == (len(slots), heads * DH)
    assert got.dtype == dtype
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert diff.max() < tol
    # the leaves: the new rows where the plain write puts them, every other
    # row of every layer and slot as it was
    assert np.array_equal(np.asarray(got_k, np.float32), np.asarray(want_k, np.float32))
    assert np.array_equal(np.asarray(got_v, np.float32), np.asarray(want_v, np.float32))


@pytest.mark.parametrize("heads, kv_heads", [(4, 4), (14, 2)])
def test_a_block_of_every_key_head_and_the_whole_leaf(heads, kv_heads):
    """The block as the shapes give it (no cut): every key head of the slot
    and all of a short leaf's rows in one grid step."""
    rows, ring, slots = SLOTS["ring_wrapped_once"]
    pos, pad = (jnp.asarray(x, jnp.int32) for x in zip(*slots))
    q, kn, vn, kc, vc = _inputs(rows, heads, kv_heads, len(slots), jnp.float32)
    assert A.decode_block(rows, kv_heads, DH, 4) == (kv_heads, rows)
    got, got_k, _ = A.decode_attention(q, kn, vn, kc, vc, 0, pos, pad, interpret=True)
    want, want_k, _ = _plain(q, kn, vn, kc, vc, 0, pos, pad, ring)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.array_equal(np.asarray(got_k), np.asarray(want_k))


# a tile the kernel must not read, a NaN in every key and value of it: the
# plain path multiplies the NaN by a weight of 0 and returns NaN
@pytest.mark.parametrize("where, slot, tile", [
    ("past_the_position", (100, 3), 1),
    ("past_the_position_at_the_end", (255, 0), 2),
    ("in_the_padding", (300, 130), 0),
])
def test_a_tile_with_no_live_row_is_never_read(where, slot, tile, small_blocks):
    pos, pad = (jnp.asarray([x], jnp.int32) for x in slot)
    q, kn, vn, kc, vc = _inputs(320, 2, 1, 1, jnp.float32)
    clean, _, _ = A.decode_attention(q, kn, vn, kc, vc, 0, pos, pad, interpret=True)
    at = slice(tile * TILE, (tile + 1) * TILE)
    kc, vc = kc.at[0, :, :, at].set(jnp.nan), vc.at[0, :, :, at].set(jnp.nan)
    got, _, _ = A.decode_attention(q, kn, vn, kc, vc, 0, pos, pad, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(clean))
    assert np.isnan(np.asarray(_plain(q, kn, vn, kc, vc, 0, pos, pad, False)[0])).all()


def test_the_block_is_a_function_of_the_shapes():
    assert A.decode_block(2048, 32, 128) == (32, 128)  # rag-cerebras-6b7
    assert A.decode_block(16384, 4, 128) == (4, 1024)  # rag-smallthinker-21b-a3b
    assert A.decode_block(4096, 4, 128) == (4, 1024)  # and its ring
    assert A.decode_block(1024, 8, 256) == (8, 256)
    assert A.decode_block(2048, 64, 128) == (32, 128)  # too many heads for one
    assert A.decode_block(8, 2, 128) == (2, 8)  # a ring shorter than a tile
    assert A.decode_block(320, 2, 128, 4) == (2, 256)
    narrow = jnp.zeros((1, 2, 64))  # heads of 64
    leaf = jnp.zeros((1, 1, 2, 128, 64))
    vec = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="multiple of 128"):
        A.decode_attention(narrow, narrow, narrow, leaf, leaf, 0, vec, vec)


CEREBRAS = dict(d_model=4096, n_heads=32, n_layers=1, d_ff=64, max_len=2048)
SMALLTHINKER = dict(d_model=2560, n_heads=28, n_kv_heads=4, head_size=128,
                    n_layers=1, d_ff=64, max_len=16384)
GPT2_XL = dict(d_model=1600, n_heads=25, n_layers=1, d_ff=64, max_len=1024)


@pytest.mark.parametrize("keys, backend, want", [
    (CEREBRAS, "tpu", True),
    (SMALLTHINKER, "tpu", True),
    (GPT2_XL, "tpu", False),  # heads of 64
    (CEREBRAS, "cpu", False),  # off the TPU
    (SMALLTHINKER, "gpu", False),
    # tensor-parallel parameters, a slot axis over a mesh
    ({**CEREBRAS, "fused_attention": False}, "tpu", False),
])
def test_the_rule_that_chooses_the_steps_path(keys, backend, want, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert SM.step_uses_kernel(lm_config(vocab_size=64, **keys)) is want
