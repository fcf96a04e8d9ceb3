"""The routed expert layer's kernels (ops/experts.py `grouped_experts`) in
Pallas interpret mode on the CPU against the per-expert loop in float32 at
`highest` precision, the experts layer and a prefill through the slot cache
on the kernel path against the plain path (`ragged_dot`), and the rule that
chooses between the two (models/routed.py `experts_use_kernel`). The
tile is cut to 32 rows in blocks of 16 so that tiny runs of rows meet every
case: an edge inside a block, a tile that holds three groups, an expert with
no pair, a last tile that hangs over the rows' end.
"""

from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import LayerSpec, lm_config
from pathway_tpu.models import routed as RT
from pathway_tpu.models import transformer as T
from pathway_tpu.ops import experts as X

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))
from pwbench import spec  # noqa: E402

FAMILY = spec.family("smallthinker")
TILE, BLOCK = 32, 16
D, FF = 128, 128


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(X, "_TILE", TILE)
    monkeypatch.setattr(X, "_BLOCK", BLOCK)


def _operands(sizes: list[int], dtype=jnp.float32, seed: int = 0):
    m, e = sum(sizes), len(sizes)
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (
        jax.random.normal(k[0], (m, D), dtype),
        jax.random.uniform(k[1], (m,), jnp.float32, 0.05, 1.0),
        jnp.asarray(sizes, jnp.int32),
        (jax.random.normal(k[2], (e, D, FF)) / D ** 0.5).astype(dtype),
        (jax.random.normal(k[3], (e, D, FF)) / D ** 0.5).astype(dtype),
        (jax.random.normal(k[4], (e, FF, D)) / FF ** 0.5).astype(dtype),
    )


def _loop(rows, weight, sizes, gate, up, down, hidden_dtype=None):
    """Expert by expert over its own run of rows, float32 at `highest`."""
    out, at = [], 0
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        for e, n in enumerate(np.asarray(sizes).tolist()):
            x = f32(rows[at:at + n])
            hidden = jax.nn.relu(x @ f32(gate[e])) * (x @ f32(up[e]))
            if hidden_dtype is not None:  # the rounding between the kernels
                hidden = f32(hidden.astype(hidden_dtype))
            out.append((hidden @ f32(down[e])) * weight[at:at + n, None])
            at += n
    return jnp.concatenate(out)


CASES = {
    "an even router": [24, 24, 24, 24],
    "every token to one expert": [96, 0, 0, 0],
    "the last expert alone": [0, 0, 0, 96],
    "experts with no pair among the others": [0, 50, 0, 46],
    "sizes that are no multiple of the tile": [33, 31, 17, 15],
    "a tile that straddles three groups": [3, 5, 7, 81],
    "a block that straddles three groups": [35, 2, 3, 56],
    "a last tile that hangs over the end": [33, 33, 34, 0],
    "fewer rows than a tile": [4, 0, 9, 7],
    "one row an expert": [1, 1, 1, 1],
}


@pytest.mark.parametrize("sizes", CASES.values(), ids=list(CASES))
def test_the_kernels_agree_with_the_per_expert_loop(sizes):
    ops = _operands(sizes)
    got = X.grouped_experts(*ops, interpret=True)
    want = _loop(*ops)
    # a row is a slab of lane tiles, for `combine_experts` to fetch whole
    assert got.shape == (sum(sizes), D // 128, 128) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got.reshape(want.shape) - want)).max() < 1e-5


def test_bfloat16_operands_accumulate_in_float32():
    """bf16 rows and matrices: against the loop over the same bf16 values in
    float32, with the one rounding the layer states (the ReLU product to
    bf16 between the kernels), the kernels differ by float32 rounding only
    (read: 1.3e-7 of the largest value). The same products with their sums
    rounded to bf16 are 5.3e-3 off: the tolerance, 1e-5, is one that a
    bf16 accumulator fails 500 times over."""
    ops = _operands([3, 5, 7, 81], jnp.bfloat16, seed=1)
    got = X.grouped_experts(*ops, interpret=True).reshape(-1, D)
    want = _loop(*ops, hidden_dtype=jnp.bfloat16)
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got - want)).max() < 1e-5 * scale
    rows, weight, sizes, gate, up, down = ops
    at, low = 0, []
    for e, n in enumerate(sizes.tolist()):  # products that round their sums
        x = rows[at:at + n]
        dot = lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.bfloat16)  # noqa: E731
        hidden = (jax.nn.relu(dot(x, gate[e])) * dot(x, up[e])).astype(jnp.bfloat16)
        low.append(dot(hidden, down[e]).astype(jnp.float32) * weight[at:at + n, None])
        at += n
    assert np.abs(np.asarray(jnp.concatenate(low) - want)).max() > 1e-3 * scale


def test_a_leaf_of_another_dtype_is_refused():
    rows, weight, sizes, gate, up, down = _operands([8, 8])
    with pytest.raises(ValueError, match="expert_up is bfloat16"):
        X.grouped_experts(rows, weight, sizes, gate, up.astype(jnp.bfloat16),
                          down, interpret=True)


@pytest.mark.parametrize("sizes, m, tm", [
    ([24, 24, 24, 24], 96, 32), ([3, 5, 7, 81], 96, 32), ([0, 50, 0, 46], 96, 32),
    ([33, 33, 34, 0], 100, 32), ([0, 0, 0, 7], 7, 16),
])
def test_the_visits_cover_every_row_once_in_order(sizes, m, tm):
    tile, group, start = (
        np.asarray(a) for a in X.expert_visits(jnp.asarray(sizes, jnp.int32), m, tm)
    )
    assert len(tile) == -(-m // tm) + len(sizes) - 1  # fixed by the shapes
    assert start[0] == 0 and start[-1] == m and (np.diff(start) >= 0).all()
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    for v in range(len(tile)):
        lo, hi = start[v], start[v + 1]
        if hi > lo:  # inside one tile and one group
            assert tile[v] * tm <= lo and hi <= (tile[v] + 1) * tm
            assert offsets[group[v]] <= lo and hi <= offsets[group[v] + 1]
    # a tile's visits follow each other (its result block is revisited),
    # and an empty visit names its neighbour's blocks: it fetches nothing
    assert (np.diff(tile) >= 0).all() and (np.diff(group) >= 0).all()


@pytest.mark.parametrize("tokens, k, d, dtype", [
    (40, 3, 128, jnp.float32),  # a last tile that hangs over the tokens
    (64, 6, 256, jnp.bfloat16),
    (7, 2, 128, jnp.float32),  # fewer tokens than a tile
])
def test_the_combine_sums_each_tokens_rows_in_float32(tokens, k, d, dtype, monkeypatch):
    monkeypatch.setattr(X, "_COMBINE_TOKENS", 16)
    key = jax.random.split(jax.random.PRNGKey(tokens), 2)
    y = jax.random.normal(key[0], (tokens * k, d), jnp.float32)
    back = jax.random.permutation(key[1], tokens * k).reshape(k, tokens)
    got = X.combine_experts(
        y.reshape(-1, d // 128, 128), back.astype(jnp.int32), dtype, interpret=True
    )
    want = jnp.sum(y[back], axis=0).astype(dtype)  # the sum, then one rounding
    assert got.shape == (tokens, d) and got.dtype == dtype
    assert np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max() < 1e-6


# --------------------------------------------------------------- the layer

KEYS = dict(
    vocab_size=256, hidden_size=128, num_attention_heads=2,
    num_key_value_heads=1, head_dim=128, num_hidden_layers=4,
    moe_ffn_hidden_size=128, moe_num_primary_experts=8,
    moe_num_active_primary_experts=2, rope_theta=1.5e6,
    sliding_window_size=64, rope_layout=[0, 1, 1, 1],
    sliding_window_layout=[0, 1, 1, 1], max_position_embeddings=192,
    tie_word_embeddings=False,
)
SIZES = FAMILY.sizes(KEYS)
SEED = 9


@functools.lru_cache(maxsize=None)
def _params(dtype=jnp.float32):
    return jax.tree.map(lambda x: x.astype(dtype), FAMILY.make_params(SEED, SIZES))


@pytest.fixture
def on_the_kernel(monkeypatch):
    """The rule says kernel for a prefill's pairs (as on a TPU with enough
    of them; interpreted here) and `ragged_dot` for a step's, as it does."""
    monkeypatch.setattr(
        RT, "experts_use_kernel", lambda cfg, pairs: pairs >= 16 * cfg.n_active
    )
    for name in ("grouped_experts", "combine_experts"):
        monkeypatch.setattr(
            X, name, functools.partial(getattr(X, name), interpret=True)
        )


def _layer_inputs(cfg, b=2, s=24, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    u = jax.random.normal(k[0], (b, s, cfg.d_model), cfg.dtype)
    idx, w = RT.route(
        jax.random.normal(k[1], (b, s, cfg.d_model), cfg.dtype),
        _params()["blocks"][0], cfg,
    )
    return u, idx, w


def test_the_layer_on_the_kernel_is_the_layer_on_ragged_dot(on_the_kernel, monkeypatch):
    """Padded rows (`live` false) are computed or not, and counted out."""
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    block = _params()["blocks"][0]
    u, idx, w = _layer_inputs(cfg)
    live = jnp.ones((2, 24), bool).at[0, :5].set(False)
    got, got_counts = RT.experts(u, idx, w, live, block, cfg)
    monkeypatch.setattr(RT, "experts_use_kernel", lambda cfg, pairs: False)
    want, want_counts = RT.experts(u, idx, w, live, block, cfg)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert got_counts.tolist() == want_counts.tolist()
    assert int(got_counts.sum()) == (48 - 5) * cfg.n_active
    by_hand = np.bincount(
        np.asarray(idx)[np.asarray(live)].reshape(-1), minlength=cfg.n_experts
    )
    assert got_counts.tolist() == by_hand.tolist()


def test_every_token_to_one_expert_loses_none_on_the_kernel(on_the_kernel):
    """No capacity: 48 tokens all choose experts 0 and 1 (a run of 48 rows
    each, a tile and a half), and every pair is multiplied by its expert."""
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    block = _params()["blocks"][0]
    u, _, _ = _layer_inputs(cfg)
    idx = jnp.broadcast_to(jnp.asarray([0, 1]), (2, 24, 2))
    w = jnp.broadcast_to(jnp.asarray([0.25, 0.75]), (2, 24, 2))
    y, counts = RT.experts(u, idx, w, jnp.ones((2, 24), bool), block, cfg)
    with jax.default_matmul_precision("highest"):
        want = sum(
            share * (
                jax.nn.relu(u @ block["expert_gate"][e]) * (u @ block["expert_up"][e])
            ) @ block["expert_down"][e]
            for e, share in ((0, 0.25), (1, 0.75))
        )
    assert np.abs(np.asarray(y - want)).max() < 1e-5
    assert counts.tolist() == [48, 48, 0, 0, 0, 0, 0, 0]


def _left_padded(row: list[int], width: int):
    ids = np.zeros((1, width), np.int32)
    mask = np.zeros((1, width), np.int32)
    ids[0, width - len(row):], mask[0, width - len(row):] = row, 1
    return jnp.asarray(ids), jnp.asarray(mask)


def _prompt(length: int) -> list[int]:
    return np.random.default_rng(length).integers(2, 256, length).tolist()


def _serve(cfg, params, row: list[int], width: int, n_steps: int = 6):
    """`prefill_into_slot` into slot 1 of three and `n_steps`
    `decode_step_slots`: (the prefill's logits, its counters, each step's
    tokens and counters)."""
    ids, mask = _left_padded(row, width)
    lg, _, _ = T._prefill(params, ids, T.init_kv_cache(cfg, 1), cfg, mask)
    first, cache = T.prefill_into_slot(
        params, ids, mask, T.init_kv_cache(cfg, 3), jnp.asarray(1), cfg
    )
    first = np.asarray(first)
    step = jax.jit(functools.partial(T.decode_step_slots, cfg=cfg))
    tok, pos, pad = (np.zeros(3, np.int32) for _ in range(3))
    tok[1], pos[1], pad[1] = first[0], width, width - len(row)
    steps = []
    for _ in range(n_steps):
        nxt, cache = step(params, cache, jnp.asarray(tok), jnp.asarray(pos),
                          jnp.asarray(pad))
        nxt = np.asarray(nxt)
        steps.append(nxt.tolist())
        tok[1], pos[1] = nxt[1], pos[1] + 1
    return np.asarray(lg[0], np.float32), first.tolist(), steps


@pytest.mark.parametrize("length, width", [(100, 128), (128, 128)])
def test_a_prefill_on_the_kernel_serves_the_plain_paths_logits_and_counts(
    length, width, on_the_kernel, monkeypatch
):
    """Float32 through four layers and the slot cache: the logits of the
    two paths differ by the order of float32 sums only (read: 3.1e-6 and
    4.5e-6 over logits of unit spread; the tolerance is 2e-5, and a product
    whose sums round to bf16 is 5.3e-3 of its scale off, the test above:
    250 times that), `routed_pairs` and `expert_load_max` are the same
    numbers, and the steps behind it (on `ragged_dot` by the rule, under
    both) decode the same tokens and count the same `experts_touched`."""
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    got = _serve(cfg, _params(), _prompt(length), width)
    monkeypatch.setattr(RT, "experts_use_kernel", lambda cfg, pairs: False)
    want = _serve(cfg, _params(), _prompt(length), width)
    assert np.abs(got[0] - want[0]).max() < 2e-5
    assert got[1] == want[1] and got[1][1] == length * cfg.n_active * 4
    assert got[2] == want[2]
    assert len({s[1] for s in want[2]}) > 2  # the steps decoded something


def test_a_bfloat16_prefill_on_the_kernel_stays_within_bfloat16_of_the_plain_path(
    on_the_kernel, monkeypatch
):
    """bf16 leaves and activations, as served: both paths round the same
    values at the same places (the ReLU product, the layer's result), so
    they differ where the order of a float32 sum moved a bf16 rounding or
    a router's near-tie. Read: largest 0.0 and 0.020, mean 0.0 and 0.0044
    over logits of spread 1 (prompts of 100 and 128); a changed mechanism
    (tests/test_decoder_kinds.py) moves the largest by 2.3-4.9."""
    cfg = FAMILY.program_config(KEYS, jnp.bfloat16)
    params = _params(jnp.bfloat16)
    got = _serve(cfg, params, _prompt(128), 128, n_steps=0)
    monkeypatch.setattr(RT, "experts_use_kernel", lambda cfg, pairs: False)
    want = _serve(cfg, params, _prompt(128), 128, n_steps=0)
    assert np.abs(got[0] - want[0]).mean() < 0.02
    assert np.abs(got[0] - want[0]).max() < 0.2
    assert got[1][1:] == want[1][1:]  # the counters


# ---------------------------------------------------------------- the rule

ST = dict(
    vocab_size=64, d_model=2560, n_heads=28, n_kv_heads=4, head_size=128,
    n_layers=1, d_ff=768, n_experts=64, n_active=6, max_len=16384,
    layers=(LayerSpec(ff="experts"),),
)


@pytest.mark.parametrize("keys, width, backend, want", [
    (ST, 10240, "tpu", True),  # the second cell's prefill: 960 pairs an expert
    (ST, 1408, "tpu", True),  # 132 an expert
    (ST, 1280, "tpu", False),  # 120 an expert: too few to fill a block
    (ST, 8, "tpu", False),  # a decode step's pairs
    ({**ST, "max_len": 65536}, 32768, "tpu", True),
    ({**ST, "max_len": 65536}, 40960, "tpu", False),  # the indices' room
    (ST, 10240, "cpu", False),
    ({**ST, "d_ff": 800}, 10240, "tpu", False),  # no multiple of 128 lanes
    ({**ST, "fused_attention": False}, 10240, "tpu", False),  # a mesh
    ({**ST, "layers": (LayerSpec(),), "n_experts": 0, "n_active": 0}, 10240,
     "tpu", False),  # a dense block has no such layer
])
def test_the_rule_that_chooses_the_experts_path(keys, width, backend, want, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = lm_config(**keys)
    assert RT.prefill_experts_use_kernel(cfg, width) is want
