"""The decoder's per-layer kinds (models/config.py `LayerSpec`): routed
ReGLU experts with the router on the layer's input, grouped-query heads,
window layers whose cache rows are a ring, rotary and no positions, an
untied head. Served through the slot cache (`prefill_into_slot`'s and
`decode_step_slots`' cores) and compared with the benchmark's plain float32
reference of the block (bench/families/smallthinker.py `decoder_logits`: no
cache, no ring, weights drawn again from the seed), at tiny sizes:
d 64, 4 query heads over 2 key/value heads of 16, 8 experts of width 32
with 2 active, window 8, 8 layers [global NoPE, window rotary x 3] x 2.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import LayerSpec, TransformerConfig, lm_config
from pathway_tpu.models import encoder as EN
from pathway_tpu.models import routed as RT
from pathway_tpu.models import transformer as T
from pathway_tpu.models.mixers import softmax as SM
from pathway_tpu.ops import attention as A
from pathway_tpu.ops import rowwise as R

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))
from pwbench import spec  # noqa: E402

FAMILY = spec.family("smallthinker")
SEED = 5
WINDOW = 8
KEYS = dict(
    vocab_size=256, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_hidden_layers=8,
    moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=2, rope_theta=1.5e6,
    sliding_window_size=WINDOW, rope_layout=[0, 1, 1, 1] * 2,
    sliding_window_layout=[0, 1, 1, 1] * 2, max_position_embeddings=64,
    tie_word_embeddings=False,
)
SIZES = FAMILY.sizes(KEYS)
# the same decoder with heads of 128 lanes and room for a bucket of 256:
# what the prefill's attention kernel (ops/attention.py) takes
KERNEL_KEYS = {**KEYS, "head_dim": 128, "max_position_embeddings": 320}
KERNEL_SIZES = FAMILY.sizes(KERNEL_KEYS)
N_STEPS = 20  # from any of the prompts below, the ring wraps at least twice


@functools.lru_cache(maxsize=None)
def _params(kernel: bool = False):
    return FAMILY.make_params(SEED, KERNEL_SIZES if kernel else SIZES)


def _prompt(length: int) -> list[int]:
    return np.random.default_rng(length).integers(2, 256, length).tolist()


def _left_padded(row: list[int], width: int):
    """The prompt at the end of a row `width` wide, and its mask."""
    ids = np.zeros((1, width), np.int32)
    mask = np.zeros((1, width), np.int32)
    ids[0, width - len(row):], mask[0, width - len(row):] = row, 1
    return ids, mask


def _served_logits(cfg, row: list[int], width: int, slot: int = 1, slots: int = 3):
    """The program's logits at the prompt's last position and after each of
    N_STEPS greedy steps, through a slot cache: the prompt left-padded to
    `width`, prefilled into a scratch row, scattered into `slot`, decoded
    with the neighbouring slots free. Returns (logits, the row decoded)."""
    params = _params(kernel=cfg.head_dim == 128)
    ids, mask = _left_padded(row, width)
    lg, mini, _ = T._prefill(
        params, jnp.asarray(ids), T.init_kv_cache(cfg, 1), cfg, jnp.asarray(mask)
    )
    cache = T.init_kv_cache(cfg, slots)
    for name in mini:
        cache[name] = jax.lax.dynamic_update_slice(
            cache[name], mini[name], (0, slot, 0, 0, 0)
        )
    step = jax.jit(functools.partial(T._step_rows, cfg=cfg))
    got, toks = [np.asarray(lg[0], np.float32)], list(row)
    for i in range(N_STEPS):
        toks.append(int(got[-1].argmax()))
        tok, pos, pad = (np.zeros(slots, np.int32) for _ in range(3))
        tok[slot], pos[slot], pad[slot] = toks[-1], width + i, width - len(row)
        lg, cache, _ = step(params, cache, jnp.asarray(tok), jnp.asarray(pos),
                            jnp.asarray(pad))
        got.append(np.asarray(lg[slot], np.float32))
    return np.stack(got), toks


def _reference_logits(toks: list[int], n_prompt: int, sizes: dict = SIZES):
    at = range(n_prompt - 1, len(toks))
    return FAMILY.decoder_logits(SEED, sizes, [toks], [at], 64)[0]


# prompts shorter than, as long as and longer than the window, each
# left-padded to a bucket; with 20 steps behind it the ring has wrapped
PROMPTS = [(5, 8), (8, 8), (21, 32)]
# and through the prefill's attention kernel: buckets of one tile and of
# two, a prompt that fills its bucket, one whose padding is most of a tile
KERNEL_PROMPTS = [(100, 128), (128, 128), (150, 256)]


@pytest.mark.parametrize("length, width", PROMPTS)
def test_slot_cache_matches_the_plain_reference_in_float32(length, width):
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    got, toks = _served_logits(cfg, _prompt(length), width)
    assert width + N_STEPS > 2 * WINDOW  # the ring wrapped
    want = _reference_logits(toks, length)
    assert np.abs(got - want).max() < 1e-4


@pytest.mark.parametrize("length, width", KERNEL_PROMPTS)
def test_slot_cache_through_the_prefill_kernel_matches_the_plain_reference(
    length, width, monkeypatch
):
    """`_prefill` with the rule saying kernel (as on a TPU; interpreted
    here, tiles of 128): every layer kind's attention, the window layers'
    rotary positions through ops/rowwise.py `rowwise_heads` (the rotary
    part alone: this family has no q/k norm), the ring's write and the
    steps behind it against the family's reference, which has no kernel, no
    cache and no ring."""
    monkeypatch.setattr(SM, "prefill_uses_kernel", lambda cfg, p: True)
    monkeypatch.setattr(
        A, "prefill_attention",
        functools.partial(A.prefill_attention, interpret=True),
    )
    rotated, rowwise = [], R.rowwise_heads
    monkeypatch.setattr(
        R, "rowwise_heads",
        lambda x, scale, rope, live, **kw: rotated.append((scale, live))
        or rowwise(x, scale, rope, live, interpret=True, **kw),
    )
    monkeypatch.setattr(A, "_PREFILL_TILE_MAX", 128)
    cfg = FAMILY.program_config(KERNEL_KEYS, jnp.float32)
    assert SM.rowwise_uses_kernel(cfg, width)
    got, toks = _served_logits(cfg, _prompt(length), width)
    # q and k of the six window layers, and of no global one; no norm, no zero
    assert rotated and len(rotated) % (2 * 6) == 0
    assert all(scale is None and live is None for scale, live in rotated)
    at = range(length - 1, len(toks))
    want = FAMILY.decoder_logits(SEED, KERNEL_SIZES, [toks], [at], 512)[0]
    assert np.abs(got - want).max() < 1e-4


def _slot_tokens(cfg, rows: list[list[int]], width: int, n_steps: int):
    """`prefill_into_slot` of each prompt into every other slot of a pool
    (a free slot between two live ones), then `n_steps` `decode_step_slots`
    over the pool: the tokens each live slot decoded."""
    params = _params(kernel=True)
    slots = 2 * len(rows) - 1
    cache = T.init_kv_cache(cfg, slots)
    tok, pos, pad = (np.zeros(slots, np.int32) for _ in range(3))
    for i, row in enumerate(rows):
        ids, mask = _left_padded(row, width)
        first, cache = T.prefill_into_slot(
            params, jnp.asarray(ids), jnp.asarray(mask), cache,
            jnp.asarray(2 * i), cfg,
        )
        tok[2 * i], pos[2 * i], pad[2 * i] = int(first[0]), width, width - len(row)
    step = jax.jit(functools.partial(T.decode_step_slots, cfg=cfg))
    out = [tok.copy()]
    for _ in range(n_steps):
        nxt, cache = step(
            params, cache, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(pad)
        )
        tok = np.where(pos > 0, np.asarray(nxt)[:slots], 0).astype(np.int32)
        pos = np.where(pos > 0, pos + 1, 0).astype(np.int32)
        out.append(tok.copy())
    return np.stack(out)[:, ::2]


def test_the_steps_through_the_decode_kernel_serve_the_plain_paths_tokens(
    monkeypatch,
):
    """`decode_step_slots` with the rule saying kernel (as on a TPU;
    interpreted here): both layer kinds' attention and row write through
    ops/attention.py `decode_attention`, 32 steps behind two prefills, the
    ring of 8 wrapping four times: the tokens of the plain path."""
    cfg = FAMILY.program_config(KERNEL_KEYS, jnp.float32)
    rows = [_prompt(100), _prompt(21)]
    want = _slot_tokens(cfg, rows, 128, 32)
    monkeypatch.setattr(SM, "step_uses_kernel", lambda cfg: True)
    monkeypatch.setattr(
        A, "decode_attention",
        functools.partial(A.decode_attention, interpret=True),
    )
    got = _slot_tokens(cfg, rows, 128, 32)
    assert want.shape == (33, 2) and len(set(want[:, 0].tolist())) > 4
    assert got.tolist() == want.tolist()


@pytest.mark.parametrize("length, width", PROMPTS)
def test_slot_cache_matches_the_plain_reference_in_bfloat16(length, width):
    """bf16 activations against the float32 reference. The tolerance of
    bf16, stated: over logits of unit spread the mean difference stays
    under 0.1 and the largest under 1.0 (read: 0.017-0.049 and 0.09-0.68;
    where a near-tie of the router falls the other way a position moves
    by tenths); a changed mechanism moves the largest by 2.3-4.9."""
    cfg = FAMILY.program_config(KEYS, jnp.bfloat16)
    row = _prompt(length)
    # the reference is conditioned on the tokens the float32 program emits
    _, toks = _served_logits(FAMILY.program_config(KEYS, jnp.float32), row, width)
    params = _params()
    ids, mask = _left_padded(row, width)
    lg, cache, _ = T._prefill(
        params, jnp.asarray(ids), T.init_kv_cache(cfg, 1), cfg, jnp.asarray(mask)
    )
    got = [np.asarray(lg[0], np.float32)]
    for i, tok in enumerate(toks[length:-1]):
        lg, cache, _ = T._step_rows(
            params, cache, jnp.asarray([tok], jnp.int32),
            jnp.asarray([width + i], jnp.int32),
            jnp.asarray([width - length], jnp.int32), cfg,
        )
        got.append(np.asarray(lg[0], np.float32))
    want = _reference_logits(toks[:-1], length)
    err = np.abs(np.stack(got) - want)
    assert err.mean() < 0.1 and err.max() < 1.0


def _variant(monkeypatch, name: str) -> dict:
    """The reference with one mechanism changed."""
    if name == "window_off":
        return {**SIZES, "window_layout": (0,) * 8}
    if name == "rotary_in_a_nope_layer":
        return {**SIZES, "rope_layout": (1,) * 8}
    route = FAMILY._route
    if name == "router_after_the_norm":
        monkeypatch.setattr(
            FAMILY, "_route",
            lambda x, hline, w, sz, fp8: route(hline, hline, w, sz, fp8),
        )
    elif name == "one_chosen_expert_dropped":
        def dropped(x, hline, w, sz, fp8):
            idx, wts = route(x, hline, w, sz, fp8)
            return idx, wts.at[:, -1].set(0.0)

        monkeypatch.setattr(FAMILY, "_route", dropped)
    FAMILY._layer_fn.cache_clear()  # the jitted layers hold the old router
    return dict(SIZES)


@pytest.mark.parametrize("name", [
    "window_off", "rotary_in_a_nope_layer", "router_after_the_norm",
    "one_chosen_expert_dropped",
])
def test_the_comparison_sees_each_mechanism(name, monkeypatch):
    """The reference with one mechanism changed differs from the program by
    far more than the tolerance the true reference is held to."""
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    got, toks = _served_logits(cfg, _prompt(21), 32)
    try:
        want = _reference_logits(toks, 21, _variant(monkeypatch, name))
    finally:
        monkeypatch.undo()
        FAMILY._layer_fn.cache_clear()
    assert np.abs(got - want).max() > 100 * 1e-4


def test_every_token_to_one_expert_loses_none():
    """No capacity: all 24 tokens choose experts 0 and 1, and every pair is
    multiplied by its expert."""
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    block = jax.tree.map(lambda x: x.astype(jnp.float32), _params()["blocks"][0])
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 64), jnp.float32)
    idx = jnp.broadcast_to(jnp.asarray([0, 1]), (2, 12, 2))
    w = jnp.broadcast_to(jnp.asarray([0.25, 0.75]), (2, 12, 2))
    live = jnp.ones((2, 12), bool).at[0, :3].set(False)
    y, counts = RT.experts(u, idx, w, live, block, cfg)
    want = sum(
        share * (
            jax.nn.relu(u @ block["expert_gate"][e]) * (u @ block["expert_up"][e])
        ) @ block["expert_down"][e]
        for e, share in ((0, 0.25), (1, 0.75))
    )
    assert np.abs(np.asarray(y - want)).max() < 1e-5
    assert counts.tolist() == [21, 21, 0, 0, 0, 0, 0, 0]  # the live tokens'


class _Ids:
    """Prompts are written as token ids."""

    def tokenize(self, prompt: str) -> list[int]:
        return [int(t) for t in prompt.split()]


def _batcher(cfg, n_steps=12, n_slots=2):
    from pathway_tpu.engine.device_plane import DevicePlane
    from pathway_tpu.serving.continuous_batching import ContinuousBatcher

    return ContinuousBatcher(
        params=_params(), cfg=cfg, tokenizer=_Ids(), n_steps=n_steps,
        n_slots=n_slots, plane=DevicePlane(),
    )


def _text(row: list[int]) -> str:
    return " ".join(str(t) for t in row)


def test_two_requests_in_neighbouring_slots_equal_each_alone():
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    short, long_ = _prompt(6), _prompt(21)
    alone = []
    for row in (short, long_):
        cb = _batcher(cfg)
        alone.append(cb.submit(_text(row)).result(timeout=120))
        cb.close()
    cb = _batcher(cfg)
    both = [cb.submit(_text(row)) for row in (short, long_)]
    assert [f.result(timeout=120) for f in both] == alone
    cb.close()


def test_the_counters_add_up():
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    cb = _batcher(cfg, n_steps=6)
    rows = [_prompt(n) for n in (6, 21, 9)]
    for f in [cb.submit(_text(row)) for row in rows]:
        f.result(timeout=120)
    cb.drain()
    st = dict(cb.stats)
    cb.close()
    layers, active = 8, 2
    assert st["prompt_tokens"] == 36
    assert st["routed_pairs"] == 36 * active * layers  # padding is not counted
    # the fullest expert of a layer holds its share at least, at most all
    assert st["routed_pairs"] / 8 <= st["expert_load_max"] <= 36 * layers
    assert st["moe_layers_run"] == st["decode_steps"] * layers
    assert active * st["moe_layers_run"] <= st["experts_touched"]
    assert st["experts_touched"] <= 2 * active * st["moe_layers_run"]  # 2 slots


def test_a_block_without_experts_counts_nothing_and_returns_tokens_alone():
    cfg = lm_config(vocab_size=256, d_model=16, n_heads=2, n_layers=1, d_ff=32,
                    max_len=64)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    ids = jnp.ones((1, 8), jnp.int32)
    first, cache = T.prefill_into_slot(
        params, ids, ids, T.init_kv_cache(cfg, 2), jnp.asarray(0), cfg
    )
    zeros = jnp.zeros((2,), jnp.int32)
    nxt, cache = T.decode_step_slots(params, cache, zeros, zeros + 8, zeros, cfg)
    assert first.shape == (1,) and nxt.shape == (2,)
    assert sorted(cache) == ["k", "v"] and cache["k"].shape == (1, 2, 2, 64, 8)


def test_an_experts_decoder_sends_its_counters_behind_the_tokens():
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    ids = jnp.asarray([_prompt(8)], jnp.int32)
    first, cache = T.prefill_into_slot(
        _params(), ids, jnp.ones_like(ids), T.init_kv_cache(cfg, 2),
        jnp.asarray(1), cfg,
    )
    assert first.shape == (1 + len(T.prefill_counters(cfg)),)
    assert int(first[1]) == 8 * 2 * 8
    assert cache["k"].shape == (2, 2, 2, 64, 16)  # global layers: every row
    assert cache["k_win"].shape == (6, 2, 2, WINDOW, 16)  # window layers: a ring
    tok = jnp.asarray([0, int(first[0])], jnp.int32)
    nxt, _ = T.decode_step_slots(
        _params(), cache, tok, jnp.asarray([0, 8], jnp.int32),
        jnp.zeros((2,), jnp.int32), cfg,
    )
    assert nxt.shape == (2 + len(T.step_counters(cfg)),)
    assert int(nxt[3]) == 8 and 2 * 8 == int(nxt[2])  # one live row: 2 a layer


def test_the_wave_aligned_path_serves_the_same_tokens():
    """`generate_serving` (sampled generation's path and the slot path's
    oracle) runs the same kinds: its tokens are the slot path's."""
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    row = _prompt(21)
    _, toks = _served_logits(cfg, row, 32)
    ids, mask = _left_padded(row, 32)
    out = T.generate(
        _params(), jnp.asarray(ids), N_STEPS, cfg, prompt_mask=jnp.asarray(mask)
    )
    assert np.asarray(out)[0, 32:].tolist() == toks[21:]


# tokens of the parent commit (13fe5b1) for this configuration, seed and
# prompt, through `generate` on the CPU: the default list is the plain block
PARENT_TOKENS = [
    [492, 347, 413, 492, 475, 492, 217, 217],
    [492, 279, 41, 41, 41, 492, 279, 279],
    [439, 218, 218, 439, 413, 492, 186, 439],
    [35, 71, 11, 71, 413, 46, 46, 190],
    [356, 356, 356, 356, 356, 510, 413, 190],
    [356, 413, 413, 413, 413, 413, 413, 413],
    [434, 413, 374, 356, 356, 413, 413, 413],
    [347, 434, 356, 434, 434, 413, 413, 356],
]


def test_the_default_configuration_serves_the_parents_tokens():
    cfg = lm_config(vocab_size=512, d_model=64, n_heads=4, n_layers=3, d_ff=128,
                    max_len=128, dtype=jnp.bfloat16)
    assert cfg.plain and cfg.layer_specs == (LayerSpec(),) * 3
    params = T.cast_params(T.init_params(jax.random.PRNGKey(0), cfg))
    ids = np.random.default_rng(0).integers(1, 512, (8, 32)).astype(np.int32)
    mask = np.ones((8, 32), np.int32)
    mask[:4, :10] = 0
    out = T.generate(params, jnp.asarray(ids), 8, cfg, prompt_mask=jnp.asarray(mask))
    assert np.asarray(out)[:, 32:].tolist() == PARENT_TOKENS


@pytest.mark.parametrize("kw, message", [
    (dict(n_kv_heads=3), "divisible by n_kv_heads"),
    (dict(layers=(LayerSpec(),)), "lists 1 layers"),
    (dict(layers=(LayerSpec(window=4), LayerSpec(window=8))), "share one window"),
    (dict(layers=(LayerSpec(ff="experts"),) * 2), "n_active"),
    (dict(layers=(LayerSpec(pos="alibi"),) * 2), "learned|rotary|none"),
    (dict(causal=False, tie_embeddings=False), "plain block only"),
])
def test_a_configuration_that_cannot_be_served_is_refused(kw, message):
    base = dict(vocab_size=64, d_model=16, n_heads=4, n_layers=2, d_ff=32,
                max_len=32, causal=True)
    with pytest.raises(ValueError, match=message.replace("|", r"\|")):
        TransformerConfig(**{**base, **kw})


def test_forward_refuses_what_it_does_not_run():
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    with pytest.raises(NotImplementedError):
        EN.forward(_params(), jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4)), cfg)
    assert dataclasses.replace(cfg, layers=None, n_kv_heads=None, head_size=None,
                               tie_embeddings=True).plain
