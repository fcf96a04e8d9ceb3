"""The `sparse` and `linear` mixers of the decoder (models/mixers/,
`LayerSpec.mixer`, `SparseSpec`), the SwiGLU feed-forward, the norms and
gates around a mixer and MiniCPM's three scalings, served through the slot
cache (`prefill_into_slot`'s and `decode_step_slots`' cores, and the
`ContinuousBatcher`) and compared with the benchmark's plain float32
reference of the block (bench/families/minicpm_sala.py `decoder_logits`: no
cache, no state, no chunks, the selection by a ranking; weights drawn again
from the seed), at tiny sizes: d 64, 4 query heads over 2 key/value heads of
16, 4 linear heads, feed-forward 128, layers [minicpm4, lightning-attn x 3],
blocks of 8 positions, pooled keys of 4 every 2, 2 local blocks, top-5,
dense up to 32 positions.

(a) the system against the reference, logits, under and over `dense_len`;
(b) a slot taken again by the batcher serves what a fresh pool serves;
(c) the chunked scan is the recurrence, and a step is one more position;
(d) the two kernels, interpreted, against the `jax.numpy` formulas;
(e) what the selection always takes, how much, and for whom, and the
    selection's kernel (ops/sparse_attention.py `sparse_select`),
    interpreted, choosing `select_blocks`' set element for element;
(f) the row-wise pass over q and k (ops/rowwise.py) and the scan kernel's
    output norm round where the program a TPU runs today rounds (`rmsnorm`,
    `rope` and `_linear_out` with float32 between the first two), at the
    positions the third cell has; a table or a position in bf16 does not,
    nor does a second rounding between the norm and the rotation.
"""

from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import config as CF
from pathway_tpu.models import encoder as EN
from pathway_tpu.models import layers as LY
from pathway_tpu.models import transformer as T
from pathway_tpu.models.mixers import linear as LIN
from pathway_tpu.models.mixers import softmax as SM
from pathway_tpu.models.mixers import sparse as SPA
from pathway_tpu.ops import attention as A
from pathway_tpu.ops import linear_attention as L
from pathway_tpu.ops import rowwise as R
from pathway_tpu.ops import sparse_attention as S

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))
from pwbench import spec  # noqa: E402

FAMILY = spec.family("minicpm_sala")
SEED = 5
DENSE_LEN = 32
KEYS = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    num_hidden_layers=4,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"],
    rope_theta=10000, max_position_embeddings=256, scale_emb=12,
    scale_depth=1.4, dim_model_base=16, mup_denominator=32,
    published={"num_hidden_layers": 32},
    sparse_config=dict(
        topk=5, block_size=8, kernel_size=4, kernel_stride=2, init_blocks=1,
        window_size=16, dense_len=DENSE_LEN,
    ),
    hidden_act="silu", attention_bias=False, attn_use_rope=False,
    tie_word_embeddings=False, qk_norm=True, use_output_gate=True,
    use_output_norm=True, attn_use_output_gate=True, lightning_use_rope=True,
)
SIZES = FAMILY.sizes(KEYS)
# the same decoder with heads of 128 lanes: what the kernels take
KERNEL_KEYS = {**KEYS, "head_dim": 128, "lightning_head_dim": 128}
KERNEL_SIZES = FAMILY.sizes(KERNEL_KEYS)
N_STEPS = 12
SQ = FAMILY.program_config(KEYS, jnp.float32).sparse


@functools.lru_cache(maxsize=None)
def _params(kernel: bool = False):
    return FAMILY.make_params(SEED, KERNEL_SIZES if kernel else SIZES)


def _prompt(length: int) -> list[int]:
    return np.random.default_rng(length).integers(2, 256, length).tolist()


def _left_padded(row: list[int], width: int):
    ids = np.zeros((1, width), np.int32)
    mask = np.zeros((1, width), np.int32)
    ids[0, width - len(row):], mask[0, width - len(row):] = row, 1
    return ids, mask


def _served_logits(cfg, row: list[int], width: int, slot: int = 1, slots: int = 3):
    """The program's logits at the prompt's last position and after each of
    N_STEPS greedy steps, through a slot cache: the prompt left-padded to
    `width`, prefilled into the slot by `prefill_into_slot`'s core and its
    scatter, decoded by `decode_step_slots`' core with the neighbouring
    slots free. Returns (logits, the row decoded)."""
    params = _params(kernel=cfg.head_dim == 128)
    ids, mask = _left_padded(row, width)
    lg, mini, _ = T._prefill(
        params, jnp.asarray(ids), T.init_kv_cache(cfg, 1), cfg, jnp.asarray(mask)
    )
    first, cache = T.prefill_into_slot(
        params, jnp.asarray(ids), jnp.asarray(mask), T.init_kv_cache(cfg, slots),
        jnp.asarray(slot), cfg,
    )
    assert int(first[0]) == int(lg[0].argmax())
    for name, leaf in mini.items():  # the scatter put the scratch row there
        assert jnp.array_equal(cache[name][:, slot], leaf[:, 0]), name
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
    return FAMILY.decoder_logits(SEED, sizes, [toks], [at], 512)[0]


# ------------------------------------------- (a) against the reference

# under dense_len and staying there; under it and crossing it while
# decoding; over it (5 blocks of 7 to 14 chosen); a prompt that fills its rung
PROMPTS = [(9, 16), (27, 32), (60, 64), (101, 128), (128, 128)]


@pytest.mark.parametrize("length, width", PROMPTS)
def test_slot_cache_matches_the_plain_reference_in_float32(length, width):
    """float32 activations: the same function in another order of sums
    (chunks and a carried state against the sum over j written out, a
    threshold-free top-k against a ranking), so 1e-4 over logits of unit
    spread; a block chosen otherwise would show as 1e-2 or more."""
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    got, toks = _served_logits(cfg, _prompt(length), width)
    want = _reference_logits(toks, length)
    assert 0.5 < want.std() < 2.0
    assert np.abs(got - want).max() < 1e-4


@pytest.mark.parametrize("length, width", [(27, 32), (101, 128)])
def test_slot_cache_matches_the_plain_reference_in_bfloat16(length, width):
    """bf16 activations against the float32 reference, the tolerance of
    bf16 stated: over logits of unit spread the mean difference stays under
    0.05 and the largest under 0.5 (read: 0.005-0.008 and 0.02-0.2; a near
    tie between two blocks may fall the other way in bf16, which moves a few
    logits by a tenth)."""
    cfg = FAMILY.program_config(KEYS, jnp.bfloat16)
    got, toks = _served_logits(cfg, _prompt(length), width)
    diff = np.abs(got - _reference_logits(toks, length))
    assert diff.mean() < 0.05 and diff.max() < 0.5


def _interpret_the_prefill_kernels(monkeypatch):
    """The rule says kernel, as on a TPU, and every kernel a prefill then
    takes is interpreted (tiles of 128)."""
    monkeypatch.setattr(SM, "prefill_uses_kernel", lambda cfg, p: True)
    for module, name in (
        (A, "prefill_attention"), (L, "linear_prefill_attention"),
        (S, "sparse_prefill_attention"), (S, "sparse_select"), (R, "rowwise_heads"),
    ):
        monkeypatch.setattr(
            module, name, functools.partial(getattr(module, name), interpret=True)
        )
    monkeypatch.setattr(A, "_PREFILL_TILE_MAX", 128)


@pytest.mark.parametrize("length, width", [(20, 128), (100, 128), (150, 256)])
def test_slot_cache_through_the_kernels_matches_the_plain_reference(
    length, width, monkeypatch
):
    """`_prefill` with the rule saying kernel (as on a TPU; interpreted
    here): the row-wise pass over q and k, the scan's kernel with the output
    norm in it, the selection's kernel and the selected-block kernel past
    `dense_len` and `prefill_attention` up to it, the state, rows and pooled
    keys they leave
    and the steps behind them, against the family's reference."""
    _interpret_the_prefill_kernels(monkeypatch)
    cfg = FAMILY.program_config(
        {**KERNEL_KEYS, "sparse_config": {**KEYS["sparse_config"], "dense_len": 64}},
        jnp.float32,
    )
    assert LIN.linear_prefill_uses_kernel(cfg, width)
    assert SPA.sparse_prefill_uses_kernel(cfg, width)
    assert SM.rowwise_uses_kernel(cfg, width)
    sizes = FAMILY.sizes(
        {**KERNEL_KEYS, "sparse_config": {**KEYS["sparse_config"], "dense_len": 64}}
    )
    got, toks = _served_logits(cfg, _prompt(length), width)
    want = _reference_logits(toks, length, sizes)
    assert np.abs(got - want).max() < 1e-4


def test_the_slot_programs_send_the_mixers_counters_behind_their_tokens():
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    row, width, slots = _prompt(60), 64, 2
    ids, mask = _left_padded(row, width)
    first, cache = T.prefill_into_slot(
        _params(), jnp.asarray(ids), jnp.asarray(mask), T.init_kv_cache(cfg, slots),
        jnp.asarray(1), cfg,
    )
    assert T.prefill_counters(cfg) == T.step_counters(cfg) == (
        "sparse_blocks_read", "sparse_blocks_visible", "linear_tokens"
    )
    assert first.shape == (1 + 3,)
    blocks = [min(t // 8 + 1, 5) for t in range(60)]
    visible = [t // 8 + 1 for t in range(60)]
    assert first[1:].tolist() == [2 * sum(blocks), 2 * sum(visible), 3 * 60]
    tok = np.asarray([0, int(first[0])], np.int32)
    nxt, cache = T.decode_step_slots(
        _params(), cache, jnp.asarray(tok), jnp.asarray([0, width], jnp.int32),
        jnp.asarray([0, width - 60], jnp.int32), cfg,
    )
    # the one occupied row, at logical position 60: 5 of 8 blocks, a key head
    assert nxt.shape == (slots + 3,)
    assert nxt[slots:].tolist() == [2 * 5, 2 * 8, 0]
    # a free row's state and rows stay as they were: inert
    assert not np.asarray(cache["state"][:, 0]).any()


def test_the_cache_has_a_leaf_for_each_kind_and_no_other():
    cfg = FAMILY.program_config(KEYS, jnp.bfloat16)
    cache = T.init_kv_cache(cfg, 3)
    assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
        "k_sparse": ((1, 3, 2, 256, 16), "bfloat16"),
        "v_sparse": ((1, 3, 2, 256, 16), "bfloat16"),
        "k_pool": ((1, 3, 2, 128, 16), "bfloat16"),
        "state": ((3, 3, 4, 16, 16), "float32"),
    }
    rows = T._cache_rows(cfg)
    assert rows[0] == (SPA.SPARSE, 0)
    assert [r for r in rows[1:]] == [(LIN.LINEAR, i) for i in range(3)]


@pytest.mark.parametrize("kw, message", [
    (dict(sparse=None), "sparse layers need"),
    (dict(linear_slopes=(0.5,)), "a slope for each linear head"),
    (dict(max_len=250), "sparse:"),
])
def test_a_configuration_that_cannot_be_served_is_refused(kw, message):
    import dataclasses

    cfg = FAMILY.program_config(KEYS, jnp.float32)
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(cfg, **kw)


def test_forward_refuses_the_new_kinds():
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    assert not cfg.plain
    with pytest.raises(NotImplementedError):
        EN.forward(_params(), jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32), cfg)


# ------------------------------------------------ (b) a slot taken again


def _batcher_tokens(prompts: list[str], n_slots: int):
    from pathway_tpu.xpacks.llm.llms import JaxLMChat

    chat = JaxLMChat(
        config=FAMILY.program_config(KEYS, jnp.float32), params=_params(),
        max_new_tokens=6, decode_slots=n_slots,
    )
    futures = [chat._cb.submit(p) for p in prompts]
    out = [f.result(timeout=300) for f in futures]
    chat._cb.drain()
    stats = dict(chat._cb.stats)
    chat._finalizer()
    return out, stats


def test_a_slot_taken_again_serves_what_a_fresh_pool_serves():
    """One slot, four requests queued at once, so that every prefill but the
    first goes into a slot whose last request's steps are still dispatched
    ahead of it: a long prompt's rows, pooled keys and states must not reach
    the short prompt behind it (which stays under `dense_len` in its prefill
    and passes it while decoding, so it reads pooled keys at positions the
    long prompt wrote)."""
    words = [f"w{i}" for i in range(200)]
    prompts = [
        " ".join(words[:90]), " ".join(words[100:128]), " ".join(words[40:160]),
        " ".join(words[5:20]),
    ]
    served, stats = _batcher_tokens(prompts, n_slots=1)
    assert stats["prefills"] == 4 and stats["dispatched_ahead"] > 0
    assert stats["linear_tokens"] == 3 * stats["prompt_tokens"]
    assert 0 < stats["sparse_blocks_read"] < stats["sparse_blocks_visible"]
    for prompt, got in zip(prompts, served):
        (alone,), _ = _batcher_tokens([prompt], n_slots=2)
        assert got == alone


# ------------------------------------ (c) the scan and the recurrence


def _qkv(b, p, h, dh, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, p, h, dh)), dtype) for _ in range(3)
    )


SLOPES = jnp.asarray([2.0 ** (-8 * h / 4) for h in range(1, 5)], jnp.float32)


def _recurrence(q, k, v, slopes):
    b, p, h, dh = q.shape
    state, outs = jnp.zeros((b, h, dh, dh), jnp.float32), []
    for t in range(p):
        out, state = LIN.linear_step(q[:, t], k[:, t], v[:, t], state, slopes)
        outs.append(out)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("p, chunk", [(64, 16), (50, 16), (7, 16), (96, 32)])
def test_the_chunked_scan_is_the_recurrence_token_by_token(p, chunk):
    """float32 inputs: the decay-masked product of a chunk and the carried
    state are the same sums as one position after another (1e-4 of values
    of a few units; a width the chunk does not divide is padded in front)."""
    q, k, v = _qkv(2, p, 4, 16, seed=p)
    out, state = LIN.linear_scan(q, k, v, SLOPES, chunk)
    want, want_state = _recurrence(q, k, v, SLOPES)
    assert np.abs(np.asarray(out - want)).max() < 1e-4
    assert np.abs(np.asarray(state - want_state)).max() < 1e-4


def test_a_step_is_one_more_position_of_the_scan():
    q, k, v = _qkv(2, 33, 4, 16, seed=3)
    out, state = LIN.linear_scan(q, k, v, SLOPES, 8)
    _, before = LIN.linear_scan(q[:, :32], k[:, :32], v[:, :32], SLOPES, 8)
    one, after = LIN.linear_step(q[:, 32], k[:, 32], v[:, 32], before, SLOPES)
    assert np.abs(np.asarray(one - out[:, 32])).max() < 1e-5
    assert np.abs(np.asarray(after - state)).max() < 1e-5


def test_pads_in_front_add_nothing_and_decay_nothing():
    q, k, v = _qkv(1, 40, 4, 16, seed=4)
    pad = 24
    front = lambda a, fill: jnp.concatenate(  # noqa: E731
        [jnp.full((1, pad) + a.shape[2:], fill, a.dtype), a], axis=1
    )
    out, state = LIN.linear_scan(q, k, v, SLOPES, 16)
    # a pad's key is zeroed by the layer; its query and value are anything
    padded, padded_state = LIN.linear_scan(
        front(q, 3.0), front(k, 0.0), front(v, -2.0), SLOPES, 16
    )
    assert np.abs(np.asarray(padded[:, pad:] - out)).max() < 1e-5
    assert np.abs(np.asarray(padded_state - state)).max() < 1e-5


# -------------------------------------------------- (d) the two kernels


@pytest.mark.parametrize("p, chunk, pad", [(256, 128, 0), (256, 128, 37), (200, 64, 11)])
def test_the_scan_kernel_matches_the_jnp_scan(p, chunk, pad):
    """Interpreted, bf16 inputs, heads of 128: the kernel against
    `linear_scan` at the same chunk (the masked pairs are rounded to bf16
    chunk by chunk in both), padded and unpadded."""
    q, k, v = _qkv(2, p, 2, 128, seed=p + pad, dtype=jnp.bfloat16)
    live = (jnp.arange(p) >= pad)[None, :, None, None]
    k = jnp.where(live, k, jnp.zeros_like(k))
    slopes = jnp.asarray([0.84, 0.0039], jnp.float32)
    want, want_state = LIN.linear_scan(q, k, v, slopes, chunk)
    got, state = L.linear_prefill_attention(q, k, v, slopes, chunk, interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    assert np.abs(np.asarray(got - want)).max() < 2e-3 * scale
    assert np.abs(np.asarray(state - want_state)).max() < 1e-4 * float(
        jnp.abs(want_state).max()
    )


def _chosen(q, k, valid, sq):
    """The blocks the sparse kind's `prefill` would hand the kernel."""
    b, p, h, dh = q.shape
    hk = k.shape[2]
    n = valid.sum(axis=1)
    turn = jax.vmap(lambda a, by: jnp.roll(a, by, axis=1))
    pooled = SPA.pool_keys(turn(k.transpose(0, 2, 1, 3), n - p), sq)
    at = jnp.where(valid > 0, jnp.cumsum(valid, axis=1) - 1, -1)
    blocks = SPA.select_blocks(
        q.reshape(b, p, hk, h // hk, dh), pooled, at, n <= sq.dense_len, sq
    )
    return blocks, at


@pytest.mark.parametrize("pads", [(0, 0), (0, 37), (130, 255)])
def test_the_selected_block_kernel_matches_the_jnp_attention(pads, monkeypatch):
    """Interpreted, bf16 inputs, 4 query heads over 2 key heads of 128,
    tiles of 128: the kernel against `attend` under the mask of the same
    chosen blocks, on the rows that are real."""
    monkeypatch.setattr(A, "_PREFILL_TILE_MAX", 128)
    b, p = 2, 256
    rng = np.random.default_rng(sum(pads))
    q = jnp.asarray(2 * rng.standard_normal((b, p, 4, 128)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, p, 2, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, p, 2, 128)), jnp.bfloat16)
    valid = (jnp.arange(p)[None, :] >= jnp.asarray(pads)[:, None]).astype(jnp.int32)
    blocks, at = _chosen(q, k, valid, SQ)
    ok = SPA._keys_of_blocks(blocks, at, SQ) & LY.build_mask(valid, causal=True)
    cfg = CF.lm_config(dtype=jnp.bfloat16)
    want = LY.attend(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), ok, cfg)
    got = S.sparse_prefill_attention(q, k, v, valid, blocks, SQ.block, interpret=True)
    diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    assert float(jnp.where(valid[:, :, None] > 0, diff, 0.0).max()) < 0.04
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    # and it is not the dense attention: most rows read fewer blocks
    dense = LY.attend(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        LY.build_mask(valid, causal=True), cfg,
    )
    assert float(jnp.abs(dense.astype(jnp.float32) - want.astype(jnp.float32)).max()) > 0.5


# ------------------------------------------------------ (e) the selection


def test_what_the_selection_always_takes_and_how_much():
    rng = np.random.default_rng(8)
    b, p, hk, g, dh = 2, 120, 2, 2, 16
    q = jnp.asarray(3 * rng.standard_normal((b, p, hk, g, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hk, p, dh)), jnp.float32)
    pooled = SPA.pool_keys(k, SQ)
    t = jnp.broadcast_to(jnp.arange(p)[None], (b, p))
    blocks = np.asarray(SPA.select_blocks(q, pooled, t, jnp.asarray([False, False]), SQ))
    assert blocks.shape == (b, hk, p, p // SQ.block)  # one set a group
    own = np.arange(p) // SQ.block
    for at in range(p):
        chosen = blocks[:, :, at]
        assert chosen[..., 0].all()  # the init block
        for back in range(SQ.local_blocks):  # the local blocks
            assert chosen[..., max(own[at] - back, 0)].all()
        assert not chosen[..., own[at] + 1:].any()  # nothing after the query
        assert (chosen.sum(-1) == min(SQ.topk, own[at] + 1)).all()
    # the key heads choose for themselves
    assert (blocks[:, 0] != blocks[:, 1]).any()
    # a dense row takes every block at or before the query
    dense = np.asarray(SPA.select_blocks(q, pooled, t, jnp.asarray([True, False]), SQ))
    assert (dense[0].sum(-1) == own + 1).all()
    assert (dense[1] == blocks[1]).all()


def test_the_selection_is_the_references():
    """The program's chosen blocks are the reference's, chosen another way
    (a ranking by a stable sort; pooled keys gathered window by window):
    equal scores, which neighbouring blocks share with the pooled key that
    reaches from one into the next, go to the lower block in both."""
    rng = np.random.default_rng(9)
    s, hk, g, dh = 150, 2, 2, 16
    q = jnp.asarray(3 * rng.standard_normal((s, hk, g, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, hk, dh)), jnp.float32)
    sp = FAMILY._sparse(SIZES)
    at, lo, hi = FAMILY._pooled_windows(sp, s)
    theirs = FAMILY._chosen_blocks(
        q, jnp.mean(k[at], axis=1), jnp.arange(s), False, sp, lo, hi, False
    )
    pooled = SPA.pool_keys(k.transpose(1, 0, 2)[None], SQ)
    ours = SPA.select_blocks(
        q[None], pooled, jnp.arange(s)[None], jnp.asarray([False]), SQ
    )[0]
    assert np.array_equal(np.asarray(ours), np.asarray(theirs))


def _selection_case(case: str):
    """(q [b, nq, kv heads, group, 128], pooled, t [b, nq], dense [b]) bf16,
    as the sparse kind's `prefill` hands a chunk to the selection: pooled keys of a
    whole prompt of 512 positions (64 blocks) from its first real token."""
    rng = np.random.default_rng(len(case))
    b, p, hk, g, dh = 2, 512, 2, 2, 128
    q = 2 * rng.standard_normal((b, p, hk, g, dh))
    k = rng.standard_normal((b, hk, p, dh))
    pads = np.zeros(b, np.int64)
    dense = [False, False]
    if case == "ties":
        # whole numbers, so that every product is exact, and one key at
        # every position: every pooled key a query sees scores alike to the
        # bit, and so does every block it sees whole
        q = rng.integers(-2, 3, (b, p, hk, g, dh))
        k = np.broadcast_to(rng.integers(-2, 3, (b, hk, 1, dh)), (b, hk, p, dh))
    if case == "left_pads":
        pads = np.asarray([37, 301])
    if case == "dense_row":
        dense = [True, False]
    t = np.arange(p)[None, :] - pads[:, None]
    t = np.where(t >= 0, t, -1)
    if case == "later_chunk":  # the third chunk of 128 queries of the prompt
        q, t = q[:, 256:384], t[:, 256:384]
    if case == "few_blocks":  # 40 queries: none sees topk blocks yet
        q, t = q[:, :40], t[:, :40]
    pooled = SPA.pool_keys(jnp.asarray(k, jnp.bfloat16), SQ)
    return (
        jnp.asarray(q, jnp.bfloat16), pooled, jnp.asarray(t, jnp.int32),
        jnp.asarray(dense),
    )


@pytest.mark.parametrize(
    "case", ["random", "left_pads", "dense_row", "later_chunk", "ties", "few_blocks"]
)
def test_the_selection_kernel_chooses_select_blocks_set(case):
    """ops/sparse_attention.py `sparse_select`, interpreted, with chunks of
    16 blocks of pooled keys (so that a query tile scores only the chunks it
    sees into): element for element `select_blocks`' set, and what the
    selection always takes and how much (the init block, the local blocks,
    nothing after the query, min(topk, blocks at or before it))."""
    q, pooled, t, dense = _selection_case(case)
    want = np.asarray(SPA.select_blocks(q, pooled, t, dense, SQ))
    got = np.asarray(
        S.sparse_select(q, pooled, t, dense, SQ, key_blocks=16, interpret=True)
    )
    assert got.shape == want.shape == (*q.shape[:1], q.shape[2], q.shape[1], 64)
    assert np.array_equal(got, want)
    t = np.asarray(t)
    for row in range(got.shape[0]):
        for i, at in enumerate(t[row]):
            chosen = got[row, :, i]
            if at < 0:  # a pad chooses nothing
                assert not chosen.any()
                continue
            own = at // SQ.block
            assert chosen[:, 0].all()
            assert chosen[:, max(own - SQ.local_blocks + 1, 0):own + 1].all()
            assert not chosen[:, own + 1:].any()
            every = bool(dense[row])
            assert (chosen.sum(-1) == (own + 1 if every else min(SQ.topk, own + 1))).all()
            if case == "ties" and own >= SQ.topk:
                # the others all tie, and the lowest of them are taken
                forced = {*range(SQ.init_blocks), *range(own - SQ.local_blocks + 1, own + 1)}
                lowest = [blk for blk in range(own) if blk not in forced]
                lowest = lowest[:SQ.topk - len(forced)]
                assert {*np.flatnonzero(chosen[0])} == forced | {*lowest}
                assert (chosen[0] == chosen[1]).all()


# ------------------------------------ the step's kernel over chosen blocks


def test_the_step_kernel_matches_the_jnp_attention_and_its_row_write():
    """Interpreted, bf16, 4 query heads over 2 key heads of 128, tiles of
    two blocks: a free slot, a dense row (every block before it, ten of
    them in five tiles) and a row past `dense_len` (6 chosen blocks), against
    `attend` under the mask of the same blocks after the indexed row write:
    the context to bf16's last place, the leaves bit for bit."""
    slots, h, hk, dh, rows = 3, 4, 2, 128, 512
    sq = T.SparseSpec(topk=6, block=16, kernel=8, stride=4, init_blocks=1,
                      window=32, dense_len=192)
    tile = S.sparse_decode_tile(sq.block, sq.topk, sq.dense_len)
    assert tile == 32
    rng = np.random.default_rng(0)
    kc, vc = (
        jnp.asarray(rng.standard_normal((2, slots, hk, rows, dh)), jnp.bfloat16)
        for _ in range(2)
    )
    q = jnp.asarray(2 * rng.standard_normal((slots, h, dh)), jnp.bfloat16)
    kn, vn = (
        jnp.asarray(rng.standard_normal((slots, hk, dh)), jnp.bfloat16)
        for _ in range(2)
    )
    t, li = jnp.asarray([0, 150, 437], jnp.int32), 1
    blocks = SPA.select_blocks(
        q.reshape(slots, 1, hk, h // hk, dh), SPA.pool_keys(kc[li], sq), t[:, None],
        t < sq.dense_len, sq,
    )
    assert blocks[:, :, 0].sum(-1).tolist() == [[1, 1], [10, 10], [6, 6]]
    r, hd = jnp.arange(slots)[:, None], jnp.arange(hk)[None, :]
    k2 = kc.at[li, r, hd, t[:, None]].set(kn)
    v2 = vc.at[li, r, hd, t[:, None]].set(vn)
    at = jnp.broadcast_to(jnp.arange(rows)[None], (slots, rows))
    ok = SPA._keys_of_blocks(blocks, at, sq) & (at <= t[:, None])[:, None, None, :]
    want = LY.attend(q[:, None], k2[li], v2[li], ok, CF.lm_config(dtype=jnp.bfloat16))[:, 0]
    got, k3, v3 = S.sparse_decode_attention(
        q, kn, vn, kc, vc, jnp.asarray(li), t, blocks[:, :, 0], block=sq.block,
        tile=tile, steps=sq.topk, interpret=True,
    )
    assert float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()) < 0.02
    assert jnp.array_equal(k2, k3) and jnp.array_equal(v2, v3)


def test_the_steps_through_the_kernels_serve_the_plain_paths_logits(monkeypatch):
    """`_step_rows` with the rule saying kernel (as on a TPU; interpreted
    here): the sparse layer's attention and row write through
    `sparse_decode_attention`, a prompt under `dense_len` that passes it
    while decoding and one past it, against the plain path's logits."""
    keys = {**KERNEL_KEYS, "sparse_config": dict(
        topk=6, block_size=16, kernel_size=8, kernel_stride=4, init_blocks=1,
        window_size=32, dense_len=96,
    )}
    cfg = FAMILY.program_config(keys, jnp.float32)
    assert not SPA.sparse_step_uses_kernel(cfg)  # this process runs on the CPU
    plain = [_served_logits(cfg, _prompt(n), w)[0] for n, w in ((90, 96), (150, 160))]
    monkeypatch.setattr(SM, "step_uses_kernel", lambda cfg: True)
    monkeypatch.setattr(
        S, "sparse_decode_attention",
        functools.partial(S.sparse_decode_attention, interpret=True),
    )
    assert SPA.sparse_step_uses_kernel(cfg)
    for want, (n, w) in zip(plain, ((90, 96), (150, 160))):
        got = _served_logits(cfg, _prompt(n), w)[0]
        assert np.abs(got - want).max() < 1e-4


# ------------------- (f) the row-wise pass and the scan kernel's output norm


def _bf16_ulps(a, b) -> np.ndarray:
    """How many bf16 values lie between each pair of elements."""
    def line(x):  # the bits, in the order of the values
        bits = np.asarray(x, jnp.bfloat16).view(np.uint16).astype(np.int32)
        return np.where(bits & 0x8000, 0x8000 - (bits & 0x7FFF), bits + 0x8000)

    return np.abs(line(a) - line(b))


def _rounds_alike(got, want) -> bool:
    """The rule of this section: the present program's result to the bf16
    bit, but for the rare element where another order of a float32 sum or a
    1-ulp float32 difference crosses a rounding boundary: at most 0.5% of
    the elements differ, none by more than one bf16 ulp. (An element of
    values of unit spread that cancelled to under 2**-12, x1 cos = x2 sin,
    holds the float32 rounding of its two terms and no bf16 one: it may
    differ by 2**-20, a bf16 ulp at 2**-12.)"""
    ulps = _bf16_ulps(got, want)
    a, b = np.asarray(got, np.float32), np.asarray(want, np.float32)
    cancelled = (np.abs(b) < 2.0 ** -12) & (np.abs(a - b) <= 2.0 ** -20)
    return bool((ulps > 0).mean() <= 0.005 and (ulps <= 1)[~cancelled].all())


# rows at the positions the third cell has: a prompt's first, the 24,576
# rung's last, the slot cache's last. A bf16 position is exact up to 256
ROW_POS = np.concatenate([
    np.arange(0, 256), np.arange(24_000, 24_576), np.arange(32_768 - 192, 32_768)
]).astype(np.int32)[None]
ROPE_CFG = CF.lm_config(vocab_size=64, d_model=512, n_heads=4, n_layers=1,
                       max_len=32_768, rope_theta=10_000.0, dtype=jnp.bfloat16)


def _product(seed: int = 0):
    """A qkv product of 4 query heads and 2 key/value heads of 128 over
    ROW_POS, bf16, the norms' scales and the rows that are live."""
    rng = np.random.default_rng(seed)
    p = ROW_POS.shape[1]
    qkv = jnp.asarray(1.5 * rng.standard_normal((1, p, 8 * 128)), jnp.bfloat16)
    q_scale = jnp.asarray(1.0 + 0.1 * rng.standard_normal(128), jnp.bfloat16)
    k_scale = jnp.asarray(1.8 + 0.1 * rng.standard_normal(128), jnp.bfloat16)
    live = jnp.asarray(rng.random((1, p)) > 0.2)
    return qkv, q_scale, k_scale, live


def _chips_chain(x, scale, rotary, live):
    """What `_layer` and the linear kind's `prefill` do to q or k [b, p,
    heads, dh] in the program XLA compiles for a TPU: `rmsnorm` and `rope` themselves,
    handed float32 so that the cast between them is none (inside a fusion
    the TPU backend drops the pair of casts: my chip run, PR 42, a third of
    the elements differ from the chain with the cast and none from this
    one), and one rounding behind them."""
    y = x.astype(jnp.float32)
    if scale is not None:
        y = LY.rmsnorm(y, scale)
    if rotary:
        y = LY.rope(y, jnp.asarray(ROW_POS), ROPE_CFG)
    y = y.astype(x.dtype)
    if live is not None:
        y = jnp.where(live[:, :, None, None], y, jnp.zeros_like(y))
    return y


@pytest.mark.parametrize("norm, rotary, zero", [
    (True, True, False), (False, True, False), (True, False, False),
    (True, True, True), (False, False, True),
])
def test_the_rowwise_pass_rounds_where_the_chips_program_rounds(norm, rotary, zero):
    """Interpreted, bf16 in and out, q (4 heads from lane 0) and k (2 heads
    behind them) out of the product as it lies, every part on and off."""
    qkv, q_scale, k_scale, live = _product()
    rope = R.rope_tables(jnp.asarray(ROW_POS), 10_000.0, 128) if rotary else None
    p = qkv.shape[1]
    for first, heads, scale in ((0, 4, q_scale), (4, 2, k_scale)):
        got = R.rowwise_heads(
            qkv, scale if norm else None, rope, live if zero else None,
            first=first, heads=heads, dh=128, interpret=True,
        )
        x = qkv[..., first * 128:(first + heads) * 128].reshape(1, p, heads, 128)
        want = _chips_chain(x, scale if norm else None, rotary, live if zero else None)
        if not (norm and rotary):  # one link: the plain functions as they are
            plain = LY.rmsnorm(x, scale) if norm else x
            plain = LY.rope(plain, jnp.asarray(ROW_POS), ROPE_CFG) if rotary else plain
            if zero:
                plain = jnp.where(live[:, :, None, None], plain, jnp.zeros_like(plain))
            assert _rounds_alike(plain, want)
        assert got.dtype == jnp.bfloat16 and got.shape == (1, p, heads * 128)
        assert _rounds_alike(got.reshape(want.shape), want), (first, heads)
        if zero:
            assert not np.asarray(got, np.float32)[~np.asarray(live)].any()


@pytest.mark.parametrize("fault", [
    "tables_in_bf16", "positions_in_bf16", "rounded_after_the_norm",
])
def test_the_rule_sees_arithmetic_that_is_not_the_present_programs(fault):
    """What a fused pass is free to get wrong and a test at 256 positions
    would not see: cosines and sines kept in bf16, positions in bf16 (exact
    up to 256 only), and the normed value rounded to bf16 before the
    rotation, which is what `_rope(_rmsnorm(x))` reads like, what a CPU
    computes from it and what PR 41 served: `logit_gap` 0.0736 of 0.05 on
    seed 1429016925, where the chip's own chain reads 0.0072 (PERF.md)."""
    qkv, q_scale, _, _ = _product(1)
    p = qkv.shape[1]
    q = qkv[..., :4 * 128].reshape(1, p, 4, 128)
    want = _chips_chain(q, q_scale, True, None)
    pos = jnp.asarray(ROW_POS)
    if fault == "rounded_after_the_norm":
        got = LY.rope(LY.rmsnorm(q, q_scale), pos, ROPE_CFG)
    else:
        if fault == "tables_in_bf16":
            rope = tuple(
                t.astype(jnp.bfloat16).astype(jnp.float32)
                for t in R.rope_tables(pos, 10_000.0, 128)
            )
        else:
            rope = R.rope_tables(pos.astype(jnp.bfloat16), 10_000.0, 128)
        got = R.rowwise_heads(
            qkv, q_scale, rope, None, first=0, heads=4, dh=128, interpret=True
        ).reshape(want.shape)
    assert not _rounds_alike(got, want)
    # and at positions a bf16 holds exactly, only the table's own rounding shows
    if fault == "positions_in_bf16":
        assert _rounds_alike(got[:, :256], want[:, :256])


@pytest.mark.parametrize("p, chunk, pad", [(256, 128, 0), (256, 128, 37), (200, 64, 11)])
def test_the_scan_kernels_output_norm_is_linear_out(p, chunk, pad):
    """Interpreted, bf16 inputs: the kernel with the norm's scale, cast as
    the linear kind's `prefill` casts it, against `_linear_out` of its own un-normed
    output (the present program) and of `linear_scan`'s (the definition), by
    this section's rule; the state is the same kernel's."""
    q, k, v = _qkv(2, p, 2, 128, seed=p + pad, dtype=jnp.bfloat16)
    k = jnp.where((jnp.arange(p) >= pad)[None, :, None, None], k, jnp.zeros_like(k))
    slopes = jnp.asarray([0.84, 0.0039], jnp.float32)
    rng = np.random.default_rng(pad)
    block = {"o_norm": jnp.asarray(1.0 + 0.1 * rng.standard_normal(128), jnp.bfloat16)}
    cfg = FAMILY.program_config(KERNEL_KEYS, jnp.bfloat16)
    assert cfg.linear_out_norm
    got, state = L.linear_prefill_attention(
        q, k, v, slopes, chunk, block["o_norm"], interpret=True
    )
    # float32 out of the kernel: the cast is the linear kind's `prefill`'s, as it is
    # `_linear_out`'s, so that XLA makes it where it makes that one
    assert got.dtype == jnp.float32 and got.shape == (2, p, 2, 128)
    got = got.astype(jnp.bfloat16)
    plain, plain_state = L.linear_prefill_attention(q, k, v, slopes, chunk, interpret=True)
    assert plain.dtype == jnp.float32
    assert jnp.array_equal(state, plain_state)
    live = np.arange(p) >= pad  # a pad row's output is its query's alone
    for out32 in (plain, LIN.linear_scan(q, k, v, slopes, chunk)[0]):
        want = LIN._linear_out(out32, block, cfg).reshape(got.shape)
        assert _rounds_alike(got[:, live], want[:, live])


def test_a_prefill_and_steps_through_the_rowwise_pass_serve_the_plain_paths_logits(
    monkeypatch,
):
    """`prefill_into_slot`'s core and its scatter and the steps behind them
    with the rule saying kernel (interpreted): q and k through
    `rowwise_heads` in all four layers, the output norm in the scan kernel,
    against the plain path's logits (`rmsnorm`, `rope`, `_linear_out`), in
    float32, where both are the same function."""
    cfg = FAMILY.program_config(
        {**KERNEL_KEYS, "sparse_config": {**KEYS["sparse_config"], "dense_len": 64}},
        jnp.float32,
    )
    assert not SM.rowwise_uses_kernel(cfg, 128)  # this process runs on the CPU
    want, want_toks = _served_logits(cfg, _prompt(100), 128)
    _interpret_the_prefill_kernels(monkeypatch)
    calls = []
    rowwise = R.rowwise_heads
    monkeypatch.setattr(
        R, "rowwise_heads", lambda *a, **kw: calls.append(kw) or rowwise(*a, **kw)
    )
    assert SM.rowwise_uses_kernel(cfg, 128)
    got, toks = _served_logits(cfg, _prompt(100), 128)
    # q and k of four layers, in each of the helper's two traces of a prefill
    assert len(calls) == 2 * 2 * 4
    assert toks == want_toks
    assert np.abs(got - want).max() < 1e-4
