"""The `latent` mixer of the decoder (models/config.py `LatentSpec`:
multi-head latent attention over a slot cache of low-rank rows), the expert
branch that a `shortcut` starts in one layer and lands in the next, and the
expert layer that is told which experts it holds (router "all": a softmax
over every output, a selection bias, a scaling factor, identity experts),
served through the slot cache (`prefill_into_slot`'s and
`decode_step_slots`' cores, and the `ContinuousBatcher`) and compared with
the benchmark's plain float32 reference of the block
(bench/families/longcat_flash.py `decoder_logits`: every position's keys and
values expanded, no cache, no absorption; weights drawn again from the
seed), at tiny sizes: d 64, 4 heads of 24 + 8 lanes against values of 16,
ranks 48 and 32, two double layers, feed-forward 96, experts of 40, 8 of 32
experts held beside 16 identity experts, top-4.

(a) the system against the reference, logits, prompts of different lengths
    left-padded into one pool; the absorbed step against the expanded form;
(b) a slot taken again by the batcher serves what a fresh pool serves;
(c) the shares add up: over every range of held experts, the routed parts
    and the identity part once are the uncut layer's output;
(d) the router: the bias moves the choice and not the weight; an identity
    pick adds weight x u; the counters' identity;
(e) the two kernels, interpreted, against the `jax.numpy` formulas at the
    published head widths (192 against 128, a latent row of 512 + 64);
(f) what cannot be served is refused, and the encoder's path refuses it all.
"""

from __future__ import annotations

import dataclasses
import collections
import functools
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import LayerSpec, lm_config
from pathway_tpu.models import config as CF
from pathway_tpu.models import encoder as EN
from pathway_tpu.models import layers as LY
from pathway_tpu.models import routed as RT
from pathway_tpu.models import transformer as T
from pathway_tpu.models.layers import Rows
from pathway_tpu.models.mixers import latent as LAT
from pathway_tpu.models.mixers import softmax as SM
from pathway_tpu.ops import latent_attention as LA
from pathway_tpu.ops.rowwise import rope_tables

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))
from pwbench import spec  # noqa: E402

FAMILY = spec.family("longcat_flash")
SEED = 9
KEYS = dict(
    attention_bias=False, attention_method="MLA", vocab_size=256,
    hidden_size=64, ffn_hidden_size=96, expert_ffn_hidden_size=40,
    num_layers=2, num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48,
    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=24,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6,
    n_routed_experts=8, expert_first=8, max_position_embeddings=128,
    rms_norm_eps=1e-5, rope_theta=10000000, zero_expert_num=16,
    zero_expert_type="identity", moe_topk=4,
    published={"n_routed_experts": 32},
)
SIZES = FAMILY.sizes(KEYS)
N_STEPS = 10


@functools.lru_cache(maxsize=None)
def _params():
    return FAMILY.make_params(SEED, SIZES)


def _prompt(length: int) -> list[int]:
    return np.random.default_rng(length).integers(2, 256, length).tolist()


def _left_padded(row: list[int], width: int):
    ids = np.zeros((1, width), np.int32)
    mask = np.zeros((1, width), np.int32)
    ids[0, width - len(row):], mask[0, width - len(row):] = row, 1
    return ids, mask


def _served_logits(cfg, rows: list[list[int]], width: int):
    """The program's logits at each prompt's last position and after each of
    N_STEPS greedy steps, all prompts in ONE pool: each left-padded to
    `width` and prefilled into a slot of its own by `prefill_into_slot`'s
    core and its scatter (slot 0 stays free), then decoded together by
    `decode_step_slots`' core. Returns (logits [rows, steps + 1, vocab], the
    rows decoded)."""
    params, slots = _params(), len(rows) + 1
    cache = T.init_kv_cache(cfg, slots)
    got, toks = [[] for _ in rows], [list(r) for r in rows]
    for i, row in enumerate(rows):
        ids, mask = _left_padded(row, width)
        lg, mini, _ = T._prefill(
            params, jnp.asarray(ids), T.init_kv_cache(cfg, 1), cfg, jnp.asarray(mask)
        )
        first, cache = T.prefill_into_slot(
            params, jnp.asarray(ids), jnp.asarray(mask), cache, jnp.asarray(i + 1), cfg
        )
        assert int(first[0]) == int(lg[0].argmax())
        for name, leaf in mini.items():  # the scatter put the scratch row there
            assert jnp.array_equal(cache[name][:, i + 1], leaf[:, 0]), name
        got[i].append(np.asarray(lg[0], np.float32))
    step = jax.jit(functools.partial(T._step_rows, cfg=cfg))
    for n in range(N_STEPS):
        tok, pos, pad = (np.zeros(slots, np.int32) for _ in range(3))
        for i, row in enumerate(rows):
            toks[i].append(int(got[i][-1].argmax()))
            tok[i + 1], pos[i + 1], pad[i + 1] = toks[i][-1], width + n, width - len(row)
        lg, cache, _ = step(params, cache, jnp.asarray(tok), jnp.asarray(pos),
                            jnp.asarray(pad))
        for i in range(len(rows)):
            got[i].append(np.asarray(lg[i + 1], np.float32))
    return np.stack([np.stack(g) for g in got]), toks


def _reference_logits(toks: list[list[int]], lengths: list[int]):
    at = [range(n - 1, len(t)) for t, n in zip(toks, lengths)]
    return FAMILY.decoder_logits(SEED, SIZES, toks, at, 512)


# ------------------------------------------- (a) against the reference

LENGTHS = [5, 23, 32]


def test_slot_cache_matches_the_plain_reference_in_float32():
    """float32 activations: the same function in another order of sums (the
    absorbed step against every row expanded, a grouped product over the
    held pairs against every expert over every row)."""
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    got, toks = _served_logits(cfg, [_prompt(n) for n in LENGTHS], 32)
    for g, ref in zip(got, _reference_logits(toks, LENGTHS)):
        np.testing.assert_allclose(g, ref[:len(g)], atol=2e-4, rtol=0)


def test_slot_cache_matches_the_plain_reference_in_bfloat16():
    """bfloat16 activations, as served: every product's output rounds. The
    tolerance is bfloat16's over a stack of eight sub-layers, and a decoder
    that lost a factor, a branch or a rotation is off by tenths."""
    cfg = FAMILY.program_config(KEYS, jnp.bfloat16)
    got, toks = _served_logits(cfg, [_prompt(n) for n in LENGTHS], 32)
    for g, ref in zip(got, _reference_logits(toks, LENGTHS)):
        assert np.abs(g - ref[:len(g)]).max() < 0.12
        assert np.abs(ref).max() > 2.0  # against logits of this size


def test_the_absorbed_step_is_the_expanded_form():
    """One more position of one latent layer, both ways: a step over the
    cache's latent rows (W_uk folded into the query, W_uv after the sum),
    and the same query against keys and values expanded from those rows."""
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    lt, h = cfg.latent, cfg.n_heads
    block, spec_ = _params()["blocks"][0], cfg.layer_specs[0]
    n, pad = 21, 3
    x = jax.random.normal(jax.random.PRNGKey(1), (1, n, cfg.d_model), jnp.float32)
    pos_idx = jnp.maximum(jnp.arange(n) - pad, 0)[None]
    q_n, q_r, c_kv, k_r = LAT._latent_rows(x, block, pos_idx, spec_, cfg)
    kv = jnp.einsum("bsr,rhe->bshe", c_kv, LAT._kv_up(block, cfg))
    k = jnp.concatenate(
        [kv[..., :lt.nope_dim], jnp.broadcast_to(k_r[:, :, None], (1, n, h, lt.rope_dim))],
        axis=-1,
    )
    ok = (jnp.arange(n) >= pad)[None, None, None, :]
    expanded = LAT._attend_latent(
        jnp.concatenate([q_n, q_r], axis=-1)[:, -1:], k, kv[..., lt.nope_dim:], ok, cfg
    )
    # the step: rows 0 .. n - 2 in the cache, the last row its own
    cache = T.init_kv_cache(cfg, 1)
    kind, li = T._cache_rows(cfg)[0]
    cache["c_kv"] = cache["c_kv"].at[li, 0, :n - 1].set(c_kv[0, :-1])
    cache["k_rope"] = cache["k_rope"].at[li, 0, :n - 1, :lt.rope_dim].set(k_r[0, :-1])
    rows = Rows(
        cfg=cfg, cache=cache, counters=collections.defaultdict(list),
        at=jnp.asarray([n - 1]), pad=jnp.asarray([pad]), live=jnp.asarray([[True]]),
    )
    stepped = kind.step(x[:, -1:], block, spec_, li, rows)
    np.testing.assert_allclose(stepped, expanded, atol=2e-5, rtol=0)
    assert int(rows.counters["latent_rows_read"][0]) == n - pad
    # and the step wrote its row
    np.testing.assert_array_equal(cache["c_kv"][li, 0, n - 1], c_kv[0, -1])
    np.testing.assert_array_equal(
        cache["k_rope"][li, 0, n - 1], LAT._in_rope_lanes(k_r[0, -1], cfg)
    )


def test_the_cache_has_the_latent_leaves_and_no_other():
    cfg = FAMILY.program_config(KEYS, jnp.bfloat16)
    cache = T.init_kv_cache(cfg, 3)
    assert {k: v.shape for k, v in cache.items()} == {
        "c_kv": (4, 3, 128, 32), "k_rope": (4, 3, 128, 128),
    }
    # (the rotary key's 8 lanes, 64 as published, lie in a whole lane tile)
    assert all(v.dtype == jnp.bfloat16 for v in cache.values())
    # 1,152 bytes a position and sub-layer at the published widths, where
    # whole heads of keys and values would be 40,960
    assert (512 + 64) * 2 == 1152 and 64 * (192 + 128) * 2 == 40960


# ------------------------------------------- (b) a slot taken again

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
    ahead of it: a long prompt's latent rows must not reach the short prompt
    behind it (nothing of the slot's last request survives the new prompt's
    rows). The normal path: `JaxLMChat` and its `ContinuousBatcher`."""
    words = [f"w{i}" for i in range(200)]
    prompts = [
        " ".join(words[:90]), " ".join(words[100:128]), " ".join(words[40:160]),
        " ".join(words[5:20]),
    ]
    served, stats = _batcher_tokens(prompts, n_slots=1)
    assert stats["prefills"] == 4 and stats["dispatched_ahead"] > 0
    # the counters' identity: every pair the router made is computed here,
    # an identity pick, or another chip's
    assert stats["router_pairs"] == stats["prompt_tokens"] * 4 * 2
    assert stats["router_pairs"] == (
        stats["routed_pairs"] + stats["zero_pairs"] + stats["absent_pairs"]
    )
    assert min(stats["zero_pairs"], stats["routed_pairs"], stats["absent_pairs"]) > 0
    assert stats["latent_rows_read"] > 0
    assert stats["moe_layers_run"] == 2 * stats["decode_steps"]
    for prompt, got in zip(prompts, served):
        (alone,), _ = _batcher_tokens([prompt], n_slots=2)
        assert got == alone


# ------------------------------------------- (c) the shares add up

def _layer_leaves(first: int, count: int):
    """Sub-layer 0 of layer 0 with experts first .. first + count held, in
    float32, and the sizes that say so."""
    sz = {**SIZES, "expert_first": first, "experts": count}
    key = jax.random.wrap_key_data(
        jnp.asarray(FAMILY.weights.key_data(SEED, sz["tag"]))
    )
    w = FAMILY._sub_leaves(key, 0, 0, sz)
    return {k: v.astype(jnp.float32) for k, v in w.items()}, sz


@pytest.mark.parametrize("count", [8, 16, 4])
def test_the_shares_add_up_to_the_uncut_layer(count):
    """Over all `count`-sized ranges of the 32 real experts, the routed
    parts of MoE_here plus the identity part ONCE are the uncut reference's
    MoE for the whole layer: the program's share, range by range, and the
    reference's own."""
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 37, 64), jnp.float32)
    live = jnp.ones((1, 37), bool)
    whole_w, whole_sz = _layer_leaves(0, 32)
    whole = FAMILY.moe_reference(u[0], whole_w, whole_sz)
    identity = whole - FAMILY.moe_reference(u[0], whole_w, whole_sz, zero=False)
    assert float(jnp.abs(identity).max()) > 0.1  # the identity experts weigh
    base = FAMILY.program_config(KEYS, jnp.float32)
    total, theirs, pairs = identity, identity, 0
    for first in range(0, 32, count):
        w, sz = _layer_leaves(first, count)
        # the same router and bias whatever the share, other experts
        np.testing.assert_array_equal(w["router"], whole_w["router"])
        np.testing.assert_array_equal(
            w["expert_up"], whole_w["expert_up"][first:first + count]
        )
        cfg = dataclasses.replace(base, experts_held=(first, count))
        idx, wts = RT.route(u, w, cfg)
        y, counts = RT.experts(u, idx, wts, live, w, cfg)
        mine = FAMILY.moe_reference(u[0], w, sz)
        np.testing.assert_allclose(y[0], mine, atol=2e-5, rtol=0)
        total = total + (y[0] - identity)
        theirs = theirs + (mine - identity)
        pairs += int(counts[:count].sum())
        assert int(counts[count]) == 37 * 4  # the router's pairs
        assert int(counts[:count].sum() + counts[count + 1:].sum()) == 37 * 4
    np.testing.assert_allclose(total, whole, atol=1e-4, rtol=0)
    np.testing.assert_allclose(theirs, whole, atol=1e-4, rtol=0)
    # every real pick was computed by exactly one share
    idx, _ = RT.route(u, whole_w, base)
    assert pairs == int((idx < 32).sum())


def test_more_held_pairs_than_a_pass_holds_are_all_computed(monkeypatch):
    """The loop over the held pairs runs as many passes as they fill: with
    a pass of 16 pairs and 37 x 4 picks, of which a quarter are held, the
    output is the same."""
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 37, 64), jnp.float32)
    w, sz = _layer_leaves(8, 8)
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    idx, wts = RT.route(u, w, cfg)
    monkeypatch.setattr(RT, "_HELD_CHUNK", 16)
    y, counts = RT.experts(u, idx, wts, jnp.ones((1, 37), bool), w, cfg)
    assert int(counts[:8].sum()) > 16
    np.testing.assert_allclose(
        y[0], FAMILY.moe_reference(u[0], w, sz), atol=2e-5, rtol=0
    )


# ------------------------------------------- (d) the router

def test_the_bias_moves_the_choice_and_not_the_weight():
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 50, 64), jnp.float32)
    w, sz = _layer_leaves(8, 8)
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    idx, wts = RT.route(u, w, cfg)
    plain_idx, plain_wts = RT.route(
        u, {**w, "router_bias": jnp.zeros_like(w["router_bias"])}, cfg
    )
    moved = np.asarray(jnp.sort(idx, -1) != jnp.sort(plain_idx, -1)).any(-1)
    assert 0 < moved.sum() < 50  # some tokens choose otherwise, not all
    # a chosen expert's weight is 6 x its softmax score, with or without bias
    scores = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", u, w["router"], precision="highest"), -1
    )
    np.testing.assert_allclose(
        wts, 6.0 * jnp.take_along_axis(scores, idx, -1), rtol=1e-6
    )
    np.testing.assert_allclose(
        plain_wts, 6.0 * jnp.take_along_axis(scores, plain_idx, -1), rtol=1e-6
    )
    assert float(wts.sum(-1).max()) < 6.0  # not renormalised
    # and the reference chooses the same
    ref_idx, ref_wts = FAMILY.route(u[0], w, sz)
    np.testing.assert_array_equal(idx[0], ref_idx)
    np.testing.assert_allclose(wts[0], ref_wts, rtol=1e-6)


def test_an_identity_pick_adds_its_weight_times_the_row():
    """Picks forced by hand: a token that chose identity experts only gets
    (sum of its weights) x u, one that chose absent experts only gets 0, and
    dead rows are not counted."""
    w, _ = _layer_leaves(8, 8)
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 3, 64), jnp.float32)
    idx = jnp.asarray([[[32, 40, 47, 33], [0, 1, 16, 31], [32, 0, 8, 9]]])
    wts = jnp.asarray([[[0.5, 0.25, 0.125, 0.125], [1.0, 1.0, 1.0, 1.0],
                        [0.5, 9.0, 0.0, 0.0]]])
    live = jnp.asarray([[True, True, False]])
    y, counts = RT.experts(u, idx, wts, live, w, cfg)
    np.testing.assert_allclose(y[0, 0], u[0, 0], atol=1e-6)
    np.testing.assert_array_equal(y[0, 1], jnp.zeros(64))
    np.testing.assert_allclose(y[0, 2], 0.5 * u[0, 2], atol=1e-6)
    # live pairs: 8 the router's, 4 identity, 4 absent, none computed here
    assert list(map(int, counts)) == [0] * 8 + [8, 4, 4]


# ------------------------------------------- (e) the kernels, interpreted

@pytest.mark.parametrize("h", [1, 3, 4, 8, 11, 16])
@pytest.mark.parametrize("p, pads", [(256, (0, 130)), (200, (7, 0))])
def test_the_prefill_kernel_matches_the_jnp_attention(p, pads, h):
    """192 lanes of query and key against 128 of value, one rotary key for
    all heads (the leaf's row: 64 lanes and 64 zeros), the query's rotary
    lanes not turned yet (the kernel turns them), the nope keys and the
    values with the heads outermost, rows left-padded by `pads`; a width
    that 128 does not divide is padded inside. By the heads: one (a grid
    step of one head, the kernel as it was), three, four and eight (one
    whole block), eleven (no block divides them: one head a grid step,
    eleven blocks) and sixteen (two blocks of eight)."""
    b, dn, dr, dv = len(pads), 128, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(p), 5)
    dt = jnp.bfloat16
    q_n, k_n = (jax.random.normal(k, (b, p, h, dn), dt) for k in ks[:2])
    q_r = jax.random.normal(ks[2], (b, p, h, dr), dt)
    k_r = jax.random.normal(ks[3], (b, p, dr), dt)
    v = jax.random.normal(ks[4], (b, p, h, dv), dt)
    valid = (jnp.arange(p)[None, :] >= jnp.asarray(pads)[:, None]).astype(jnp.int32)
    cfg = lm_config(
        vocab_size=16, d_model=4 * h, n_heads=h, n_layers=1, d_ff=8, max_len=512,
        dtype=dt, layers=(LayerSpec(mixer="latent", pos="rotary"),),
        latent=T.LatentSpec(q_rank=8, kv_rank=8, nope_dim=dn, rope_dim=dr, v_dim=dv),
    )
    # the kernel turns the query's rotary lanes itself, by `rope`'s rule
    pos = jnp.clip(jnp.cumsum(valid, axis=1) - 1, 0, None)
    cos, sin = rope_tables(pos, cfg.rope_theta, dr)
    out = LA.latent_prefill_attention(
        q_n, LAT._in_rope_lanes(q_r, cfg), k_n.transpose(0, 2, 3, 1),
        LAT._in_rope_lanes(k_r, cfg), v.transpose(0, 2, 3, 1), valid,
        LAT._in_rope_lanes(cos, cfg), LAT._in_rope_lanes(sin, cfg),
        scale=1.0 / math.sqrt(dn + dr), half=dr // 2, interpret=True,
    )
    at = jnp.arange(p)
    ok = valid.astype(bool)[:, None, None, :] & (at[None, :] <= at[:, None])[None, None]
    shared = jnp.broadcast_to(k_r[:, :, None, :], (b, p, h, dr))
    want = LAT._attend_latent(
        jnp.concatenate([q_n, LY.rope(q_r, pos, cfg)], -1),
        jnp.concatenate([k_n, shared], -1), v, ok, cfg,
    )
    real = np.asarray(valid, bool)
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[real], np.asarray(want, np.float32)[real],
        atol=0.04, rtol=0,
    )


def test_the_block_of_the_prefill_kernel_follows_the_shapes():
    """`latent_prefill_block` is what its docstring says: at the fourth
    cell's shape (10,240 positions, 64 heads of 128 nope lanes, the rotary
    lanes in a tile of 128, bfloat16) and its cap's rung, at head counts no
    block of eight divides, at a width 512 does not divide, and where the
    heads are too wide for eight a step."""
    assert LA.latent_prefill_block(10240, 64, 128, 128) == (8, 512)
    assert LA.latent_prefill_block(12288, 64, 128, 128) == (8, 512)
    assert LA.latent_prefill_block(256, 3, 128, 128) == (3, 256)
    assert LA.latent_prefill_block(256, 11, 128, 128) == (1, 256)
    assert LA.latent_prefill_block(256, 12, 128, 128) == (6, 256)
    assert LA.latent_prefill_block(1280, 64, 128, 128) == (8, 256)
    assert LA.latent_prefill_block(10240, 64, 256, 128) == (4, 512)
    assert LA.latent_prefill_block(10240, 64, 128, 128, 4) == (4, 512)


def test_the_step_kernel_matches_the_jnp_attention():
    """Heads of 512 + 64 lanes against the stacked leaves, layer 1 of 2: a
    free slot, a slot whose live rows span two tiles behind a pad past the
    first, and one at the leaf's last row."""
    n, h, r, dr, rows = 3, 4, 512, 128, 2048  # the rotary lanes in a lane tile
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (n, h, r), dt)
    q_r = jax.random.normal(ks[1], (n, h, dr), dt)
    c = jax.random.normal(ks[2], (2, n, rows, r), dt)
    kr = jax.random.normal(ks[3], (2, n, rows, dr), dt)
    pos, pad = jnp.asarray([0, 1500, 2047]), jnp.asarray([0, 1030, 5])
    scale = 1.0 / math.sqrt(192)
    assert LA.latent_decode_tile(rows) == 1024
    z = LA.latent_decode_attention(
        q, q_r, c, kr, jnp.int32(1), pos, pad, scale=scale, interpret=True
    )
    scores = (
        jnp.einsum("bhr,bjr->bhj", q, c[1], preferred_element_type=jnp.float32)
        + jnp.einsum("bhe,bje->bhj", q_r, kr[1], preferred_element_type=jnp.float32)
    ) * scale
    at = jnp.arange(rows)[None, :]
    ok = ((at <= pos[:, None]) & (at >= pad[:, None]))[:, None, :]
    probs = jax.nn.softmax(jnp.where(ok, scores, -1e30), -1).astype(dt)
    want = jnp.einsum("bhj,bjr->bhr", probs, c[1], preferred_element_type=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(z, np.float32), np.asarray(want, np.float32), atol=0.02, rtol=0
    )


def test_the_programs_through_the_kernels_serve_the_plain_paths_logits(monkeypatch):
    """The same decoder at head widths the kernels take (128 + 64 against
    128, a latent row of 128), both kernels interpreted in the programs'
    place: a prefill and steps serve the plain path's logits to bfloat16's
    rounding."""
    keys = {**KEYS, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "kv_lora_rank": 128, "max_position_embeddings": 256}
    sz = FAMILY.sizes(keys)
    cfg = FAMILY.program_config(keys, jnp.bfloat16)
    params = FAMILY.make_params(SEED, sz)
    ids, mask = _left_padded(_prompt(100), 128)

    def run():
        first, cache = T.prefill_into_slot(
            params, jnp.asarray(ids), jnp.asarray(mask), T.init_kv_cache(cfg, 2),
            jnp.asarray(1), cfg,
        )
        out = []
        tok = np.asarray([0, int(first[0])], np.int32)
        for i in range(3):
            lg, cache, _ = T._step_rows(
                params, cache, jnp.asarray(tok), jnp.asarray([0, 128 + i]),
                jnp.asarray([0, 28]), cfg,
            )
            out.append(np.asarray(lg[1], np.float32))
            tok[1] = int(out[-1].argmax())
        return int(first[0]), np.stack(out)

    plain_first, plain = run()
    monkeypatch.setattr(LAT, "latent_prefill_uses_kernel", lambda cfg, width: True)
    monkeypatch.setattr(LAT, "latent_step_uses_kernel", lambda cfg: True)
    monkeypatch.setattr(LA, "latent_prefill_attention", functools.partial(
        LA.latent_prefill_attention, interpret=True))
    monkeypatch.setattr(LA, "latent_decode_attention", functools.partial(
        LA.latent_decode_attention, interpret=True))
    kernel_first, kernel = run()
    assert kernel_first == plain_first
    assert np.abs(kernel - plain).max() < 0.1 < np.abs(plain).max()


# ------------------------------------------- (f) what is refused

@pytest.mark.parametrize("kw, message", [
    (dict(layers=(LayerSpec(mixer="latent"),)), "latent layers need"),
    (dict(layers=(LayerSpec(shortcut="start"),), n_experts=4, n_active=2),
     "every shortcut that starts lands"),
    (dict(layers=(LayerSpec(shortcut="land"),)), "every shortcut that starts lands"),
    (dict(layers=(LayerSpec(ff="experts", shortcut="start"),), n_experts=4,
          n_active=2), "beside a dense feed-forward"),
    (dict(layers=(LayerSpec(ff="experts"),), n_experts=4, n_active=2,
          experts_held=(2, 4)), "experts_held"),
    (dict(layers=(LayerSpec(ff="experts"),), n_experts=4, n_active=2,
          n_zero_experts=2), "router \"all\""),
    (dict(router="best"), "router must be"),
    (dict(expert_act="gelu"), "expert_act must be"),
])
def test_a_configuration_that_cannot_be_served_is_refused(kw, message):
    with pytest.raises(ValueError, match=message):
        lm_config(vocab_size=32, d_model=16, n_heads=2, n_layers=1, d_ff=32, **kw)


def test_forward_and_the_encoder_refuse_the_new_kinds():
    cfg = FAMILY.program_config(KEYS, jnp.float32)
    assert not cfg.plain
    with pytest.raises(NotImplementedError):
        EN.forward(_params(), jnp.zeros((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32), cfg)
    for change in (dict(norm_eps=1e-5), dict(expert_act="silu"), dict(d_expert=8),
                   dict(router="all"), dict(router="all", router_scale=2.0),
                   dict(latent=cfg.latent)):
        with pytest.raises(ValueError, match="plain block only"):
            CF.embedder_config(vocab_size=32, d_model=16, n_heads=2, n_layers=1, **change)


def test_the_rules_ask_the_shapes_and_where_the_process_runs(monkeypatch):
    cfg = FAMILY.program_config(KEYS, jnp.bfloat16)
    wide = dataclasses.replace(cfg, max_len=16384, latent=dataclasses.replace(
        cfg.latent, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128))
    for c in (cfg, wide):  # a CPU: no kernel, whatever the shapes
        assert not LAT.latent_prefill_uses_kernel(c, 10240)
        assert not LAT.latent_step_uses_kernel(c)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert LAT.latent_prefill_uses_kernel(wide, 10240)
    assert not LAT.latent_prefill_uses_kernel(wide, 64)
    assert LAT.latent_step_uses_kernel(wide)
    assert not LAT.latent_prefill_uses_kernel(cfg, 10240)  # values of 16 lanes
    assert not LAT.latent_step_uses_kernel(cfg)  # a latent row of 32
    # heads of 96 (6144 / 64) keep the softmax kernels' rules false
    assert not SM.prefill_uses_kernel(wide, 10240) and not SM.step_uses_kernel(wide)
    # the experts: pairs are counted against the experts HELD, and an
    # expert's gate and up matrices have to fit the fast memory twice over:
    # 2,560 x 768 do (15.7 MB), the published 6,144 x 2,048 do not (100.7)
    held = dataclasses.replace(wide, d_model=2560, d_expert=768)
    assert held.held == (8, 8)
    assert RT.experts_use_kernel(held, 128 * 8)
    assert not RT.experts_use_kernel(held, 128 * 8 - 1)
    assert RT.prefill_experts_use_kernel(held, 10240)
    assert not RT.prefill_experts_use_kernel(held, 64)
    large = dataclasses.replace(wide, d_model=6144, d_expert=2048)
    assert not RT.experts_use_kernel(large, 2048)
    assert not RT.prefill_experts_use_kernel(large, 10240)
    sharded = dataclasses.replace(wide, fused_attention=False)
    assert not LAT.latent_prefill_uses_kernel(sharded, 10240)
    assert not LAT.latent_step_uses_kernel(sharded)


# ------------------------- (g) the accepted configurations' programs

# sha256 (16 hex digits) of the lowered text of `prefill_into_slot` at 64
# positions and `decode_step_slots` of 4 slots, bfloat16, on the CPU, of the
# three accepted configurations' tiny presets, AT THE PARENT OF PR 43
# (commit 214ae50). A PR that means to change one of these programs reads
# the new text, says so in PERF.md and pins it here.
PARENTS_PROGRAMS = {
    "tiny": ("e1a28ce416d8a31c", "f73cc8911e404f27"),
    "tiny-smallthinker": ("3f30127cdb4d0c48", "4a2694aeeb59c461"),
    "tiny-minicpm-sala": ("c400e8359ea12d57", "7bb7884f229fda39"),
}


@pytest.mark.parametrize("preset", sorted(PARENTS_PROGRAMS))
def test_the_accepted_configurations_lower_to_the_parents_text(preset):
    """The latent kind, the shortcut, the router's kinds and the held range
    are chosen when a program is traced: under the defaults the two slot
    programs of the accepted configurations are text for text what they
    were before there was any of it."""
    import hashlib
    import json

    config = json.loads((BENCH / "rehearsal" / f"{preset}.json").read_text())
    family = spec.family_of(config)
    cfg = family.program_config(config, jnp.bfloat16)
    params = jax.eval_shape(lambda: family.make_params(0, family.sizes(config)))
    cache = jax.eval_shape(lambda: T.init_kv_cache(cfg, 4))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    prefill = jax.jit(
        functools.partial(T.prefill_into_slot, cfg=cfg), donate_argnums=(3,)
    ).lower(params, i32(1, 64), i32(1, 64), cache, i32()).as_text()
    step = jax.jit(
        functools.partial(T.decode_step_slots, cfg=cfg), donate_argnums=(1,)
    ).lower(params, cache, i32(4), i32(4), i32(4)).as_text()
    got = tuple(hashlib.sha256(t.encode()).hexdigest()[:16] for t in (prefill, step))
    assert got == PARENTS_PROGRAMS[preset]
