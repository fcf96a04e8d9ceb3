"""The seam every decoder layer kind stands behind (models/layers.py `Kind`,
models/transformer.py `KINDS`): for a one-layer decoder of each kind, the
cache leaves the kind declares are exactly what `init_kv_cache` allocates,
each slot program writes every one of them at its slot, and the counters
behind its tokens are the ones `prefill_counters` / `step_counters` name, in
their order."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import LatentSpec, LayerSpec, lm_config
from pathway_tpu.models import transformer as T

SPECS = {
    "global": LayerSpec(pos="rotary", ff="swiglu"),
    "window": LayerSpec(window=16, pos="rotary", ff="swiglu"),
    "sparse": LayerSpec(mixer="sparse", pos="rotary", ff="swiglu"),
    "linear": LayerSpec(mixer="linear", pos="rotary", ff="swiglu"),
    "latent": LayerSpec(mixer="latent", pos="rotary", ff="swiglu"),
}
SLOT, SLOTS, WIDTH, REAL = 1, 3, 32, 20  # a prompt of 20 tokens in 32


def _cfg(kind: str):
    return lm_config(
        vocab_size=64, d_model=32, n_heads=2, n_kv_heads=1, n_layers=1, d_ff=64,
        max_len=64, dtype=jnp.float32, layers=(SPECS[kind],),
        linear_slopes=(0.5, 0.25),
        # a prompt wider than dense_len: its queries choose 4 of up to 6
        sparse=T.SparseSpec(topk=4, block=4, kernel=4, stride=2, window=8,
                            dense_len=16),
        latent=LatentSpec(q_rank=16, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8),
    )


def _prefilled(cfg, cache):
    """The prompt prefilled into slot SLOT: (its token and counters, cache)."""
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    row = np.random.default_rng(1).integers(1, cfg.vocab_size, REAL)
    ids = np.zeros((1, WIDTH), np.int32)
    ids[0, WIDTH - REAL:] = row
    mask = (ids > 0).astype(np.int32)
    out, cache = T.prefill_into_slot(
        params, jnp.asarray(ids), jnp.asarray(mask), cache, jnp.asarray(SLOT), cfg
    )
    return params, out, cache


@pytest.mark.parametrize("program", ["prefill", "step"])
@pytest.mark.parametrize("name", list(SPECS))
def test_a_kind_declares_its_cache_and_counters_and_its_programs_write_them(
    name, program
):
    cfg = _cfg(name)
    kind = T.kind_of(cfg.layer_specs[0])
    assert kind is T.KINDS[name]
    declared = kind.cache(cfg, 1, SLOTS)
    empty = T.init_kv_cache(cfg, SLOTS)
    assert {k: (v.shape, v.dtype) for k, v in empty.items()} == {
        k: (v.shape, v.dtype) for k, v in declared.items()
    }
    params, first, cache = _prefilled(cfg, dict(empty))
    if program == "prefill":
        before, out, rows = empty, first, 1
        names = T.prefill_counters(cfg)
        assert names == kind.prefill_counters.names
    else:
        before, rows = dict(cache), SLOTS  # the program sets the dict's leaves
        tok = np.zeros(SLOTS, np.int32)
        tok[SLOT] = first[0]
        pos = np.zeros(SLOTS, np.int32)
        pos[SLOT] = WIDTH
        pad = np.zeros(SLOTS, np.int32)
        pad[SLOT] = WIDTH - REAL
        out, cache = T.decode_step_slots(
            params, cache, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(pad), cfg
        )
        names = T.step_counters(cfg)
        assert names == kind.step_counters.names
    assert out.shape == (rows + len(names),)
    for leaf in declared:
        assert not np.array_equal(cache[leaf][:, SLOT], before[leaf][:, SLOT]), leaf
        if program == "prefill":  # and nothing of another slot's row
            for other in set(range(SLOTS)) - {SLOT}:
                assert np.array_equal(cache[leaf][:, other], before[leaf][:, other])
    # each counter where its name says: what the one real row holds
    got = dict(zip(names, np.asarray(out[rows:]).tolist()))
    if name == "sparse":
        # the one key head's blocks at or before each real query (blocks of
        # 4; a prompt's queries at 0 .. 19, the step's at 20), and the 4 or
        # fewer of them each query chose
        seen = [t // 4 + 1 for t in (range(REAL) if program == "prefill" else [REAL])]
        assert got == {
            "sparse_blocks_read": sum(min(4, n) for n in seen),
            "sparse_blocks_visible": sum(seen),
        }
    if name == "linear":
        assert got == {"linear_tokens": REAL if program == "prefill" else 0}
    if name == "latent" and program == "step":
        assert got == {"latent_rows_read": REAL + 1}
    if name in ("global", "window") or (name == "latent" and program == "prefill"):
        assert got == {}
