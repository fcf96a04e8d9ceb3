"""The decoder's attention kernels (ops/attention.py `prefill_attention`,
`decode_attention`), its expert layer's (ops/experts.py `grouped_experts`)
and the programs that hold them, compiled for a
described TPU v5e by the chip's own compiler, from this CPU host: Mosaic
refuses what the interpreter lets pass (a slice off the tiling, too much
VMEM), and the compiled text shows whether the slot cache stays where it
lies. Compiles, never runs: no time or result comes from here. The
topology is described inside a fixture, and in this file only: one process
at a time may load the TPU's library.
"""

from __future__ import annotations

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pathway_tpu.models import LayerSpec, lm_config
from pathway_tpu.models import routed as RT
from pathway_tpu.models import transformer as T
from pathway_tpu.models.mixers import latent as LAT
from pathway_tpu.models.mixers import softmax as SM
from pathway_tpu.models.mixers import sparse as SPA
from pathway_tpu.ops.attention import decode_attention, prefill_attention
from pathway_tpu.ops.experts import combine_experts, grouped_experts


@pytest.fixture(scope="module")
def chip():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here, nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# the benchmark's two cells, the ladder's narrowest rung for the kernel,
# its caps for 2,048 and 16,384 positions (no multiples of 128: padded
# inside), and two rows in float32
@pytest.mark.parametrize("b, p, heads, kv_heads, window, dtype", [
    (1, 1280, 32, 32, None, jnp.bfloat16),
    (1, 10240, 28, 4, None, jnp.bfloat16),
    (1, 10240, 28, 4, 4096, jnp.bfloat16),
    (1, 128, 32, 32, None, jnp.bfloat16),
    (1, 2016, 32, 32, None, jnp.bfloat16),
    (1, 16352, 28, 4, 4096, jnp.bfloat16),
    (2, 896, 4, 4, None, jnp.float32),
])
def test_the_kernel_compiles_for_v5e(b, p, heads, kv_heads, window, dtype, chip):
    def arg(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    compiled = jax.jit(functools.partial(prefill_attention, window=window)).lower(
        arg(b, p, heads, 128), arg(b, p, kv_heads, 128), arg(b, p, kv_heads, 128),
        arg(b, p, dt=jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    # the name a device trace shows (`prefill_attention[tpu_custom_call]`)
    assert "%prefill_attention" in text


def _shaped(chip, make):
    """The shapes of what `make()` would build, placed on the chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(make),
    )


def _prefill_lowered(width: int, chip) -> str:
    """A prefill of two global and two window layers with heads of 128,
    lowered for the chip."""
    cfg = lm_config(
        vocab_size=512, d_model=256, n_heads=4, n_kv_heads=2, head_size=128,
        n_layers=4, d_ff=512, max_len=2048, dtype=jnp.bfloat16,
        layers=(LayerSpec(pos="none"), LayerSpec(window=512, pos="rotary")) * 2,
    )
    params = _shaped(
        chip, lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    cache = _shaped(chip, lambda: T.init_kv_cache(cfg, 2))
    ids = jax.ShapeDtypeStruct((1, width), jnp.int32, sharding=chip)
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    return jax.jit(
        functools.partial(T.prefill_into_slot, cfg=cfg), donate_argnums=(3,)
    ).lower(params, ids, ids, cache, slot).as_text()


def test_a_prefill_the_rule_sends_to_the_kernel_holds_it_once_a_kind(
    chip, monkeypatch
):
    """The rule asks where the process runs, and this one runs on the CPU:
    the test says TPU in its place. Both layer kinds call the kernel, and
    the program holds one lowering of it a kind, not one a layer."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _prefill_lowered(1280, chip)
    # and the window layers' rotary positions are ops/rowwise.py's pass,
    # one lowering for q (4 heads) and one for k (2)
    assert text.count("tpu_custom_call") == 2 + 2
    assert "prefill_attention" in text and "rowwise_heads" in text


def test_a_width_under_128_takes_the_plain_attention(chip, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert "tpu_custom_call" not in _prefill_lowered(64, chip)


# the benchmark's two cells (a global leaf of rag-cerebras-6b7; a global
# leaf and the ring of rag-smallthinker-21b-a3b, seven query heads a key
# head), heads of 256 (chip_smoke.py's decoder), two slots in float32
@pytest.mark.parametrize("layers, slots, heads, kv_heads, rows, dh, dtype", [
    (16, 8, 32, 32, 2048, 128, jnp.bfloat16),
    (2, 8, 28, 4, 16384, 128, jnp.bfloat16),
    (6, 8, 28, 4, 4096, 128, jnp.bfloat16),
    (18, 2, 8, 8, 1024, 256, jnp.bfloat16),
    (1, 2, 4, 4, 320, 128, jnp.float32),
])
def test_the_decode_kernel_compiles_for_v5e(
    layers, slots, heads, kv_heads, rows, dh, dtype, chip
):
    def arg(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    leaf = arg(layers, slots, kv_heads, rows, dh)
    compiled = jax.jit(decode_attention, donate_argnums=(3, 4)).lower(
        arg(slots, heads, dh), arg(slots, kv_heads, dh), arg(slots, kv_heads, dh),
        leaf, leaf, arg(dt=jnp.int32), arg(slots, dt=jnp.int32),
        arg(slots, dt=jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    # the name a device trace shows (`decode_attention[tpu_custom_call]`)
    assert "%decode_attention" in text
    # the leaves are written in place: nothing as large as one is kept
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# the two cells' decoders with the slot caches they serve ([16, 8, 32, 2048,
# 128] x 2; [2, 8, 4, 16384, 128] x 2 with [6, 8, 4, 4096, 128] x 2), their
# feed-forwards, experts and vocabularies cut small: the attention and the
# cache are what is looked at
CELLS = {
    "rag-cerebras-6b7": dict(
        vocab_size=512, d_model=4096, n_heads=32, n_layers=16, d_ff=256,
        max_len=2048,
    ),
    "rag-smallthinker-21b-a3b": dict(
        vocab_size=512, d_model=2560, n_heads=28, n_kv_heads=4, head_size=128,
        n_layers=8, d_ff=128, max_len=16384, n_experts=8, n_active=2,
        tie_embeddings=False, rope_theta=1.5e6,
        layers=(
            LayerSpec(pos="none", ff="experts"),
            *(LayerSpec(window=4096, pos="rotary", ff="experts"),) * 3,
        ) * 2,
    ),
}
_RESULT = re.compile(r"= (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cells_step_reads_and_writes_the_cache_where_it_lies(
    cell, chip, monkeypatch
):
    """The step program at a cell's cache shapes, the cache donated: every
    layer's attention is the kernel, and besides the kernel no operation
    has a result as large as one layer of one leaf (no copy, no slice, no
    transpose, no scatter over the leaf): the leaves go from the argument
    through the kernels to the result."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = lm_config(dtype=jnp.bfloat16, **CELLS[cell])
    slots = 8
    params = _shaped(
        chip, lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    cache = _shaped(chip, lambda: T.init_kv_cache(cfg, slots))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    compiled = jax.jit(
        functools.partial(T.decode_step_slots, cfg=cfg), donate_argnums=(1,)
    ).lower(params, cache, vec, vec, vec).compile()
    text = compiled.as_text()
    assert text.count("%decode_attention") >= cfg.n_layers
    layer_of_a_leaf = slots * cfg.kv_heads * (cfg.window or cfg.max_len) * cfg.head_dim
    passed_on = {"parameter", "get-tuple-element", "bitcast"}
    large = [
        (op, dims) for _, dims, op in _RESULT.findall(text)
        if op not in passed_on
        and functools.reduce(int.__mul__, map(int, dims.split(","))) >= layer_of_a_leaf
    ]
    assert large == []
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)
    )
    assert stats.temp_size_in_bytes < layer_of_a_leaf * 2  # bytes of one in bf16


# the second cell's experts layer (10,240 tokens x 6 of 64 experts of width
# 768 at d 2560), the narrowest prompt the rule sends here, and float32
@pytest.mark.parametrize("pairs, d, ff, experts, dtype", [
    (61440, 2560, 768, 64, jnp.bfloat16),
    (8448, 2560, 768, 64, jnp.bfloat16),
    (1000, 256, 128, 8, jnp.float32),
])
def test_the_expert_kernels_compile_for_v5e(pairs, d, ff, experts, dtype, chip):
    def arg(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    compiled = jax.jit(grouped_experts).lower(
        arg(pairs, d), arg(pairs, dt=jnp.float32), arg(experts, dt=jnp.int32),
        arg(experts, d, ff), arg(experts, d, ff), arg(experts, ff, d),
    ).compile()
    text = compiled.as_text()
    # the names a device trace shows
    assert "%expert_gate_up" in text and "%expert_down" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # a pair's row leaves as a slab of lane tiles, for the combine's copies
    assert re.search(rf"%expert_down\S* = f32\[{pairs},{d // 128},128\]", text)


@pytest.mark.parametrize("tokens, k, d", [(10240, 6, 2560), (32768, 6, 2560)])
def test_the_combine_kernel_compiles_for_v5e(tokens, k, d, chip):
    """The second cell's prefill, and the most pairs `experts_use_kernel`
    sends here: their row indices fit the chip's scalar memory."""
    assert tokens * k <= RT._EXPERT_KERNEL_MAX_PAIRS
    compiled = jax.jit(combine_experts, static_argnames=("dtype",)).lower(
        jax.ShapeDtypeStruct((tokens * k, d // 128, 128), jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct((k, tokens), jnp.int32, sharding=chip),
        dtype=jnp.bfloat16,
    ).compile()
    assert "%expert_combine" in compiled.as_text()


def test_a_prefills_expert_kernels_read_the_leaves_where_they_lie(
    chip, monkeypatch
):
    """A prefill of two experts layers at the second cell's widths (two
    experts' worth of them), the rule saying TPU: each layer's two kernels
    take the `expert_` leaves of `params` as their own operands, with no
    cast, slice or copy between the parameter and the call, which is what
    the benchmark's `expert_prefill_roofline` finds them by
    (`trace_reduce.op_key`: an operation is named by the leaves it reads)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = lm_config(
        vocab_size=512, d_model=2560, n_heads=28, n_kv_heads=4, head_size=128,
        n_layers=2, d_ff=768, max_len=2048, n_experts=2, n_active=1,
        tie_embeddings=False, dtype=jnp.bfloat16,
        layers=(LayerSpec(pos="none", ff="experts"),
                LayerSpec(window=512, pos="rotary", ff="experts")),
    )
    assert RT.prefill_experts_use_kernel(cfg, 1280)
    params = _shaped(
        chip, lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    cache = _shaped(chip, lambda: T.init_kv_cache(cfg, 2))
    ids = jax.ShapeDtypeStruct((1, 1280), jnp.int32, sharding=chip)
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

    def prefill_into_slot(params, prompt_ids, prompt_mask, cache, slot):
        return T.prefill_into_slot(params, prompt_ids, prompt_mask, cache, slot, cfg)

    text = jax.jit(prefill_into_slot, donate_argnums=(3,)).lower(
        params, ids, ids, cache, slot
    ).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for layer in (0, 1):
        leaf = f"%params__blocks___{layer}___expert_"
        gate_up = [ln for ln in calls if "%expert_gate_up" in ln.split("=")[0]
                   and leaf + "gate__" in ln]
        down = [ln for ln in calls if "%expert_down" in ln.split("=")[0]
                and leaf + "down__" in ln]
        assert len(gate_up) == 1 and leaf + "up__" in gate_up[0], layer
        assert len(down) == 1, layer
    # the combine reads the down kernel's rows as they were written
    assert sum("%expert_combine" in ln.split("=")[0] for ln in calls) == 2
    assert "ragged-dot" not in text


# ---------------------------------------------- the sparse and linear mixers
# (ops/linear_attention.py, ops/sparse_attention.py): the third cell's
# shapes (32 linear heads of 128; 32 query heads over 2 key heads, blocks of
# 64 positions, p 24,576 and the cap of 32,768 positions), a narrow rung,
# and float32.


@pytest.mark.parametrize("b, p, heads, dtype", [
    (1, 24576, 32, jnp.bfloat16),
    (1, 32736, 32, jnp.bfloat16),
    (1, 128, 32, jnp.bfloat16),
    (2, 640, 4, jnp.float32),
])
def test_the_scan_kernel_compiles_for_v5e(b, p, heads, dtype, chip):
    from pathway_tpu.ops.linear_attention import linear_prefill_attention

    def arg(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    compiled = jax.jit(linear_prefill_attention).lower(
        arg(b, p, heads, 128), arg(b, p, heads, 128), arg(b, p, heads, 128),
        arg(heads, dt=jnp.float32),
    ).compile()
    text = compiled.as_text()
    # the name a device trace shows (`linear_prefill_attention[tpu_custom_call]`)
    assert "%linear_prefill_attention" in text
    assert 'custom_call_target="tpu_custom_call"' in text


def test_the_scan_kernel_with_the_output_norm_compiles_for_v5e(chip):
    """The third cell's linear layer with `_linear_out`'s norm in the
    kernel's epilogue: the normed output is the kernel's own, in float32."""
    from pathway_tpu.ops.linear_attention import linear_prefill_attention

    def arg(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    def as_prefill_linear_calls_it(q, k, v, slopes, scale):
        out, state = linear_prefill_attention(q, k, v, slopes, 256, scale)
        return out.astype(jnp.bfloat16).reshape(1, 24576, 4096), state

    text = jax.jit(as_prefill_linear_calls_it).lower(
        arg(1, 24576, 32, 128), arg(1, 24576, 32, 128), arg(1, 24576, 32, 128),
        arg(32, dt=jnp.float32), arg(128),
    ).compile().as_text()
    assert re.search(r"%linear_prefill_attention\S* = \(f32\[1,24576,4096\]", text)
    # and nothing else computes a float32 array of that size: no reduction,
    # no scaling, no relayout behind the kernel
    others = [
        op for dtype, dims, op in _RESULT.findall(text)
        if dtype == "f32"
        and functools.reduce(int.__mul__, map(int, dims.split(","))) >= 24576 * 4096
        and op not in ("parameter", "get-tuple-element", "bitcast")
    ]
    assert others == []


# ops/rowwise.py: q and k of the third cell's linear layers (32 heads each
# out of a product of 96, norm, rotary, the pads' zero) and of its sparse
# layer (32 and 2 of 36, norm alone), of the second cell's window layers (28
# and 4 of 36, rotary alone), a rung under one tile of rows, and float32
@pytest.mark.parametrize("b, p, lanes, first, heads, norm, rotary, zero, dtype", [
    (1, 24576, 96, 0, 32, True, True, False, jnp.bfloat16),
    (1, 24576, 96, 32, 32, True, True, True, jnp.bfloat16),
    (1, 24576, 36, 0, 32, True, False, False, jnp.bfloat16),
    (1, 24576, 36, 32, 2, True, False, False, jnp.bfloat16),
    (1, 10240, 36, 0, 28, False, True, False, jnp.bfloat16),
    (1, 10240, 36, 28, 4, False, True, False, jnp.bfloat16),
    (1, 128, 36, 28, 4, False, True, False, jnp.bfloat16),
    (2, 640, 12, 4, 4, True, True, True, jnp.float32),
])
def test_the_rowwise_pass_compiles_for_v5e(
    b, p, lanes, first, heads, norm, rotary, zero, dtype, chip
):
    from pathway_tpu.ops.rowwise import rowwise_heads

    def arg(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    table = arg(b, p, 128, dt=jnp.float32)
    text = jax.jit(
        functools.partial(rowwise_heads, first=first, heads=heads, dh=128)
    ).lower(
        arg(b, p, lanes * 128), arg(128) if norm else None,
        (table, table) if rotary else None, arg(b, p, dt=jnp.bool_) if zero else None,
    ).compile().as_text()
    # the name a device trace shows (`rowwise_heads[tpu_custom_call]`)
    assert "%rowwise_heads" in text
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("b, p, heads, kv_heads, block, dtype", [
    (1, 24576, 32, 2, 64, jnp.bfloat16),
    (1, 32736, 32, 2, 64, jnp.bfloat16),
    (1, 10240, 28, 4, 64, jnp.bfloat16),
    (2, 896, 4, 4, 16, jnp.float32),
])
def test_the_selected_block_kernel_compiles_for_v5e(
    b, p, heads, kv_heads, block, dtype, chip
):
    from pathway_tpu.ops.sparse_attention import sparse_prefill_attention

    def arg(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    compiled = jax.jit(
        functools.partial(sparse_prefill_attention, block=block)
    ).lower(
        arg(b, p, heads, 128), arg(b, p, kv_heads, 128), arg(b, p, kv_heads, 128),
        arg(b, p, dt=jnp.int32), arg(b, kv_heads, p, -(-p // block), dt=jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "%sparse_prefill_attention" in text
    assert 'custom_call_target="tpu_custom_call"' in text


# the third cell's selection: a chunk of 768 queries as the sparse kind's `prefill`
# hands it over and the whole prompt at once; queries that are no whole
# tile against 512 blocks (padded inside); two rows in float32
@pytest.mark.parametrize("b, nq, heads, kv_heads, n_pool, dtype", [
    (1, 768, 32, 2, 1536, jnp.bfloat16),
    (1, 24576, 32, 2, 1536, jnp.bfloat16),
    (1, 1023, 32, 2, 2048, jnp.bfloat16),
    (2, 512, 4, 2, 256, jnp.float32),
])
def test_the_selection_kernel_compiles_for_v5e(
    b, nq, heads, kv_heads, n_pool, dtype, chip
):
    """`sparse_select` at InfLLM v2's sizes (blocks of 64, pooled keys every
    16 of 32, top-64). A grid step holds the group's 256 queries, its key
    head's pooled keys and what lies between them: at the cell's shape
    10.3 MB of VMEM by the kernel's own sum (`_select_vmem`), under the
    16 MB of the prefill kernels' budget, and the limit it asks is twice
    that budget."""
    from pathway_tpu.ops import sparse_attention as S

    def arg(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    group = heads // kv_heads
    compiled = jax.jit(functools.partial(S.sparse_select, sq=T.SparseSpec())).lower(
        arg(b, nq, kv_heads, group, 128), arg(b, kv_heads, n_pool, 128),
        arg(b, nq, dt=jnp.int32), arg(b, dt=jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "%sparse_select" in text
    assert 'custom_call_target="tpu_custom_call"' in text
    if (nq, heads, n_pool) == (24576, 32, 1536):  # a grid step of the cell's
        assert 10 << 20 < S._select_vmem(256, 16, 128, 1536, 384, 2) < 11 << 20


def _sala(**kw):
    """The third cell's decoder with its slot cache ([1, 8, 2, 32768, 128] x
    2 of rows, pooled keys, [3, 8, 32, 128, 128] float32 of states), its
    feed-forward and vocabulary cut small."""
    base = dict(
        vocab_size=512, d_model=4096, n_heads=32, n_kv_heads=2, head_size=128,
        n_layers=4, d_ff=256, max_len=32768, tie_embeddings=False,
        dtype=jnp.bfloat16,
        layers=(
            LayerSpec(pos="none", ff="swiglu", mixer="sparse"),
            *(LayerSpec(pos="rotary", ff="swiglu", mixer="linear"),) * 3,
        ),
        sparse=T.SparseSpec(), qk_norm=True, out_gate=True,
        linear_out_norm=True, linear_heads=32,
        linear_slopes=tuple(2.0 ** (-8 * h / 32) for h in range(1, 33)),
        embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5, logit_scale=1 / 16,
    )
    return lm_config(**{**base, **kw})


def test_the_third_cells_step_reads_and_writes_the_cache_where_it_lies(
    chip, monkeypatch
):
    """The step program at the cell's cache shapes, the cache donated: the
    sparse layer's attention and row write are the kernel over the chosen
    blocks, the states are updated in their leaf, and no operation has a
    result as large as the layer's rows (no copy, no relayout, no scatter
    over the leaf, no slice of it for the pooled key's window)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _sala()
    assert SPA.sparse_step_uses_kernel(cfg)
    slots = 8
    params = _shaped(
        chip, lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    cache = _shaped(chip, lambda: T.init_kv_cache(cfg, slots))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    compiled = jax.jit(
        functools.partial(T.decode_step_slots, cfg=cfg), donate_argnums=(1,)
    ).lower(params, cache, vec, vec, vec).compile()
    text = compiled.as_text()
    assert "%sparse_decode_attention" in text
    rows_of_the_layer = slots * cfg.kv_heads * cfg.max_len * cfg.head_dim
    passed_on = {"parameter", "get-tuple-element", "bitcast"}
    large = [
        (op, dims) for _, dims, op in _RESULT.findall(text)
        if op not in passed_on
        and functools.reduce(int.__mul__, map(int, dims.split(","))) >= rows_of_the_layer
    ]
    assert large == []
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)
    )
    assert stats.temp_size_in_bytes < 16 << 20


def test_the_third_cells_prefill_holds_its_three_kernels(chip, monkeypatch):
    """A prefill past `dense_len` (lowered, not compiled: the feed-forward
    is cut small, the rest is the cell's) calls the scan's kernel once for
    its three linear layers' one lowering, the selection's kernel and the
    selected-block kernel, and no `prefill_attention`; a prefill up to
    `dense_len` calls `prefill_attention` in the sparse layer's place and
    chooses nothing."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _sala(max_len=16384)
    params = _shaped(
        chip, lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    cache = _shaped(chip, lambda: T.init_kv_cache(cfg, 2))

    def lowered(width):
        ids = jax.ShapeDtypeStruct((1, width), jnp.int32, sharding=chip)
        return jax.jit(
            functools.partial(T.prefill_into_slot, cfg=cfg), donate_argnums=(3,)
        ).lower(params, ids, ids, cache, jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)).as_text()

    def calls(text, kernel):
        """How often a lowering names `kernel` and not a longer name."""
        return len(re.findall(rf"(?<![a-z_]){kernel}(?![a-z_])", text))

    long = lowered(10240)
    assert calls(long, "linear_prefill_attention") and calls(long, "sparse_prefill_attention")
    assert not calls(long, "prefill_attention")
    # the selection is ops/sparse_attention.py's kernel too, the body of the
    # prefill's one loop over chunks of queries
    assert calls(long, "sparse_select")
    assert len(re.findall(r"stablehlo\.while", long)) == 1
    # q and k of every layer through ops/rowwise.py's pass: four lowerings
    # (a sparse layer's q and k, normed; a linear layer's, rotated too and
    # k's pads zeroed), and the cosines and sines made once
    assert SM.rowwise_uses_kernel(cfg, 10240)
    assert long.count("tpu_custom_call") == 1 + 1 + 1 + 4
    assert len(re.findall(r"stablehlo\.cosine", long)) == 1
    short = lowered(1024)
    assert calls(short, "linear_prefill_attention") and calls(short, "prefill_attention")
    assert not calls(short, "sparse_prefill_attention")
    # up to `dense_len` nothing is chosen: no selection, kernel or loop
    assert not calls(short, "sparse_select")
    assert "stablehlo.while" not in short


# ------------------------------------------------ who takes the rowwise pass


def _two_programs(cfg, width, chip) -> tuple[str, str]:
    """`prefill_into_slot` at `width` and `decode_step_slots` of two slots,
    lowered for the chip."""
    params = _shaped(
        chip, lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    cache = _shaped(chip, lambda: T.init_kv_cache(cfg, 2))
    ids = jax.ShapeDtypeStruct((1, width), jnp.int32, sharding=chip)
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    vec = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=chip)
    prefill = jax.jit(
        functools.partial(T.prefill_into_slot, cfg=cfg), donate_argnums=(3,)
    ).lower(params, ids, ids, cache, slot).as_text()
    step = jax.jit(
        functools.partial(T.decode_step_slots, cfg=cfg), donate_argnums=(1,)
    ).lower(params, cache, vec, vec, vec).as_text()
    return prefill, step


def test_the_first_configurations_programs_do_not_change(chip, monkeypatch):
    """A decoder of the first cell's kind (learned positions, no q/k norm,
    softmax layers, heads of 128) on a TPU: `rowwise_uses_kernel` does not
    hold, and its two programs lower to the same text as where the rule
    cannot be asked at all."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = lm_config(
        vocab_size=512, d_model=256, n_heads=2, n_layers=2, d_ff=512,
        max_len=2048, dtype=jnp.bfloat16,
    )
    assert SM.prefill_uses_kernel(cfg, 1280) and not SM.rowwise_uses_kernel(cfg, 1280)
    with_the_rule = _two_programs(cfg, 1280, chip)
    assert "rowwise_heads" not in "".join(with_the_rule)

    def never(cfg, spec):
        raise AssertionError("a layer was asked whether it takes the pass")

    monkeypatch.setattr(SM, "_takes_rowwise", never)
    monkeypatch.setattr(SM, "rowwise_uses_kernel", lambda cfg, width: False)
    assert _two_programs(cfg, 1280, chip) == with_the_rule


def test_a_rotary_decoders_prefill_takes_the_pass_and_its_step_does_not(
    chip, monkeypatch
):
    """A decoder of the second cell's kind (no q/k norm, rotary positions in
    its window layers, 28 query heads over 4): the rule holds at a prefill's
    width; the step keeps `rope`; on a CPU and with `fused_attention` off (a
    pool that spans a mesh) nothing takes it."""
    cfg = lm_config(dtype=jnp.bfloat16, **CELLS["rag-smallthinker-21b-a3b"])
    assert not SM.rowwise_uses_kernel(cfg, 10240)  # this process runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert int(SM.rowwise_uses_kernel(cfg, 10240)) == 1
    assert not SM.rowwise_uses_kernel(cfg, 64)
    import dataclasses

    assert not SM.rowwise_uses_kernel(
        dataclasses.replace(cfg, fused_attention=False), 10240
    )
    small = lm_config(
        vocab_size=512, d_model=256, n_heads=4, n_kv_heads=2, head_size=128,
        n_layers=2, d_ff=512, max_len=2048, dtype=jnp.bfloat16,
        layers=(LayerSpec(pos="none"), LayerSpec(window=512, pos="rotary")),
    )
    prefill, step = _two_programs(small, 1280, chip)
    assert "rowwise_heads" in prefill and "rowwise_heads" not in step
    # the rotary layer's rotation is the pass's: one cosine, the tables'
    assert len(re.findall(r"stablehlo\.cosine", prefill)) == 1
    assert len(re.findall(r"stablehlo\.cosine", step)) >= 1


# ------------------------------------------------ the latent mixer (PR 43)


def _longcat(*, d_model: int, n_heads: int, q_rank: int, max_len: int):
    """A double layer of the fourth cell's kind: two latent sub-layers
    (heads of 128 + 64 over values of 128, a latent row of 512) around a
    shortcut's expert branch; feed-forward, experts and vocabulary small."""
    sub = LayerSpec(mixer="latent", pos="rotary", ff="swiglu")
    return lm_config(
        vocab_size=512, d_model=d_model, n_heads=n_heads, n_layers=2, d_ff=256,
        d_expert=256, max_len=max_len, dtype=jnp.bfloat16, tie_embeddings=False,
        norm_eps=1e-5, router="all", router_bias=True, router_scale=6.0,
        n_experts=32, n_zero_experts=16, n_active=4, experts_held=(8, 8),
        expert_act="silu", rope_theta=1e7,
        latent=T.LatentSpec(q_rank=q_rank, kv_rank=512, nope_dim=128, rope_dim=64,
                            v_dim=128, q_scale=2.0, kv_scale=2.0),
        layers=(dataclasses.replace(sub, shortcut="start"),
                dataclasses.replace(sub, shortcut="land")),
    )


def test_the_latent_kernels_compile_for_v5e(chip):
    """ops/latent_attention.py at the fourth cell's widths: a prefill of
    10,240 positions and 64 heads of 128 nope lanes and 64 rotary lanes in a
    lane tile against one rotary key and values of 128, keys and values
    heads-outermost, and a step of 8 slots over leaves of 12,288 latent
    rows of 512 lanes
    with the rotary key in a lane tile."""
    from pathway_tpu.ops.latent_attention import (
        latent_decode_attention, latent_prefill_attention,
    )

    def arg(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    p, h = 10240, 64
    text = jax.jit(
        functools.partial(latent_prefill_attention, scale=192 ** -0.5, half=32)
    ).lower(
        arg(1, p, h, 128), arg(1, p, h, 128), arg(1, h, 128, p), arg(1, p, 128),
        arg(1, h, 128, p), arg(1, p, dt=jnp.int32),
        arg(1, p, 128, dt=jnp.float32), arg(1, p, 128, dt=jnp.float32),
    ).compile().as_text()
    assert "%latent_prefill_attention" in text  # the name a device trace shows
    vec = arg(8, dt=jnp.int32)
    text = jax.jit(
        functools.partial(latent_decode_attention, scale=192 ** -0.5)
    ).lower(
        arg(8, h, 512), arg(8, h, 128), arg(8, 8, 12288, 512),
        arg(8, 8, 12288, 128), arg(dt=jnp.int32), vec, vec,
    ).compile().as_text()
    assert "%latent_decode_attention" in text


def test_a_latent_step_leaves_the_cache_where_it_lies(chip, monkeypatch):
    """The compiled step of a decoder with latent layers and a shortcut's
    expert branch (heads of 128 + 64 over 128, a latent row of 512, 4 slots
    of 2,048 rows): both kernels' names are in it, the leaves are results
    of their own buffers, and no operation copies a leaf (a `k_rope` leaf 64
    lanes wide was copied there and back every step: `_rope_lanes`)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _longcat(d_model=256, n_heads=4, q_rank=384, max_len=2048)
    assert LAT.latent_step_uses_kernel(cfg) and LAT.latent_prefill_uses_kernel(cfg, 1280)
    params = _shaped(
        chip, lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    cache = _shaped(chip, lambda: T.init_kv_cache(cfg, 4))
    vec = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=chip)
    step = jax.jit(
        functools.partial(T.decode_step_slots, cfg=cfg), donate_argnums=(1,)
    ).lower(params, cache, vec, vec, vec).compile().as_text()
    assert "%latent_decode_attention" in step
    leaves = ("bf16[2,4,2048,512]", "bf16[2,4,2048,128]")
    copies = [
        line for line in step.splitlines()
        if re.search(r"= \S+ copy\(", line) and any(s in line for s in leaves)
    ]
    assert not copies, copies[:2]
    ids = jax.ShapeDtypeStruct((1, 1280), jnp.int32, sharding=chip)
    prefill = jax.jit(
        functools.partial(T.prefill_into_slot, cfg=cfg), donate_argnums=(3,)
    ).lower(
        params, ids, ids, cache, jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    ).compile().as_text()
    assert "%latent_prefill_attention" in prefill


# ------------------------- the accepted cells' attention kernels (PR 44)

# sha256 (16 hex digits) of what the three accepted cells' prefill attention
# kernels lower to for the chip, AT THE PARENT OF PR 44 (commit 8bc38a2),
# whose tile body knew one key tile a group only. A PR that means to change
# one of these kernels reads the new text, says so in PERF.md and pins it
# here.
PARENTS_KERNELS = {
    "rag-cerebras-6b7": "4e7412658ac10281",
    "rag-smallthinker-21b-a3b global": "55f9091f7b4e6ef4",
    "rag-smallthinker-21b-a3b window": "6695c61284b8fdfe",
    "rag-minicpm-sala": "8fdd4741d2b0637d",
}


def _kernels_lowered(cell: str, chip) -> str:
    """The lowered text of a cell's prefill attention kernel at the cell's
    shapes, without source locations: a Pallas kernel's serialized body
    carries them, and they move with every line added above it."""
    from pathway_tpu.ops.sparse_attention import sparse_prefill_attention

    def arg(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    def dense(p, heads, kv_heads, window):
        return jax.jit(functools.partial(prefill_attention, window=window)).lower(
            arg(1, p, heads, 128), arg(1, p, kv_heads, 128),
            arg(1, p, kv_heads, 128), arg(1, p, dt=jnp.int32),
        )

    def sparse(p, heads, kv_heads, block):
        return jax.jit(functools.partial(sparse_prefill_attention, block=block)).lower(
            arg(1, p, heads, 128), arg(1, p, kv_heads, 128),
            arg(1, p, kv_heads, 128), arg(1, p, dt=jnp.int32),
            arg(1, kv_heads, p, p // block, dt=jnp.bool_),
        )

    lower = {
        "rag-cerebras-6b7": lambda: dense(1280, 32, 32, None),
        "rag-smallthinker-21b-a3b global": lambda: dense(10240, 28, 4, None),
        "rag-smallthinker-21b-a3b window": lambda: dense(10240, 28, 4, 4096),
        "rag-minicpm-sala": lambda: sparse(24576, 32, 2, 64),
    }[cell]
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        jax.clear_caches()  # a trace made under another limit is not this one
        return lower().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)


@pytest.mark.parametrize("cell", sorted(PARENTS_KERNELS))
def test_the_accepted_cells_kernels_lower_to_the_parents_text(cell, chip):
    """`_fold_key_tile` learned that a block's heads may each have key lanes
    of their own (PR 44), and `_prefill_vmem` what that costs: where the
    group shares one key tile, as in every accepted cell, the tile, the
    VMEM limit and the kernel's body are text for text what they were."""
    import hashlib

    text = _kernels_lowered(cell, chip)
    assert "tpu_custom_call" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENTS_KERNELS[cell]


def test_the_fourth_cells_prefill_builds_no_padded_heads(chip, monkeypatch):
    """A double layer at the fourth cell's attention widths (d 6144, 64
    heads of 128 + 64 over values of 128, ranks 1536 and 512; feed-forward
    and experts cut small), prefilled 10,240 wide and compiled: the kernel
    is in it, no array of `[10240, 64, 256]` is (the parent built a head's
    192 lanes in 256 for query and key, 335 MB each a sub-layer, and
    repeated the rotary key for every head: its temporaries 2.02 GB, these
    1.40), and nothing copies an operand of the kernel on its way there:
    the query's nope lanes leave their product as rows of heads, the keys
    and values theirs with the heads outermost, which is how the kernel
    reads each."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _longcat(d_model=6144, n_heads=64, q_rank=1536, max_len=12288)
    assert LAT.latent_prefill_uses_kernel(cfg, 10240)
    params = _shaped(
        chip, lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    cache = _shaped(chip, lambda: T.init_kv_cache(cfg, 2))
    ids = jax.ShapeDtypeStruct((1, 10240), jnp.int32, sharding=chip)
    text = jax.jit(
        functools.partial(T.prefill_into_slot, cfg=cfg), donate_argnums=(3,)
    ).lower(
        params, ids, ids, cache, jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    ).compile().as_text()
    assert text.count("%latent_prefill_attention") >= 2
    assert "10240,64,256]" not in text and "10240,16384]" not in text
    copies = re.findall(r"= bf16\[1,(?:10240,8192|8192,10240)\]\S* copy\(", text)
    assert not copies, len(copies)
