"""The prefill's attention kernel (ops/attention.py `prefill_attention`)
compiled for a described TPU v5e by the chip's own compiler, from this CPU
host: Mosaic refuses what the interpreter lets pass (a slice off the
tiling, too much VMEM). Compiles, never runs: no time or result comes from
here. The topology is described inside a fixture, and in this file only:
one process at a time may load the TPU's library.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pathway_tpu.models import LayerSpec, lm_config
from pathway_tpu.models import transformer as T
from pathway_tpu.ops.attention import prefill_attention


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


def _prefill_lowered(width: int, chip) -> str:
    """A prefill of two global and two window layers with heads of 128,
    lowered for the chip."""
    cfg = lm_config(
        vocab_size=512, d_model=256, n_heads=4, n_kv_heads=2, head_size=128,
        n_layers=4, d_ff=512, max_len=2048, dtype=jnp.bfloat16,
        layers=(LayerSpec(pos="none"), LayerSpec(window=512, pos="rotary")) * 2,
    )
    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree
    )
    params = shaped(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    ))
    cache = shaped(jax.eval_shape(lambda: T.init_kv_cache(cfg, 2)))
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
    assert text.count("tpu_custom_call") == 2
    assert "prefill_attention" in text


def test_a_width_under_128_takes_the_plain_attention(chip, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert "tpu_custom_call" not in _prefill_lowered(64, chip)
