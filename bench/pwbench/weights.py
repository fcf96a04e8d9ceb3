"""Weights from ``--seed``, made by the benchmark and handed to the
program as its model: one jitted call on the device, in bfloat16, in the
tree layout the program serves. The reference (reference.py) draws the
same leaves again, layer by layer, from the same keys; it never reads the
program's copy.

Here are the encoder's sizes and the helpers every model shares: the key
of a seed, a leaf, and the leaves of the block that the encoder and the
``gpt2`` decoder family (bench/families/gpt2.py) both run. A decoder's own
sizes, and a tree of another block, are its family's."""

from __future__ import annotations

import functools
import math
from typing import Any

import numpy as np

def encoder_sizes(model: dict) -> dict:
    """The encoder's sizes, from a configuration's ``encoder`` group."""
    return dict(
        vocab=model["vocab_size"], d=model["hidden_size"],
        heads=model["num_attention_heads"],
        layers=model["num_hidden_layers"], ff=model["intermediate_size"],
        positions=model["max_position_embeddings"],
        embed=model["embedding_size"], tag=1,
    )


def key_data(seed: int, tag: int) -> np.ndarray:
    """Raw threefry key data for a seed of any size and a model tag."""
    state = np.random.SeedSequence([int(seed), int(tag)]).generate_state(2)
    return np.asarray(state, np.uint32)


def leaf(key: Any, index: int, shape: tuple, scale: float, centre: float):
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    return (centre + scale * x).astype(jnp.bfloat16)


def block_leaves(key: Any, layer: Any, sz: dict) -> dict:
    """The six leaves of block ``layer`` (a traced or a Python integer)."""
    import jax

    d, f = sz["d"], sz["ff"]
    k = jax.random.fold_in(key, 1000 + layer)
    s = 1.0 / math.sqrt(d)
    return {
        "qkv": leaf(k, 0, (d, 3 * d), s, 0.0),
        "o": leaf(k, 1, (d, d), s, 0.0),
        "ff_in": leaf(k, 2, (d, f), s, 0.0),
        "ff_out": leaf(k, 3, (f, d), 1.0 / math.sqrt(f), 0.0),
        "ln1_scale": leaf(k, 4, (d,), 0.1, 1.0),
        "ln2_scale": leaf(k, 5, (d,), 0.1, 1.0),
    }


def top_leaves(key: Any, sz: dict) -> dict:
    d = sz["d"]
    return {
        "tok_embed": leaf(key, 0, (sz["vocab"], d), 0.02, 0.0),
        "pos_embed": leaf(key, 1, (sz["positions"], d), 0.02, 0.0),
        "ln_f_scale": leaf(key, 2, (d,), 0.1, 1.0),
        "head": leaf(key, 3, (d, sz["embed"]), 1.0 / math.sqrt(d), 0.0),
    }


def _tree(kd: Any, sz_items: tuple) -> dict:
    import jax

    sz = dict(sz_items)
    key = jax.random.wrap_key_data(kd)
    params = top_leaves(key, sz)
    params["blocks"] = [block_leaves(key, i, sz) for i in range(sz["layers"])]
    return params


@functools.lru_cache(maxsize=None)
def _jitted_tree(sz_items: tuple):
    import jax

    return jax.jit(functools.partial(_tree, sz_items=sz_items))


def make_params(seed: int, sz: dict) -> dict:
    """The whole tree, in one jitted call."""
    import jax.numpy as jnp

    fn = _jitted_tree(tuple(sorted(sz.items())))
    return fn(jnp.asarray(key_data(seed, sz["tag"])))
