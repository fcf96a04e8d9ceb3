"""The GPT-2 decoder family: everything the benchmark knows of this
decoder's block, and the one file under bench/ that reads the decoder's
keys (under the names of GPT-2's published ``config.json``) and imports
the program's model module. ``pwbench/spec.py family()`` finds it by the
``family`` key of a configuration's file.

The block (the repository's, as the configurations' ``assumed`` say):
  x += attn(rms(x, ln1)) ; x += gelu_tanh(rms(x, ln2) @ ff_in) @ ff_out
  attn: qkv = h @ W_qkv, heads split in order, causal
  softmax(q k^T / sqrt(dh)) v, then @ W_o; learned positions;
  logits = rms(x, ln_f) @ tok_embed^T (tied).
The encoder of every configuration runs the same block without the causal
mask, so the block's leaves and its float32 forward are the shared helpers
of ``pwbench/weights.py`` and ``pwbench/reference.py``; what is the
decoder's alone is here.

What a family's file gives (bench/README.md, "Adding things"):
``sizes``, ``program_config``, ``make_params``, ``decoder_logits``,
``n_block``, ``token_flops``, ``prefill_flops``, ``decode_step_bytes``,
``decode_step_flops``, ``n_params``, ``STEP`` and ``compile_jobs``.
"""

from __future__ import annotations

import functools
import sys
from pathlib import Path
from typing import Any, Callable

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import reference, weights  # noqa: E402

# where the batcher binds the step program when it is built (module,
# attribute): pwbench/faults.py plants its broken steps there. The step
# takes the parameters and the slot cache first and returns (tokens, cache).
STEP = ("pathway_tpu.models.transformer", "decode_step_slots")


def sizes(config: dict) -> dict:
    """The decoder's sizes, from the top level of a configuration's file.
    ``family``, ``vocab``, ``positions``, ``layers`` and ``tag`` (the
    seed's key tag) are what the harness reads; the rest is this file's."""
    return dict(
        family="gpt2",
        vocab=config["vocab_size"], d=config["n_embd"], heads=config["n_head"],
        layers=config["n_layer"], ff=config["n_inner"],
        positions=config["n_positions"], embed=config["n_embd"], tag=2,
    )


def program_config(config: dict, dtype: Any) -> Any:
    """The program's configuration object, as ``JaxLMChat(config=...)``
    takes it."""
    from pathway_tpu.models import lm_config

    return lm_config(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=config["n_positions"],
        dtype=dtype,
    )


# (seed, sizes) -> the parameter tree in the layout the program serves:
# bfloat16, one jitted call from the seed. The shared block's tree is it.
make_params = weights.make_params


# ------------------------------------------------------------- reference

def decoder_logits(seed: int, sz: dict, rows: list[list[int]],
                   at: list[range], width: int,
                   fp8: bool = False) -> list[np.ndarray]:
    """For each row of token ids, the logits [len(at[i]), vocab] at the
    positions ``at[i]``, in float32 at ``highest`` precision from weights
    drawn again from the seed, layer by layer. Rows are padded on the right
    to ``width``, one shape for every call so that one program serves every
    seed. ``fp8`` is the control (reference.py)."""
    import jax

    n = len(rows)
    ids = np.zeros((n, width), np.int32)
    mask = np.zeros((n, width), np.int32)
    for i, r in enumerate(rows):
        ids[i, :len(r)] = r
        mask[i, :len(r)] = 1
    x, top = reference.hidden(seed, sz, ids, mask, True, fp8)
    out = []
    for i in range(n):
        h = reference.rms(x[i, at[i].start:at[i].stop, :], top["ln_f_scale"])
        lg = reference.mm(h, top["tok_embed"].T, fp8)
        out.append(np.asarray(jax.device_get(lg), np.float32))
    return out


# ---------------------------------------------------------------- counts
# Operations and bytes the algorithm needs, from shapes alone (the rules
# are in pwbench/opsbytes.py, which hands each call on to the family).
# N_block = layers x (4 d^2 + 2 d ff) counts the matrices of the blocks
# only (norm scales and tables left out).
#
# * a prefill of p real tokens:
#     2 N_block p  +  layers x 2 d p^2  +  2 d vocab
#   (the block matrices once per token; causal attention, QK^T and PV over
#   the p^2/2 pairs that attend; logits of the last position only).
# * a decode step with the contexts c_1..c_m of the occupied slots, bytes:
#     2 (N_block + vocab d)  +  sum_i layers x 2 x 2 d c_i  +  m x layers x 2 x 2 d
#   (weights and the tied table once, bf16; keys and values of live
#   positions; the new row written).

def n_block(sz: dict) -> int:
    d, f = sz["d"], sz["ff"]
    return sz["layers"] * (4 * d * d + 2 * d * f)


def token_flops(sz: dict) -> int:
    """2 x the non-embedding parameters: one token through the blocks."""
    return 2 * n_block(sz)


def prefill_flops(sz: dict, p: int) -> int:
    d = sz["d"]
    return token_flops(sz) * p + sz["layers"] * 2 * d * p * p + 2 * d * sz["vocab"]


def decode_step_bytes(sz: dict, contexts: list[float]) -> float:
    d, layers = sz["d"], sz["layers"]
    weights_bytes = 2 * (n_block(sz) + sz["vocab"] * d)
    kv = sum(layers * 2 * 2 * d * c for c in contexts)
    new = len(contexts) * layers * 2 * 2 * d
    return weights_bytes + kv + new


def decode_step_flops(sz: dict, contexts: list[float]) -> float:
    d = sz["d"]
    per_row = token_flops(sz) + 2 * d * sz["vocab"]
    return sum(per_row + sz["layers"] * 4 * d * c for c in contexts)


def n_params(sz: dict, *, embedding: bool) -> int:
    """Parameters of the blocks, with or without the embedding tables."""
    d, f = sz["d"], sz["ff"]
    n = sz["layers"] * (4 * d * d + 2 * d * f + 2 * d) + d
    if embedding:
        n += (sz["vocab"] + sz["positions"]) * d
    return n


# ------------------------------------------------- rehearse.py --compile

def compile_jobs(config: dict, shaped: Callable, i32: Callable) -> dict:
    """The step and prefill programs at their real shapes, name -> a
    function that lowers it. ``shaped(tree)`` puts a tree of shapes on the
    described chip; ``i32(*shape)`` is an int32 argument there."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import transformer

    srv = config["server"]
    dsz = sizes(config)
    dec_cfg = program_config(config, jnp.bfloat16)
    params = shaped(jax.eval_shape(lambda: make_params(0, dsz)))
    cache = shaped(jax.eval_shape(
        lambda: transformer.init_kv_cache(dec_cfg, srv["decode_slots"])
    ))
    n = srv["decode_slots"]
    budget = dsz["positions"] - srv["max_new_tokens"]
    jobs = {
        f"step slots={n}": lambda: jax.jit(
            functools.partial(transformer.decode_step_slots, cfg=dec_cfg),
            donate_argnums=(1,),
        ).lower(params, cache, i32(n), i32(n), i32(n)),
    }
    for p in sorted({min(1024, budget), budget}):
        jobs[f"prefill p={p}"] = lambda p=p: jax.jit(
            functools.partial(transformer.prefill_into_slot, cfg=dec_cfg),
            donate_argnums=(3,),
        ).lower(params, i32(1, p), i32(1, p), cache, i32())
    return jobs
