"""Operations and bytes the algorithm needs, from shapes alone: never what
a program happens to read (padding, dead slots), so no share of a peak
that is built on them can pass 100%.

``sz`` is ``weights.sizes_of(...)``. N_block = layers x (4 d^2 + 2 d ff)
counts the matrices of the blocks only (norm scales and tables left out).

* a prefill of p real tokens:
    2 N_block p  +  layers x 2 d p^2  +  2 d vocab
  (the block matrices once per token; causal attention, QK^T and PV over
  the p^2/2 pairs that attend; logits of the last position only).
* a decode step with the contexts c_1..c_m of the occupied slots, bytes:
    2 (N_block + vocab d)  +  sum_i layers x 2 x 2 d c_i  +  m x layers x 2 x 2 d
  (weights and the tied table once, bf16; keys and values of live
  positions; the new row written).
* the encoder's attention kernel over rows of t_i real tokens:
    flops sum_i 4 d t_i^2 ; bytes sum_i 8 d t_i  (qkv read, ctx written).
"""

from __future__ import annotations


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
    weights = 2 * (n_block(sz) + sz["vocab"] * d)
    kv = sum(layers * 2 * 2 * d * c for c in contexts)
    new = len(contexts) * layers * 2 * 2 * d
    return weights + kv + new


def decode_step_flops(sz: dict, contexts: list[float]) -> float:
    d = sz["d"]
    per_row = token_flops(sz) + 2 * d * sz["vocab"]
    return sum(per_row + sz["layers"] * 4 * d * c for c in contexts)


def encode_attn_flops(sz: dict, tokens: list[int]) -> int:
    return sum(4 * sz["d"] * t * t for t in tokens)


def encode_attn_bytes(sz: dict, tokens: list[int]) -> int:
    return sum(8 * sz["d"] * t for t in tokens)


def encode_token_flops(sz: dict) -> int:
    return 2 * n_block(sz)
