"""Operations and bytes the algorithm needs, from shapes alone: never what
a program happens to read (padding, dead slots), so no share of a peak
that is built on them can pass 100%.

A decoder's counts are its family's (bench/families/<family>.py, where the
formulas are written out): the functions here keep the names the readers
under bench/layer_metrics call and hand the call on by ``sz["family"]``,
which the family's ``sizes`` puts there. The encoder's sizes
(``weights.encoder_sizes``) name no family, and its counts are here:
N_block = layers x (4 d^2 + 2 d ff), the matrices of its blocks only, and
the attention kernel over rows of t_i real tokens:
    flops sum_i 4 d t_i^2 ; bytes sum_i 8 d t_i  (qkv read, ctx written).
"""

from __future__ import annotations

from typing import Any

from . import spec


def _of(sz: dict) -> Any:
    return spec.family(sz["family"])


def n_block(sz: dict) -> int:
    """The matrices of the blocks: what one token is multiplied with."""
    if "family" in sz:
        return _of(sz).n_block(sz)
    d, f = sz["d"], sz["ff"]
    return sz["layers"] * (4 * d * d + 2 * d * f)


def token_flops(sz: dict) -> int:
    """One token through the decoder's blocks."""
    return _of(sz).token_flops(sz)


def prefill_flops(sz: dict, p: int) -> int:
    """A prefill of p real tokens."""
    return _of(sz).prefill_flops(sz, p)


def decode_step_bytes(sz: dict, contexts: list[float]) -> float:
    """One decode step with these contexts in the occupied slots."""
    return _of(sz).decode_step_bytes(sz, contexts)


def decode_step_flops(sz: dict, contexts: list[float]) -> float:
    return _of(sz).decode_step_flops(sz, contexts)


def encode_attn_flops(sz: dict, tokens: list[int]) -> int:
    return sum(4 * sz["d"] * t * t for t in tokens)


def encode_attn_bytes(sz: dict, tokens: list[int]) -> int:
    return sum(8 * sz["d"] * t for t in tokens)


def encode_token_flops(sz: dict) -> int:
    return 2 * n_block(sz)
