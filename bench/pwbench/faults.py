"""Faults planted in the timed path, for bench/tests (CPU, tiny preset) and
for bench/control.py (by hand, on the chip, at a cell's own size): a run
with one of them has to come out with ``correct`` false. One for each
fault a serving cell on one chip can have (half a batch left out and a
missing exchange between chips are faults of training and of several
chips)."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
from typing import Any, Callable, ContextManager, Iterator

from . import spec


@dataclasses.dataclass(frozen=True)
class Fault:
    """``program(config)`` is entered while the server of that
    configuration is built (the programs it jits are the broken ones);
    ``served(rag)`` is called after warm-up."""
    program: Callable[[dict], ContextManager] = contextlib.nullcontext
    served: Callable[[Any], None] = lambda rag: None


def _broken_step(wrap: Callable) -> Callable[[dict], ContextManager]:
    """The batcher binds the step when it is built, from the module and
    attribute that the configuration's decoder family names (``STEP``):
    while the context lasts, that is ``wrap(the real one, the decoder's
    sizes)``. A step takes the parameters and the slot cache first and
    returns (next tokens, cache)."""
    @contextlib.contextmanager
    def planted(config: dict) -> Iterator[None]:
        family = spec.family_of(config)
        module = importlib.import_module(family.STEP[0])
        real = getattr(module, family.STEP[1])
        setattr(module, family.STEP[1], wrap(real, family.sizes(config)))
        try:
            yield
        finally:
            setattr(module, family.STEP[1], real)

    return planted


def _token_altered(real: Callable, sizes: dict) -> Callable:
    def step(params, cache, *rest, **kw):
        nxt, cache = real(params, cache, *rest, **kw)
        return (nxt + 1) % sizes["vocab"], cache

    return step


def _state_unchanged(real: Callable, sizes: dict) -> Callable:
    def step(params, cache, *rest, **kw):
        # (the real step rebinds the keys of the dict it is given)
        nxt, _written = real(params, dict(cache), *rest, **kw)
        return nxt, cache  # the slot cache as it came: no key or value kept

    return step


def _answer_altered(rag: Any) -> None:
    """The encoder's rows come out rotated by one: every query is answered
    with its neighbour's passages."""
    encode = rag.embedder._batcher.flush_fn

    def broken(texts):
        out = encode(texts)
        return out[1:] + out[:1] if len(out) > 1 else [-out[0]]

    rag.embedder._batcher.flush_fn = broken


# name -> (the fault, the number compared that has to catch it)
FAULTS: dict[str, tuple[Fault, str]] = {
    # every token of the step program comes out one higher
    "token_altered": (Fault(program=_broken_step(_token_altered)), "logit_gap"),
    # the step returns its state, the slot cache, unchanged
    "state_unchanged": (Fault(program=_broken_step(_state_unchanged)), "logit_gap"),
    # an answer of the index altered where it is produced
    "answer_altered": (Fault(served=_answer_altered), "rank_gap"),
}
