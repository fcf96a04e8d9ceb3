"""Tests of the ``smallthinker`` decoder family at its tiny preset
(bench/rehearsal/tiny-smallthinker.json, cell
``tiny-smallthinker.backlog`` of bench/rehearsal/smallthinker.BENCHMARK.json)
on the CPU. Run by hand, beside test_correct.py; ``pytest tests/`` does not
collect them:

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests/test_smallthinker.py -q

* the control (the fp8 reference's tokens and passages in the served ones'
  place) comes out not correct, while what the program served in the same
  run is correct;
* a run whose timed path is broken underneath comes out not correct: a
  token altered, an answer of the index altered, and this block's own
  fault, a window layer that keeps and reads every row;
* a sound run comes out correct, with an answer of every slot compared and
  the expert counters of the program in its counters;
* the counts of the family are those of ISSUE 30's arithmetic at the
  published widths.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", str(ROOT / ".pathway-cache" / "xla-rehearsal")
)
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT))

from pwbench import faults, harness, spec  # noqa: E402

PRESET = BENCH / "rehearsal" / "smallthinker.BENCHMARK.json"
CELL = "tiny-smallthinker.backlog"
FAMILY = spec.family("smallthinker")


def run(seed: int, **kw):
    return harness.run_cell(
        PRESET, CELL, seed, 2.0, False, t_start=time.monotonic(),
        require_tpu=False, **kw,
    )


@pytest.mark.parametrize("seed", [101, 2147483747, 3000000103])
def test_control_is_not_correct(seed):
    result = run(seed, control=True)
    assert not result["correct"], result["compared"]
    assert result["program"]["correct"], result["program"]


@pytest.mark.parametrize("fault, number", [
    (faults.FAULTS["token_altered"][0], "logit_gap"),
    (faults.FAULTS["answer_altered"][0], "rank_gap"),
    (faults.Fault(program=FAMILY.window_reads_every_row), "logit_gap"),
], ids=["token_altered", "answer_altered", "window_reads_every_row"])
def test_broken_timed_path_is_not_correct(fault, number):
    result = run(7, fault=fault)
    assert not result["correct"], result["compared"]
    n = result["compared"][number]
    assert n["value"] > n["limit"], result["compared"]


def test_sound_run_is_correct_and_counts_its_experts():
    result = run(11)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    slots = result["compared"]["logit_gap"]["slots"]
    assert slots == list(range(result["counters"]["n_slots"]))
    b = result["counters"]["batcher"]
    sz = FAMILY.sizes(json.loads((BENCH / "rehearsal" / "tiny-smallthinker.json").read_text()))
    assert b["routed_pairs"] == b["prompt_tokens"] * sz["active"] * sz["layers"]
    assert b["moe_layers_run"] == b["decode_steps"] * sz["layers"]
    assert 0 < b["experts_touched"] <= b["moe_layers_run"] * sz["experts"]


def test_counts_at_the_published_widths():
    """ISSUE 30's arithmetic: 9.08 T in the blocks, 722 G a global and
    469 G a window layer of attention at p = 10,040; 34.9 experts touched
    by 8 rows; 2,048 B a position and layer."""
    config = json.loads((BENCH / "configs" / "rag-smallthinker-21b-a3b.json").read_text())
    sz = FAMILY.sizes(config)
    p = 10040
    assert FAMILY.token_flops(sz) * p == pytest.approx(9.08e12, rel=5e-3)
    attention = FAMILY.prefill_flops(sz, p) - FAMILY.token_flops(sz) * p
    assert attention == pytest.approx(2 * 722e9 + 6 * 469e9, rel=5e-3)
    assert FAMILY.experts_touched(sz, 8) == pytest.approx(34.9, abs=0.05)
    assert FAMILY._row_bytes(sz) == 2048
    assert FAMILY.n_params(sz, embedding=True) == pytest.approx(3.967e9, rel=2e-3)
    step = FAMILY.decode_step_bytes(sz, [10056.0] * 8)
    assert step == pytest.approx(5.1e9, rel=0.03)
