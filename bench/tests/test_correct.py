"""Tests of the comparison that decides ``correct``, at the tiny preset of
bench/rehearsal on the CPU. Run by hand; ``pytest tests/`` does not
collect them:

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q

* the control (the fp8 reference's tokens and passages in the served ones'
  place, judged by the harness's own comparison) comes out not correct, on
  three seeds, while what the program served in the same run is correct;
* a run whose timed path is broken underneath comes out not correct, once
  for each fault a serving cell can have (pwbench/faults.py);
* sound runs of the three mixes come out correct, and the sample of a
  closed loop holds an answer of every slot.
"""

from __future__ import annotations

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

from pwbench import faults, harness  # noqa: E402

PRESET = BENCH / "rehearsal" / "BENCHMARK.json"


def run(workload: str, seed: int, **kw):
    return harness.run_cell(
        PRESET, workload, seed, 2.0, False, t_start=time.monotonic(),
        require_tpu=False, **kw,
    )


@pytest.mark.parametrize("seed", [101, 2147483747, 3000000103])
def test_control_is_not_correct(seed):
    result = run("tiny.backlog", seed, control=True)
    assert not result["correct"], result["compared"]
    assert result["program"]["correct"], result["program"]


@pytest.mark.parametrize("name", sorted(faults.FAULTS))
def test_broken_timed_path_is_not_correct(name):
    fault, number = faults.FAULTS[name]
    result = run("tiny.backlog", 7, fault=fault)
    assert not result["correct"], result["compared"]
    n = result["compared"][number]
    assert n["value"] > n["limit"], result["compared"]


@pytest.mark.parametrize(
    "workload", ["tiny.backlog", "tiny.answer-steady", "tiny.retrieve-churn"]
)
def test_sound_run_is_correct(workload):
    result = run(workload, 11)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    if workload == "tiny.backlog":
        slots = result["compared"]["logit_gap"]["slots"]
        assert slots == list(range(result["counters"]["n_slots"]))
