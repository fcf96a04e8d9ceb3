"""Tests of the ``minicpm_sala`` decoder family at its tiny preset
(bench/rehearsal/tiny-minicpm-sala.json, cell ``tiny-minicpm-sala.backlog``
of bench/rehearsal/minicpm-sala.BENCHMARK.json) on the CPU. Run by hand,
beside test_correct.py, and imported into ``pytest tests/`` by
tests/test_bench_minicpm_sala.py:

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests/test_minicpm_sala.py -q

* the control (the fp8 reference's tokens and passages in the served ones'
  place) comes out not correct, while what the program served in the same
  run is correct;
* a run whose timed path is broken underneath comes out not correct: a
  token altered, and this block's two own faults, sparse layers that read
  every block and a linear state that never decays;
* a sound run comes out correct, with an answer of every slot compared, the
  mixers' counters of the program in its counters and the three readers
  finding what they read;
* the counts of the family are those of ISSUE 38's arithmetic at the
  published widths.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import numpy as np
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

PRESET = BENCH / "rehearsal" / "minicpm-sala.BENCHMARK.json"
CELL = "tiny-minicpm-sala.backlog"
FAMILY = spec.family("minicpm_sala")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


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


@pytest.mark.parametrize("fault", [
    faults.FAULTS["token_altered"][0],
    faults.Fault(program=FAMILY.selection_reads_every_block),
    faults.Fault(program=FAMILY.state_not_decayed),
], ids=["token_altered", "selection_reads_every_block", "state_not_decayed"])
def test_broken_timed_path_is_not_correct(fault):
    result = run(7, fault=fault)
    assert not result["correct"], result["compared"]
    n = result["compared"]["logit_gap"]
    assert n["value"] > n["limit"], result["compared"]


def test_sound_run_is_correct_and_counts_its_mixers():
    result = run(11)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    slots = result["compared"]["logit_gap"]["slots"]
    assert slots == list(range(result["counters"]["n_slots"]))
    b = result["counters"]["batcher"]
    config = json.loads((BENCH / "rehearsal" / "tiny-minicpm-sala.json").read_text())
    sz = FAMILY.sizes(config)
    assert b["linear_tokens"] == b["prompt_tokens"] * sz["kinds"].count("linear")
    # every prompt is past the tiny dense_len: fewer blocks read than seen
    assert 0 < b["sparse_blocks_read"] < b["sparse_blocks_visible"]
    # the readers: the share from the counters alone; the two rooflines need
    # a device trace and a kernel, which the CPU has not: nothing, no error
    ctx = {
        "counters": {"batcher": b}, "peaks": None, "trace": None,
        "dec_sizes": sz, "prompt_tokens": [60],
    }
    cell = spec.Cell(PRESET, CELL)
    share = cell.reader("sparse_selected_share.tput")(ctx)
    assert share == pytest.approx(
        100.0 * b["sparse_blocks_read"] / b["sparse_blocks_visible"]
    )
    assert cell.reader("linear_prefill_roofline.tput")(ctx) is None
    assert cell.reader("sparse_prefill_roofline.tput")(ctx) is None


def test_the_readers_read_a_trace_and_nothing_of_another_program():
    """On a reduced trace that holds the two kernels the rooflines are the
    family's counts over the kernels' seconds; on the counters of a program
    without sparse or linear layers all three read nothing."""
    config = json.loads((BENCH / "configs" / "rag-minicpm-sala.json").read_text())
    sz = FAMILY.sizes(config)
    cell = spec.Cell(PRESET, CELL)
    p, prefills = 24040, 10
    trace = {
        "programs": {"prefill_into_slot": {"count": 2, "total_s": 1.2}},
        "ops": {
            "prefill_into_slot: linear_prefill_attention[tpu_custom_call]": {"total_s": 0.012},
            "prefill_into_slot: sparse_prefill_attention[tpu_custom_call]": {"total_s": 0.100},
            "prefill_into_slot: while": {"total_s": 0.020},
            "prefill_into_slot: fusion": {"total_s": 0.3},
            "prefill_into_slot: fusion(ff_gate)": {"total_s": 0.5},
            "decode_step_slots: while": {"total_s": 9.0},
        },
    }
    b = {
        "prefills": prefills, "linear_tokens": 3 * p * prefills,
        "sparse_blocks_read": 1, "sparse_blocks_visible": 6,
    }
    ctx = {
        "counters": {"batcher": b}, "peaks": V5E, "trace": trace,
        "dec_sizes": sz, "prompt_tokens": [p] * 4,
    }
    linear = cell.reader("linear_prefill_roofline.tput")(ctx)
    assert linear == pytest.approx(
        100.0 * FAMILY.linear_scan_seconds(sz, 3 * p, 3, V5E) / 0.006
    )
    sparse = cell.reader("sparse_prefill_roofline.tput")(ctx)
    assert sparse == pytest.approx(
        100.0 * FAMILY.sparse_prefill_flops(sz, p) / 197e12 / 0.060
    )
    assert 0 < linear < 100 and 0 < sparse < 100
    other = {**ctx, "counters": {"batcher": {"prefills": prefills}}}
    for name in ("linear_prefill_roofline.tput", "sparse_prefill_roofline.tput",
                 "sparse_selected_share.tput"):
        assert cell.reader(name)(other) is None


def test_counts_at_the_published_widths():
    """ISSUE 38's arithmetic: 285.2 M a lightning layer and 253.8 M a
    minicpm4 layer, 1,109.4 M a period, 3.42 GB with the whole vocabulary;
    54.5 T of matrix products in a prefill of 24,576; 1.66 T of sparse
    attention (ISSUE 38: "about 1.8") against 4.9 T dense, and 0.05 T a linear layer; a cache of
    0.34 GB over 8 slots."""
    config = json.loads((BENCH / "configs" / "rag-minicpm-sala.json").read_text())
    sz = FAMILY.sizes(config)
    assert sz["kinds"] == ("sparse", "linear", "linear", "linear")
    assert FAMILY._mixer_matrix_elements(sz, "linear") + 3 * 4096 * 16384 == pytest.approx(285.2e6, rel=1e-3)
    assert FAMILY._mixer_matrix_elements(sz, "sparse") + 3 * 4096 * 16384 == pytest.approx(253.8e6, rel=1e-3)
    assert FAMILY.n_block(sz) == pytest.approx(1109.4e6, rel=1e-4)
    assert 2 * FAMILY.n_params(sz, embedding=True) == pytest.approx(3.42e9, rel=3e-3)
    p = 24576
    assert FAMILY.token_flops(sz) * p == pytest.approx(54.5e12, rel=2e-3)
    sparse = FAMILY.sparse_prefill_flops(sz, p)
    # (ISSUE 38's "about 1.8" gives every query 4,096 keys; the first 4,096
    # queries have fewer, and a query's own block ends at the query)
    assert sparse == pytest.approx(1.656e12, rel=2e-3)
    assert sparse < 4 * 4096 * 4096 * p + 2 * 4096 * p * p / 32 < 1.81e12
    assert 4 * 4096 * p * p / 2 == pytest.approx(4.9e12, rel=0.01)
    linear = 4 * 32 * 128 * 128 * p
    assert linear == pytest.approx(0.05e12, rel=0.05)
    assert FAMILY.prefill_flops(sz, p) == pytest.approx(
        54.5e12 + sparse + 3 * linear + 2 * 4096 * 73448, rel=2e-3
    )
    # a query past 64 blocks reads 64 of them, its own up to itself
    t = np.asarray([100, 8191, 8192, 24575])
    assert list(FAMILY.chosen_keys(sz, t, p)) == [101, 4096, 4033, 4096]
    assert list(FAMILY.chosen_keys(sz, t[:2], 8192)) == [101, 8192]
    assert list(FAMILY.pooled_seen(sz, t, p)) == [5, 511, 511, 1535]
    # the slot cache: rows and pooled keys of the minicpm4 layer, 3 states
    cache = 8 * (2 * 2 * 32768 * 128 * 2 + 2 * 2048 * 128 * 2 + 3 * 32 * 128 * 128 * 4)
    assert cache == pytest.approx(0.33e9, rel=0.03)
    # a step of 8 rows at 24,056: the weights once (2.82 GB with the head),
    # 4,096 rows and 1,502 pooled keys a row, 6 states read and written
    step = FAMILY.decode_step_bytes(sz, [24056.0] * 8)
    weights = 2 * (FAMILY.n_block(sz) + 4096 * 73448)
    assert weights == pytest.approx(2.82e9, rel=3e-3)
    assert step - weights == pytest.approx(
        8 * (4096 * 1024 + 1502 * 512 + 3 * 2 * 2097152 + 1024), rel=0.02
    )
