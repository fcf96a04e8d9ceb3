"""Tests of the ``longcat_flash`` decoder family at its tiny preset
(bench/rehearsal/tiny-longcat-flash.json, cell ``tiny-longcat-flash.backlog``
of bench/rehearsal/longcat-flash.BENCHMARK.json) on the CPU. Run by hand,
beside test_correct.py, and imported into ``pytest tests/`` by
tests/test_bench_longcat_flash.py:

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests/test_longcat_flash.py -q

* the control (the fp8 reference's tokens and passages in the served ones'
  place) comes out not correct, while what the program served in the same
  run is correct;
* a run whose timed path is broken underneath comes out not correct: a
  token altered, and this block's two own faults, a c_kv without its
  factor and identity picks that add nothing;
* a sound run comes out correct, with an answer of every slot compared, the
  router's and the latent rows' counters of the program in its counters and
  the readers finding what they read;
* the counts of the family are those of ISSUE 43's arithmetic at the
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

PRESET = BENCH / "rehearsal" / "longcat-flash.BENCHMARK.json"
CELL = "tiny-longcat-flash.backlog"
FAMILY = spec.family("longcat_flash")
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
    faults.Fault(program=FAMILY.latent_scale_dropped),
    faults.Fault(program=FAMILY.zero_experts_silent),
], ids=["token_altered", "latent_scale_dropped", "zero_experts_silent"])
def test_broken_timed_path_is_not_correct(fault):
    result = run(7, fault=fault)
    assert not result["correct"], result["compared"]
    n = result["compared"]["logit_gap"]
    assert n["value"] > n["limit"], result["compared"]


def test_sound_run_is_correct_and_counts_its_shares():
    result = run(11)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    slots = result["compared"]["logit_gap"]["slots"]
    assert slots == list(range(result["counters"]["n_slots"]))
    b = result["counters"]["batcher"]
    config = json.loads((BENCH / "rehearsal" / "tiny-longcat-flash.json").read_text())
    sz = FAMILY.sizes(config)
    # every pair the router made is computed here, an identity pick, or
    # another chip's; the pairs are the prefills' real tokens x top-4 x 2 layers
    assert b["router_pairs"] == b["prompt_tokens"] * sz["active"] * sz["layers"]
    assert b["router_pairs"] == b["routed_pairs"] + b["zero_pairs"] + b["absent_pairs"]
    assert b["moe_layers_run"] == sz["layers"] * b["decode_steps"]
    assert b["latent_rows_read"] > 2 * sz["layers"] * b["decode_steps"]
    # the readers: the shares from the counters alone, near an even router's
    # 16 / 48 and 8 / 32; the rooflines need a device trace and a kernel,
    # which the CPU has not: nothing, no error
    ctx = {
        "counters": {"batcher": b}, "peaks": None, "trace": None,
        "dec_sizes": sz, "prompt_tokens": [60],
    }
    cell = spec.Cell(PRESET, CELL)
    zero = cell.reader("zero_expert_share.tput")(ctx)
    assert zero == pytest.approx(100.0 * b["zero_pairs"] / b["router_pairs"])
    assert 25 < zero < 42
    held = cell.reader("expert_held_share.tput")(ctx)
    assert held == pytest.approx(
        100.0 * b["routed_pairs"] / (b["router_pairs"] - b["zero_pairs"])
    )
    assert 18 < held < 32
    skew = cell.reader("expert_load_skew.tput")(ctx)
    assert skew == pytest.approx(b["expert_load_max"] * 8 / b["routed_pairs"])
    assert cell.reader("mla_prefill_roofline.tput")(ctx) is None
    assert cell.reader("mla_step_roofline.tput")(ctx) is None


def test_the_readers_read_a_trace_and_nothing_of_another_program():
    """On a reduced trace that holds the two kernels the rooflines are the
    family's counts over the kernels' seconds; on the counters of a program
    without latent layers or shares all four read nothing."""
    config = json.loads((BENCH / "configs" / "rag-longcat-flash-omni.json").read_text())
    sz = FAMILY.sizes(config)
    cell = spec.Cell(PRESET, CELL)
    p, steps = 10040, 50
    rows = 8 * 8 * (p + 16)  # 8 slots, 8 sub-layers
    trace = {
        "programs": {
            "prefill_into_slot": {"count": 2, "total_s": 1.0},
            "decode_step_slots": {"count": 10, "total_s": 0.09},
        },
        "ops": {
            "prefill_into_slot: latent_prefill_attention[tpu_custom_call]": {"total_s": 0.3},
            "prefill_into_slot: fusion(ff_gate)": {"total_s": 0.5},
            "decode_step_slots: latent_decode_attention[tpu_custom_call]": {"total_s": 0.012},
            "decode_step_slots: fusion(kv_b)": {"total_s": 0.01},
        },
    }
    b = {
        "prefills": 10, "decode_steps": steps, "latent_rows_read": rows * steps,
        "router_pairs": 1200, "zero_pairs": 400, "routed_pairs": 25,
    }
    ctx = {
        "counters": {"batcher": b}, "peaks": V5E, "trace": trace,
        "dec_sizes": sz, "prompt_tokens": [p] * 4,
    }
    prefill = cell.reader("mla_prefill_roofline.tput")(ctx)
    assert prefill == pytest.approx(
        100.0 * FAMILY.mla_prefill_flops(sz, p) / 197e12 / 0.15
    )
    step = cell.reader("mla_step_roofline.tput")(ctx)
    assert step == pytest.approx(
        100.0 * FAMILY.mla_step_seconds(sz, rows, V5E) / 0.0012
    )
    assert 0 < prefill < 100 and 0 < step < 100
    assert cell.reader("zero_expert_share.tput")(ctx) == pytest.approx(100 / 3)
    assert cell.reader("expert_held_share.tput")(ctx) == pytest.approx(3.125)
    other = {**ctx, "counters": {"batcher": {"prefills": 10, "decode_steps": steps}},
             "trace": {**trace, "ops": {}}}
    for name in ("mla_prefill_roofline.tput", "mla_step_roofline.tput",
                 "zero_expert_share.tput", "expert_held_share.tput"):
        assert cell.reader(name)(other) is None


def test_counts_at_the_published_widths():
    """ISSUE 43's arithmetic: 90.57 M an MLA sub-layer, 638.8 M a layer
    outside its experts, 37.75 M an expert, 1,242.8 M a layer here, 5.17 B
    parameters and 10.35 GB with an eighth of the vocabulary; 13.0 T of
    matrix products and 4.1 T of attention a layer in a prefill of 10,240;
    1,152 bytes a latent row."""
    config = json.loads((BENCH / "configs" / "rag-longcat-flash-omni.json").read_text())
    sz = FAMILY.sizes(config)
    assert (sz["layers"], sz["experts"], sz["experts_all"], sz["zero"]) == (4, 16, 512, 256)
    assert sz["q_scale"] == pytest.approx(2.0) and sz["kv_scale"] == pytest.approx(3.4641, rel=1e-4)
    assert FAMILY._mla_matrix_elements(sz) == pytest.approx(90.57e6, rel=1e-4)
    assert FAMILY._layer_dense_elements(sz) == pytest.approx(638.8e6, rel=1e-4)
    assert FAMILY.expert_matrix_elements(sz) == pytest.approx(37.75e6, rel=1e-4)
    layer = FAMILY._layer_dense_elements(sz) + 16 * FAMILY.expert_matrix_elements(sz)
    assert layer == pytest.approx(1242.8e6, rel=1e-4)
    held = FAMILY.n_params(sz, embedding=True)
    assert held == pytest.approx(5.17e9, rel=2e-3)
    assert 2 * held == pytest.approx(10.35e9, rel=2e-3)
    # the whole model by the same count: the published 560 B
    whole = 28 * (FAMILY._layer_dense_elements(sz) + 512 * FAMILY.expert_matrix_elements(sz)) + 2 * 131072 * 6144
    assert whole == pytest.approx(560.7e9, rel=1e-3)
    # a quarter of an expert a token is an expectation under an even router
    assert FAMILY.held_picks(sz) == pytest.approx(0.25)
    assert FAMILY.n_block(sz) == pytest.approx(4 * (638.8e6 + 0.25 * 37.75e6), rel=1e-4)
    p = 10240
    assert 2 * FAMILY._layer_dense_elements(sz) * p == pytest.approx(13.08e12, rel=2e-3)
    assert FAMILY.mla_prefill_flops(sz, p) / 4 == pytest.approx(4.3e12, rel=0.01)
    assert FAMILY.prefill_flops(sz, p) == pytest.approx(
        FAMILY.token_flops(sz) * p + FAMILY.mla_prefill_flops(sz, p) + 2 * 6144 * 16384
    )
    assert FAMILY.prefill_flops(sz, p) == pytest.approx(70.3e12, rel=0.01)
    assert FAMILY._row_bytes(sz) == 1152
    # the slot cache: 8 slots x 12,288 rows x 8 sub-layers x 1,152 B
    assert 8 * 12288 * 8 * 1152 == pytest.approx(0.906e9, rel=1e-3)
    # a step of 8 rows at 10,056: the matrices outside the experts and the
    # head once (5.31 GB), the few held experts its 96 picks touch, and
    # 0.74 GB of live latent rows
    step = FAMILY.decode_step_bytes(sz, [10056.0] * 8)
    once = 2 * (4 * FAMILY._layer_dense_elements(sz) + 6144 * 16384)
    assert once == pytest.approx(5.31e9, rel=2e-3)
    touched = FAMILY.experts_touched(sz, 8)
    assert touched == pytest.approx(16 * (1 - (767 / 768) ** 96))
    rows = 8 * 8 * 10057 * 1152
    assert rows == pytest.approx(0.74e9, rel=0.01)
    assert step == pytest.approx(
        once + 2 * 4 * touched * FAMILY.expert_matrix_elements(sz) + rows
    )
    # the step's attention is bound by the rows' bytes, not by its products
    assert FAMILY.mla_step_seconds(sz, 1000.0, V5E) == pytest.approx(
        1000 * 1152 / 819e9
    )
