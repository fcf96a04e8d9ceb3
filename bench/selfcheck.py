"""Checks the yardstick itself, by hand (``python3 bench/selfcheck.py``;
not collected by ``pytest tests/``): the trace reduction on the small
trace recorded on the chip (bench/testdata/tiny.xplane.pb, written by
bench/testdata/record_trace.py) and on hand-made planes, and the
operation-and-byte functions against numbers worked by hand for both
decoders."""

from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from pwbench import opsbytes, spec, trace_reduce  # noqa: E402


def check(name: str, ok: bool, detail: object = "") -> bool:
    print(("ok   " if ok else "FAIL ") + name + (f": {detail}" if not ok else ""))
    return ok


def hand_made() -> list[bool]:
    """Two programs, one request span; every number worked by hand (ns).
    The first and the last module touch their device's first and last
    instant: the trace's edges cut them, and they are in no sum."""
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit__unknown(1)", 40.0, 20.0),  # what the trace kept of a step
                ("jit__unknown(1)", 100.0, 50.0), ("jit__unknown(2)", 200.0, 20.0),
                ("jit__unknown(1)", 300.0, 50.0),
                ("jit__unknown(2)", 985.0, 15.0)]},  # a prefill, cut at its end
            {"name": "XLA Ops", "events": [
                ("%fusion.1 = x fusion(%params__blocks___0___qkv__.1)", 40.0, 20.0),
                ("%fusion.1 = x fusion(%params__blocks___0___qkv__.1)", 100.0, 30.0),
                ("%fusion.2 = y", 120.0, 30.0),  # overlaps the first by 10
                ("%fusion.1 = x", 205.0, 10.0), ("%fusion.1 = x", 300.0, 50.0),
                ("%fusion.1 = x", 985.0, 15.0)]},
            {"name": "Async XLA Ops", "events": [("%copy-start = z", 0.0, 1000.0)]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "", "events": [
            ("PjitFunction(step)", 30.0, 5.0),
            ("PjitFunction(step)", 90.0, 5.0), ("PjitFunction(prefill)", 190.0, 5.0),
            ("PjitFunction(step)", 290.0, 5.0), ("PjitFunction(prefill)", 975.0, 5.0),
            ("bench.req 1", 80.0, 300.0)]}]},
    ]
    r = trace_reduce.reduce_planes(planes)
    busy = (150 - 100) + 10 + 50  # union: [100,150) [205,215) [300,350)
    cut_busy = 20 + 15  # the two cut executions: busy all the same
    return [
        check("busy is the union of XLA Ops, async copies left out",
              abs(r["busy_s"] - (busy + cut_busy) * 1e-9) < 1e-15, r["busy_s"]),
        check("an execution that touches its device's first or last instant is cut",
              {k: v["cut"] for k, v in r["programs"].items()} == {"step": 1, "prefill": 1}
              and [(k, round(v * 1e9)) for k, v in sorted(r["cut_modules"])]
              == [("prefill", 15), ("step", 20)],
              r["cut_modules"]),
        check("window runs from the first event to the last",
              abs(r["window_s"] - 1000e-9) < 1e-15, r["window_s"]),
        check("modules are named by the host's launches",
              {k: v["count"] for k, v in r["programs"].items()} == {"step": 2, "prefill": 1},
              r["programs"]),
        check("per-program sums", abs(r["programs"]["step"]["total_s"] - 100e-9) < 1e-15),
        check("operations are keyed by program, stem and leaves",
              abs(r["ops"]["step: fusion(qkv)"]["total_s"] - 30e-9) < 1e-15, r["ops"]),
        check("a request's span and the busy time inside it",
              r["requests"] == [[300e-9, busy * 1e-9]] or
              abs(r["requests"][0][1] - busy * 1e-9) < 1e-15, r["requests"]),
        check("idle gaps go to the host span that covers most of each",
              r["idle_gaps"][0][0] == "bench.req 1", r["idle_gaps"]),
    ]


def recorded() -> list[bool]:
    r = trace_reduce.reduce_file(str(HERE / "testdata" / "tiny.xplane.pb"))
    counts = {k: v["count"] for k, v in r["programs"].items()}
    return [
        check("recorded trace: one TPU plane", r["n_device_planes"] == 1),
        check("recorded trace: 12 steps and 3 prefills, named from the host, the "
              "first prefill and the last step at the trace's edges",
              counts.get("small_step") == 11 and counts.get("small_prefill") == 2
              and r["programs"]["small_step"]["cut"] == 1
              and r["programs"]["small_prefill"]["cut"] == 1, r["programs"]),
        check("recorded trace: 0 < busy < window",
              0 < r["busy_s"] < r["window_s"], (r["busy_s"], r["window_s"])),
        check("recorded trace: three request spans, busy inside each below its length",
              len(r["requests"]) == 3 and all(0 < b < d for d, b in r["requests"]),
              r["requests"]),
        check("recorded trace: idle gaps attributed", len(r["idle_gaps"]) > 0),
    ]


def worked_by_hand() -> list[bool]:
    cfgs = {
        n: json.load(open(HERE / "configs" / f"{n}.json"))
        for n in ("rag-gpt2-xl", "rag-cerebras-6b7")
    }
    # both through the family their files name, as the harness reads them
    family = spec.family_of(cfgs["rag-gpt2-xl"])
    xl = family.sizes(cfgs["rag-gpt2-xl"])
    cb = spec.family_of(cfgs["rag-cerebras-6b7"]).sizes(cfgs["rag-cerebras-6b7"])
    return [
        # 48 x (4 x 1600^2 + 2 x 1600 x 6400) = 48 x 30,720,000
        check("gpt2-xl block matrices", opsbytes.n_block(xl) == 1_474_560_000),
        # 16 x (4 x 4096^2 + 2 x 4096 x 16384) = 16 x 201,326,592
        check("cerebras (16 layers) block matrices", opsbytes.n_block(cb) == 3_221_225_472),
        check("cerebras flops a token", opsbytes.token_flops(cb) == 6_442_450_944),
        # 6,442,450,944 x 1000 + 16 x 2 x 4096 x 10^6 + 2 x 4096 x 50257
        check("cerebras prefill of 1000 tokens",
              opsbytes.prefill_flops(cb, 1000) == 6_442_450_944_000 + 131_072_000_000 + 411_705_344),
        # 2 x (1,474,560,000 + 50257 x 1600) + 8 x 48 x 4 x 1600 x 500 + 8 x 48 x 4 x 1600
        check("gpt2-xl step bytes, 8 slots at 500 tokens",
              opsbytes.decode_step_bytes(xl, [500] * 8) == 3_109_942_400 + 1_228_800_000 + 2_457_600),
        check("parameters of gpt2-xl with its tables",
              family.n_params(xl, embedding=True)
              == 1_474_560_000 + 48 * 2 * 1600 + 1600 + (50257 + 1024) * 1600),
    ]


if __name__ == "__main__":
    results = hand_made() + recorded() + worked_by_hand()
    print(f"{sum(results)} of {len(results)} checks passed")
    sys.exit(0 if all(results) else 1)
