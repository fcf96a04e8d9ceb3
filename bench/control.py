"""What has to come out with ``correct`` false, run by hand on the chip at
a cell's own size (bench/tests keeps the same at the tiny preset): a short
window of the cell's own load, then

* without ``--fault``: the control. The reference in the nearest precision
  below the served one (fp8 for bfloat16) is put in the program's place:
  its passages and, at each position of the same prompts and served
  tokens, its tokens are judged by the harness's own comparison. The line
  also carries what the program itself served in that run (``program``);
* with ``--fault <name>`` (pwbench/faults.py): the timed path broken
  underneath.

Prints one line a run; exits 0 when the run was not correct (and, for the
control, the program's own answers were). The benchmark's own runs never
run any of this.

    python3 bench/control.py --workload <cell> --seed 11 --seconds 12 [--fault state_unchanged]
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT))
    from pwbench import faults, harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    a = ap.parse_args()
    fault = faults.FAULTS[a.fault][0] if a.fault else None
    result = harness.run_cell(
        ROOT / "BENCHMARK.json", a.workload, a.seed, a.seconds, False,
        t_start=T_START, fault=fault, control=fault is None,
    )
    print(json.dumps({
        "workload": a.workload, "seed": a.seed, "fault": a.fault,
        "control": fault is None, "correct": result["correct"],
        "program": result.get("program"), "attempted": result["attempted"],
        "phases_s": result["phases_s"], "compared": result["compared"],
    }), flush=True)
    as_expected = not result["correct"] and (
        fault is not None or result["program"]["correct"]
    )
    os._exit(0 if as_expected else 1)
