"""Runs one cell of BENCHMARK.json once and prints the result's line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process builds the live RAG server from the cell's configuration
file, ingests the corpus, warms the cell's own shapes, drives the cell's
traffic from client threads in the same process, compares what was served
with the plain reference and exits. With no TPU (or fewer chips than the
cell asks for) it exits 2 and prints no result."""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT))
    from pwbench import harness

    try:
        result = harness.run_cell(
            ROOT / "BENCHMARK.json", args.workload, args.seed, args.seconds,
            bool(args.trace), t_start=T_START,
        )
    except harness.RunFailed as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except Exception:  # noqa: BLE001 — no result line, a non-zero code
        traceback.print_exc()
        return 1
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the server (HTTP loop, engine) must not hold the
    # exit; everything of ours is stopped and joined by now
    os._exit(code)
