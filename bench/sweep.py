"""Finds the highest rate a cell's server sustains, once, by hand, on the
chip: one server, one window per rate, the mix's own arrivals. A rate is
sustained when the backlog (requests due and not yet answered) at the end
of its window is no larger than at its middle. The readings go into
``bench/traffic/<mix>.sweep.json``; the mix's ``rate_per_s`` is then set
by hand to four fifths of the highest sustained rate.

    python3 bench/sweep.py --workload <name> --rates 2,3,4,5,6 --seconds 30 --out chiprun_out/sweep.json
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2200000033)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT))
    from pwbench import harness, loadgen, spec, traffic
    from pwbench.server import Rag

    cell = spec.Cell(ROOT / "BENCHMARK.json", a.workload)
    devices = harness._device_check(cell.chips, True)
    cfg, mix = cell.config, cell.mix
    k = int(cfg["server"]["search_topk"])
    corpus = traffic.Corpus(a.seed, cfg["corpus"])
    rag = Rag(cfg, a.seed)
    rag.start()
    rows = []
    try:
        harness._ingest(rag, corpus)
        harness._warm_up(rag, cell, corpus)
        for i, rate in enumerate(float(r) for r in a.rates.split(",")):
            due = list(traffic.arrival_times(a.seed + i, rate, a.seconds))
            questions = traffic.make_questions(a.seed + i, corpus, mix, len(due))
            before = rag.counters()
            t0 = time.monotonic()
            upserter = harness.Upserter(
                rag, corpus, traffic.upsert_plan(a.seed + i, mix, a.seconds), t0,
                a.seed + i,
            )
            upserter.start()
            records = loadgen.open_loop(
                rag.port, mix["route"], questions, due, k, t0,
                int(mix["max_in_flight"]),
            )
            upserter.halt()
            upserter.join()
            after = rag.counters()

            def backlog(at: float) -> int:
                return sum(1 for r in records if r.due <= at and r.done > at)

            lat = [r.done - r.due for r in records if r.status == 200]
            row = {
                "rate_per_s": rate, "sent": len(records),
                "failed": sum(1 for r in records if r.status != 200),
                "backlog_middle": backlog(t0 + a.seconds / 2),
                "backlog_end": backlog(t0 + a.seconds),
                "p50_ms": 1e3 * loadgen.percentile(lat, 50),
                "p95_ms": 1e3 * loadgen.percentile(lat, 95),
                "drain_s": max(r.done for r in records) - t0 - a.seconds,
                "late_p95_ms": 1e3 * loadgen.percentile(
                    [r.sent - r.due for r in records], 95
                ),
                "decode_steps": after["batcher"].get("decode_steps", 0)
                - before["batcher"].get("decode_steps", 0),
                "upserts": len(upserter.log),
                "compiled": harness._grown(before["compiles"], after["compiles"]),
            }
            row["sustained"] = row["backlog_end"] <= row["backlog_middle"] and not row["failed"]
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        rag.stop()
    out = {
        "workload": a.workload, "seconds": a.seconds, "seed": a.seed,
        "device": devices[0].device_kind, "rows": rows,
    }
    Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
