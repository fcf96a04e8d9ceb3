#!/usr/bin/env python
"""Closed-loop serving load bench: N concurrent clients against a live
gateway-fronted RAG pipeline, measuring p50/p99 latency and goodput.

One process hosts both sides (one invocation per measurement, the
established one-pw.run-per-process discipline):

* **server** — `rest_connector` (+ optional `ServingGateway`) feeding a
  RAG-shaped stage: hash-embed the query, cosine-retrieve over a small
  in-memory doc matrix, answer with the top doc. An optional straggler
  rides the fault plane: the stage probes the `serving.straggler`
  injection point and sleeps ``--straggler-ms`` when the installed
  ``PATHWAY_FAULTS`` schedule fires it — the 20 ms straggler of the
  acceptance run is ``PATHWAY_FAULTS="serving.straggler@1+"``.
* **clients** — ``--clients`` closed-loop asyncio workers: each POSTs,
  awaits the response, then immediately POSTs again, for ``--duration``
  seconds. A 429 honors ``Retry-After`` up to a small cap (a shed
  request must not spin the loop).

The report separates *goodput* (HTTP 200/sec) from raw throughput and
records the server-side queue observables: ``max_pending`` (response
futures piled into the connector — the thing admission control bounds)
and the gateway's shed/queue counters. The acceptance contrast
(docs/serving.md §6): under the straggler, a gateway run keeps p99
bounded by shedding at the edge, while the ``--no-gateway`` control's
pending map grows to the full client count.

``--rolling-upgrade`` adds the zero-downtime rung (docs/robustness.md
§elasticity): mid-bench, a REAL blue/green plan swap
(parallel/bluegreen.py — clone, green replay, verified gates, atomic
rename commit) runs against a persisted pipeline root on the same host
while the client fleet keeps hammering the live server. The report then
splits p99 into during-swap vs outside-swap windows and records the
swap's own duration and verdict — the claim under test is that an
upgrade swap never stalls serving (blue never stops). On a 1-CPU host
the swap subprocess and the server serialize on the same core, which
measures the scheduler, not the swap — the rung skips with an explicit
reason instead of reporting a junk p99.

Usage:
  python scripts/serving_loadgen.py --clients 100 --duration 5
  PATHWAY_FAULTS="serving.straggler@1+" python scripts/serving_loadgen.py \
      --clients 100 --duration 5 --straggler-ms 20 [--no-gateway]
  python scripts/serving_loadgen.py --clients 50 --duration 6 --rolling-upgrade

Prints ONE JSON line; --json PATH also writes it to a file.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_DOCS = 512
DIM = 64


def build_server(args, port: int):
    """Register the pipeline (rest_connector -> RAG-shaped stage) and
    return (webserver, gateway, run_thread_starter)."""
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.engine import faults

    rng = np.random.default_rng(7)
    docs = rng.normal(size=(N_DOCS, DIM)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    straggle_s = args.straggler_ms / 1000.0

    def embed(text: str) -> "np.ndarray":
        v = np.zeros(DIM, np.float32)
        for i, tok in enumerate(text.split()):
            v[hash(tok) % DIM] += 1.0 + (i % 3)
        n = np.linalg.norm(v)
        return v / n if n > 0 else v

    @pw.udf
    def rag_answer(q: str) -> str:
        # the straggler: a seeded PATHWAY_FAULTS schedule decides which
        # requests hit the slow path (serving.straggler@1+ = all of them)
        if straggle_s > 0 and faults.fire("serving.straggler"):
            time.sleep(straggle_s)
        scores = docs @ embed(q)
        top = int(np.argmax(scores))
        return f"doc{top}:{scores[top]:.3f}"

    gateway = None
    if not args.no_gateway:
        backpressure = None
        if args.backpressure:
            backpressure = pw.serving.WatermarkBackpressure(
                delay_lag_s=args.delay_lag_s, shed_lag_s=args.shed_lag_s
            )
        gateway = pw.serving.ServingGateway(
            rate=args.rate,
            burst=args.burst or args.rate,
            max_queue=args.max_queue,
            backpressure=backpressure,
        )
    webserver = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    queries, writer = pw.io.http.rest_connector(
        webserver=webserver,
        route="/answer",
        schema=pw.schema_from_types(query=str, user=str),
        gateway=gateway,
        delete_completed_queries=True,
        timeout_s=args.timeout_s,
    )
    writer(queries.select(result=rag_answer(pw.this.query)))

    def start_run() -> threading.Thread:
        t = threading.Thread(target=pw.run, daemon=True, name="pw-loadgen-run")
        t.start()
        return t

    return webserver, gateway, start_run


# the pipeline whose root the rolling-upgrade rung swaps: a paced
# streaming groupby persisted to ROOT with a real jsonlines sink (the
# same shape the blue/green drills in scripts/chaos_drill.py use)
UPGRADE_SOLO = textwrap.dedent(
    """
    import os, sys, time
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw
    from pathway_tpu.io.python import ConnectorSubject

    ROOT, OUT, N = sys.argv[1], sys.argv[2], int(sys.argv[3])

    class Nums(ConnectorSubject):
        def run(self):
            for i in range(N):
                self.next(g=f"g{{i % 4}}", v=i)
                time.sleep(0.005)

    t = pw.io.python.read(
        Nums(), schema=pw.schema_from_types(g=str, v=int), name="nums"
    )
    agg = t.groupby(t.g).reduce(
        t.g, total=pw.reducers.sum(t.v), n=pw.reducers.count()
    )
    pw.io.jsonlines.write(agg, OUT)
    pw.run(persistence_config=pw.persistence.Config(
        pw.persistence.Backend.filesystem(ROOT)))
    """
).format(repo=REPO)


def _upgrade_solo(root: str, out: str, n: int) -> None:
    r = subprocess.run(
        [sys.executable, "-c", UPGRADE_SOLO, root, out, str(n)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PATHWAY_THREADS": "1",
             "PATHWAY_FAULTS": "0"},
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"upgrade pipeline rc={r.returncode}\n" + r.stderr[-2000:]
        )


def _upgrade_table(n: int) -> dict:
    exp: dict = {}
    for i in range(n):
        g = f"g{i % 4}"
        t0, n0 = exp.get(g, (0, 0))
        exp[g] = (t0 + i, n0 + 1)
    return exp


def _upgrade_sink_state(path: str) -> dict:
    state: dict = {}
    if os.path.exists(path):
        for line in open(path):
            rec = json.loads(line)
            if rec["diff"] > 0:
                state[rec["g"]] = (rec["total"], rec["n"])
            elif state.get(rec["g"]) == (rec["total"], rec["n"]):
                del state[rec["g"]]
    return state


def run_rolling_swap(workdir: str, info: dict) -> None:
    """One real blue/green swap: blue persisted at 32 events, green
    replays the full 64-event stream from the clone, gates verify, the
    rename pair commits. Fills `info` in place (the bench thread reads
    it after joining)."""
    from pathway_tpu.parallel import bluegreen as bg

    blue = os.path.join(workdir, "blue")
    try:
        t0 = time.perf_counter()

        def green(stage):
            out = os.path.join(workdir, "green.jsonl")
            _upgrade_solo(stage, out, 64)
            return _upgrade_sink_state(out)

        res = bg.swap_plan(blue, green, baseline=_upgrade_table(64))
        info["swap_seconds"] = round(time.perf_counter() - t0, 3)
        info["swap_committed"] = bool(res["committed"])
        if not res["committed"]:
            info["swap_reason"] = res["reason"]
    except Exception as e:  # noqa: BLE001 — the bench must still report
        info["swap_committed"] = False
        info["swap_reason"] = f"{type(e).__name__}: {e}"
    finally:
        info["t_end"] = time.perf_counter()


async def drive_clients(args, port: int) -> dict:
    """Closed-loop client fleet; returns raw measurements."""
    import aiohttp

    url = f"http://127.0.0.1:{port}/answer"
    latencies: list[float] = []
    stamps: list[float] = []  # completion time of each 200, for windowing
    counts = {"ok": 0, "shed": 0, "timeout": 0, "error": 0}
    stop_at = time.perf_counter() + args.duration
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=args.timeout_s + 30)
    async with aiohttp.ClientSession(connector=conn, timeout=timeout) as sess:

        async def client(i: int) -> None:
            n = 0
            while time.perf_counter() < stop_at:
                n += 1
                t0 = time.perf_counter()
                try:
                    async with sess.post(
                        url, json={"query": f"query {i} {n}", "user": f"u{i}"}
                    ) as resp:
                        await resp.read()
                        dt = time.perf_counter() - t0
                        if resp.status == 200:
                            counts["ok"] += 1
                            latencies.append(dt)
                            stamps.append(time.perf_counter())
                        elif resp.status == 429:
                            counts["shed"] += 1
                            ra = float(resp.headers.get("Retry-After", "1"))
                            await asyncio.sleep(min(ra, 0.25))
                        elif resp.status == 504:
                            counts["timeout"] += 1
                        else:
                            counts["error"] += 1
                except Exception:  # noqa: BLE001 — count, keep looping
                    counts["error"] += 1
                    await asyncio.sleep(0.05)

        await asyncio.gather(*(client(i) for i in range(args.clients)))
    return {"latencies": latencies, "stamps": stamps, **counts}


def percentile(xs: list[float], p: float) -> float | None:
    if not xs:
        return None
    xs = sorted(xs)
    k = min(int(round((p / 100.0) * (len(xs) - 1))), len(xs) - 1)
    return xs[k]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--no-gateway", action="store_true")
    ap.add_argument("--rate", type=float, default=None,
                    help="route token-bucket rate (default: queue bound only)")
    ap.add_argument("--burst", type=float, default=None)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--backpressure", action="store_true",
                    help="arm watermark backpressure (needs observability)")
    ap.add_argument("--delay-lag-s", type=float, default=1.0)
    ap.add_argument("--shed-lag-s", type=float, default=5.0)
    ap.add_argument("--straggler-ms", type=float, default=0.0,
                    help="slow-path sleep when serving.straggler fires")
    ap.add_argument("--timeout-s", type=float, default=30.0)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--rolling-upgrade", action="store_true",
                    help="run a real blue/green plan swap mid-bench and "
                         "report during-swap vs outside-swap p99")
    ap.add_argument("--json", dest="json_path", default=None)
    args = ap.parse_args()

    if args.rolling_upgrade and (os.cpu_count() or 1) < 2:
        # the swap subprocess and the server would timeshare one core:
        # the p99 split would measure the OS scheduler, not the swap
        line = json.dumps({
            "skipped": True,
            "reason": "rolling-upgrade rung needs >=2 CPUs "
                      f"(os.cpu_count()={os.cpu_count()}); a 1-core host "
                      "serializes the swap against the server and the "
                      "p99 contrast is meaningless",
        })
        print(line)
        if args.json_path:
            with open(args.json_path, "w") as f:
                f.write(line + "\n")
        return 0

    port = args.port
    if port == 0:
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()

    import pathway_tpu as pw

    webserver, gateway, start_run = build_server(args, port)
    start_run()
    webserver._ready.wait(timeout=15)
    deadline = time.time() + 10  # wait until the pipeline answers
    import requests

    while time.time() < deadline:
        try:
            r = requests.post(
                f"http://127.0.0.1:{port}/answer",
                json={"query": "warmup", "user": "warmup"}, timeout=10,
            )
            if r.status_code in (200, 429):
                break
        except Exception:  # noqa: BLE001
            pass
        time.sleep(0.2)

    # sample server-side queue depth while the fleet runs
    depth_samples: list[int] = []
    sampling = True

    def sampler() -> None:
        while sampling:
            st = pw.io.http.route_stats().get("/answer", {})
            depth_samples.append(int(st.get("pending", 0)))
            time.sleep(0.05)

    st_thread = threading.Thread(target=sampler, daemon=True)
    st_thread.start()

    # rolling upgrade: stage blue BEFORE the bench (its pipeline run is
    # not part of the serving measurement), swap DURING it
    swap_info: dict = {}
    swap_thread = None
    upgrade_dir = None
    if args.rolling_upgrade:
        upgrade_dir = tempfile.mkdtemp(prefix="pathway-upgrade-")
        _upgrade_solo(
            os.path.join(upgrade_dir, "blue"),
            os.path.join(upgrade_dir, "blue.jsonl"), 32,
        )

        def _swapper() -> None:
            time.sleep(args.duration / 3.0)  # let the fleet reach steady state
            swap_info["t_start"] = time.perf_counter()
            run_rolling_swap(upgrade_dir, swap_info)

        swap_thread = threading.Thread(target=_swapper, daemon=True)

    t0 = time.perf_counter()
    if swap_thread is not None:
        swap_thread.start()
    raw = asyncio.run(drive_clients(args, port))
    wall = time.perf_counter() - t0
    sampling = False
    st_thread.join(timeout=2)
    if swap_thread is not None:
        swap_thread.join(timeout=120)

    lat = raw.pop("latencies")
    stamps = raw.pop("stamps")
    route = pw.io.http.route_stats().get("/answer", {})
    out = {
        "clients": args.clients,
        "duration_s": round(wall, 3),
        "gateway": not args.no_gateway,
        "max_queue": None if args.no_gateway else args.max_queue,
        "straggler_ms": args.straggler_ms,
        "ok": raw["ok"],
        "shed": raw["shed"],
        "timeout": raw["timeout"],
        "error": raw["error"],
        "p50_ms": round(1000 * percentile(lat, 50), 2) if lat else None,
        "p99_ms": round(1000 * percentile(lat, 99), 2) if lat else None,
        "goodput_rps": round(raw["ok"] / wall, 1) if wall > 0 else None,
        # the queue observable: futures piled into the connector
        "max_pending": int(max(depth_samples, default=0)),
        "route_max_pending": int(route.get("max_pending", 0)),
        "server_timeouts": int(route.get("timeouts", 0)),
    }
    if gateway is not None:
        out["gateway_stats"] = gateway.snapshot()
    if args.rolling_upgrade:
        t_start = swap_info.get("t_start")
        t_end = swap_info.get("t_end")
        during, outside = [], []
        if t_start is not None and t_end is not None:
            for ts, dt in zip(stamps, lat):
                (during if t_start <= ts <= t_end else outside).append(dt)
        out["rolling_upgrade"] = {
            "swap_committed": swap_info.get("swap_committed", False),
            "swap_seconds": swap_info.get("swap_seconds"),
            "ok_during_swap": len(during),
            "p99_ms_during_swap": (
                round(1000 * percentile(during, 99), 2) if during else None
            ),
            "p99_ms_outside_swap": (
                round(1000 * percentile(outside, 99), 2) if outside else None
            ),
        }
        if "swap_reason" in swap_info:
            out["rolling_upgrade"]["swap_reason"] = swap_info["swap_reason"]
        if upgrade_dir:
            import shutil

            shutil.rmtree(upgrade_dir, ignore_errors=True)
    line = json.dumps(out)
    print(line)
    if args.json_path:
        with open(args.json_path, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
