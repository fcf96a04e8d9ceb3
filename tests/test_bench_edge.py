"""The seven per-layer metrics of ISSUE 40 through the benchmark's own
harness, on the CPU at the tiny preset: `bench/rehearsal/edge.BENCHMARK.json`
is the tiny backlog cell with the accepted per-layer entries and the seven
new ones, and a traced run of it reports all seven and holds their
identity: what the program's request clocks and the client's clock say of
an answer's time outside the batcher agrees with `outside_batcher_ms`,
which takes the same time from outside. Counts and identities only: no
time measured here means anything.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
PRESET = BENCH / "rehearsal" / "edge.BENCHMARK.json"
SEVEN = (
    "edge_inbound_ms", "retrieve_wait_ms", "edge_outbound_ms",
    "client_side_ms", "tokenize_ms", "program_threads_cpu_pct",
    "harness_threads_cpu_pct",
)


def test_the_rehearsal_file_is_the_tiny_cell_with_the_seven_appended():
    edge = json.loads(PRESET.read_text())
    tiny = json.loads((PRESET.parent / "BENCHMARK.json").read_text())
    top = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [w["name"] for w in edge["workloads"]] == ["tiny.backlog"]
    accepted = [
        m for m in tiny["per_layer"] if "tiny.backlog" in m["workloads"]
    ]
    names = [m["name"] for m in edge["per_layer"]]
    assert names == [m["name"] for m in accepted] + [f"{n}.tput" for n in SEVEN]
    # the new entries are the top file's, letter for letter but the cells
    listed = [m["name"] for m in top["per_layer"]]
    first = listed.index(f"{SEVEN[0]}.tput")  # (later PRs append behind them)
    mine = {m["name"]: m for m in top["per_layer"][first:first + len(SEVEN)]}
    assert list(mine) == names[-len(SEVEN):]
    for m in edge["per_layer"][-len(SEVEN):]:
        assert {**mine[m["name"]], "workloads": ["tiny.backlog"]} == m
        assert (BENCH / "layer_metrics" / f"{m['name'].split('.')[0]}.py").is_file()


def test_a_traced_run_of_the_tiny_cell_reports_the_seven_and_their_identity(
    monkeypatch, tmp_path,
):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.syspath_prepend(str(ROOT))
    monkeypatch.syspath_prepend(str(BENCH))
    from pwbench import harness

    from pathway_tpu.internals import observability as obs
    from pathway_tpu.io.http import route_stats

    result = harness.run_cell(
        PRESET, "tiny.backlog", 2147489999, 4.0, True,
        t_start=time.monotonic(), require_tpu=False, out_dir=tmp_path,
    )
    # `correct` but for the error log, which is the process's: an earlier
    # test of this worker may have left entries in it
    wrong = {
        k: n for k, n in result["compared"].items()
        if k != "error_log" and not n["value"] <= n["limit"]
    }
    assert not wrong and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert {f"{n}.tput" for n in SEVEN} <= set(metrics)
    assert "outside_batcher_ms.tput" in metrics
    assert obs.CLOCKS == {}  # no request in flight: no clock kept

    # every answer of the run left its clock, whole and in order
    recent = route_stats()["/v2/answer"]["recent"]
    assert len(recent) >= result["counters"]["completed_in_window"]
    for stamps in recent:
        assert len(stamps) == len(obs.STAGES) + 1
        assert all(end > start for start, end in zip(stamps, stamps[1:]))

    # the identity: inbound + outbound + the client's side is the time
    # outside the batcher, but for the requests in flight at the window's
    # two ends (the records are those sent in it, the counters those
    # finished in it; at most 16 clients of the window's answers)
    parts = (
        metrics["edge_inbound_ms.tput"] + metrics["edge_outbound_ms.tput"]
        + metrics["client_side_ms.tput"]
    )
    b = result["counters"]["batcher"]
    answer_ms = (
        metrics["outside_batcher_ms.tput"] + 1e3 * b["residence_s"] / b["completed"]
    )
    assert abs(parts - metrics["outside_batcher_ms.tput"]) < 0.2 * answer_ms
    assert 0 < metrics["retrieve_wait_ms.tput"] < metrics["edge_inbound_ms.tput"]
    assert metrics["tokenize_ms.tput"] == pytest.approx(
        1e3 * b["tokenize_s"] / b["submitted"]
    )
    assert 0 < metrics["tokenize_ms.tput"] < metrics["queue_wait_ms.tput"]
    # the clients and the loader are threads of this process
    assert metrics["harness_threads_cpu_pct.tput"] > 0
    assert metrics["program_threads_cpu_pct.tput"] > 0
