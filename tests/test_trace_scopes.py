"""scripts/trace_scopes.py: the raw `.xplane.pb` read by its wire format,
on the trace the benchmark keeps for its own self-check
(bench/testdata/tiny.xplane.pb, recorded on a TPU v5e): it finds what the
benchmark's reader finds, and beside it what `jax.profiler.ProfileData`
drops, the scope and the source line of each operation."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "scripts"))
import trace_scopes  # noqa: E402 — puts bench/ on the path for `pwbench`

TRACE = str(ROOT / "bench" / "testdata" / "tiny.xplane.pb")


def test_the_raw_reader_finds_the_executions_the_benchmarks_reader_finds():
    from pwbench import trace_reduce

    theirs = trace_reduce.reduce_file(TRACE)["programs"]
    ours = trace_scopes.by_scope(TRACE, min_ms=0.0)
    assert sum(p["executions"] for p in ours.values()) == sum(
        p["count"] for p in theirs.values()
    )
    assert sum(p["ms_per_execution"] * p["executions"] for p in ours.values()) == (
        pytest.approx(1e3 * sum(p["total_s"] for p in theirs.values()), rel=1e-3)  # theirs in whole nanoseconds
    )


def test_an_operation_has_its_scope_its_source_line_and_its_bytes():
    device = next(p for p in trace_scopes.planes(TRACE) if p["name"] == "/device:TPU:0")
    assert [ln["name"] for ln in device["lines"]][:2] == ["XLA Modules", "XLA Ops"]
    rows = [r for p in trace_scopes.by_scope(TRACE, 0.0).values() for r in p["rows"]]
    scope, op, shape, source, count, ms, mb = rows[0]  # the longest
    assert (scope, op, source) == (
        "dot_general", "convolution_tanh_fusion", "record_trace.py:22"
    )
    assert shape.startswith("bf16[256,512]") and count > 0 and ms > 0 and mb > 0
    # an operation no scope was traced around has none, and is still counted
    assert any(r[0] == "" and r[1] == "copy-start" for r in rows)
