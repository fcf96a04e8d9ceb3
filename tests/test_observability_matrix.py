"""Observability matrix: telemetry spans (local JSONL exporter), the
OpenMetrics endpoint's exposition format, error-log plumbing, and
monitoring probe counters (reference tier-2: telemetry/monitoring
integration tests)."""

from __future__ import annotations

import json
import os
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.parse_graph import G


@pytest.fixture(autouse=True)
def _fresh_graph():
    G.clear()
    yield
    G.clear()


def test_telemetry_jsonl_spans_cover_run_and_waves(tmp_path, monkeypatch):
    """PATHWAY_TELEMETRY_FILE captures a run span and per-wave spans with
    parseable JSON lines."""
    path = tmp_path / "spans.jsonl"
    monkeypatch.setenv("PATHWAY_TELEMETRY_FILE", str(path))
    t = pw.debug.table_from_rows(
        pw.schema_from_types(v=int), [(1,), (2,), (3,)]
    )
    res = t.reduce(s=pw.reducers.sum(t.v))
    seen = []
    pw.io.subscribe(
        res,
        on_change=lambda key, row, time, is_addition: seen.append(dict(row)),
    )
    pw.run()
    assert seen and seen[-1] == {"s": 6}
    assert path.exists(), "telemetry file must be written"
    spans = [json.loads(line) for line in path.read_text().splitlines()]
    names = {s.get("name") for s in spans}
    assert "run" in names, names
    op_spans = [s for s in spans if s.get("kind") == "operator"]
    assert op_spans, "per-operator spans must be recorded"
    for sp in op_spans:
        assert "latency_ms" in sp and "operator" in sp


def test_metrics_server_openmetrics_format():
    """The metrics endpoint serves OpenMetrics text with engine counters."""
    from pathway_tpu.internals.lowering import Session
    from pathway_tpu.internals.metrics import start_metrics_server

    session = Session()
    t = pw.debug.table_from_rows(pw.schema_from_types(v=int), [(1,), (2,)])
    cap = session.capture(t.reduce(n=pw.reducers.count()))
    from conftest import free_port_base

    port = free_port_base()
    start_metrics_server(session, port=port)  # daemon thread
    session.execute()
    deadline = 20
    body = ""
    import time as _t

    for _ in range(deadline * 10):
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5
            ).read().decode()
            break
        except OSError:
            _t.sleep(0.1)
    assert "# TYPE" in body or "pathway" in body
    # counters are numeric exposition lines "name value"
    metric_lines = [
        ln for ln in body.splitlines() if ln and not ln.startswith("#")
    ]
    assert metric_lines
    for ln in metric_lines:
        parts = ln.rsplit(" ", 1)
        assert len(parts) == 2
        float(parts[1])  # value parses


def test_global_error_log_captures_expression_errors():
    from pathway_tpu.internals.errors import ERROR

    t = pw.debug.table_from_rows(
        pw.schema_from_types(a=int, b=int), [(6, 2), (1, 0)]
    )
    res = t.select(q=t.a // t.b)
    _ids, cols = pw.debug.table_to_dicts(res)
    assert sorted(cols["q"].values(), key=repr) == sorted(
        [3, ERROR], key=repr
    )
    entries = [str(e) for e in pw.global_error_log().entries]
    # logged with the user call-site trace attached
    assert any("ZeroDivisionError" in e for e in entries), entries
    assert any("test_observability_matrix" in e for e in entries), entries


def test_fill_error_substitutes_without_logging_noise():
    """fill_error handles the bad cell vectorized: the value is replaced
    and no Python exception path runs for it."""
    before = len(pw.global_error_log().entries)
    t = pw.debug.table_from_rows(
        pw.schema_from_types(a=int, b=int), [(1, 0)]
    )
    res = t.select(q=pw.fill_error(t.a // t.b, -1))
    _ids, cols = pw.debug.table_to_dicts(res)
    assert list(cols["q"].values()) == [-1]
    assert len(pw.global_error_log().entries) == before


def test_monitoring_probe_ticks_on_streaming_waves():
    """Session monitors observe wave progress on the STREAMING loop (the
    TUI's data source; static runs finish in one shot without ticks)."""
    import threading

    from pathway_tpu.internals.lowering import Session

    session = Session()
    t = pw.demo.range_stream(nb_rows=12, input_rate=500)
    session.subscribe(t, on_change=lambda key, row, time, is_addition: None)
    ticks: list[int] = []
    session.monitors.append(lambda time: ticks.append(time))
    th = threading.Thread(target=session.execute, daemon=True)
    th.start()
    th.join(30)
    assert not th.is_alive()
    assert ticks, "monitor must tick at least once per processed wave"
    assert ticks == sorted(ticks)  # wave times advance monotonically


def test_telemetry_jsonl_span_structure(tmp_path, monkeypatch):
    """Span records carry the full structure: kind/name/duration_ms/
    error/run_id/ts; metric records carry value; operator records carry
    the plan-node label (all on one run_id)."""
    path = tmp_path / "spans.jsonl"
    monkeypatch.setenv("PATHWAY_TELEMETRY_FILE", str(path))
    t = pw.debug.table_from_rows(
        pw.schema_from_types(g=str, v=int), [("a", 1), ("b", 2), ("a", 3)]
    )
    res = t.groupby(t.g).reduce(t.g, s=pw.reducers.sum(t.v))
    pw.io.subscribe(
        res, on_change=lambda key, row, time, is_addition: None
    )
    pw.run()
    records = [json.loads(ln) for ln in path.read_text().splitlines()]
    run_ids = {r["run_id"] for r in records}
    assert len(run_ids) == 1
    spans = [r for r in records if r["kind"] == "span"]
    assert spans, "at least the run span must be exported"
    for sp in spans:
        assert {"name", "duration_ms", "error", "run_id", "ts"} <= set(sp)
        assert sp["duration_ms"] >= 0 and sp["error"] is False
    ops = [r for r in records if r["kind"] == "operator"]
    assert ops and all("label" in o for o in ops)
    assert any(o["label"] == "groupby" for o in ops)


def test_telemetry_exports_observability_spine_events(tmp_path, monkeypatch):
    """With the observability plane armed, structured spine events
    (breaker flips, faults, quarantines) flow out the telemetry JSONL
    pipe as kind=event records."""
    from pathway_tpu.engine import faults
    from pathway_tpu.internals import observability as obs

    path = tmp_path / "spans.jsonl"
    monkeypatch.setenv("PATHWAY_TELEMETRY_FILE", str(path))
    monkeypatch.setenv("PATHWAY_FAULTS", "obs.telemetry.demo@1")
    faults.reset()
    obs.enable()
    t = pw.debug.table_from_rows(pw.schema_from_types(v=int), [(1,)])
    pw.io.subscribe(
        t, on_change=lambda key, row, time, is_addition: None
    )
    try:
        pw.run()
        # a fault fired mid-run would be exported live; fire one while
        # the exporter is attached by probing inside a second run
        seen = []
        pw.io.subscribe(
            pw.debug.table_from_rows(pw.schema_from_types(v=int), [(2,)]),
            on_change=lambda key, row, time, is_addition: (
                seen.append(faults.fire("obs.telemetry.demo"))
            ),
        )
        pw.run()
        assert any(seen), "the demo fault must fire inside the run"
        records = [json.loads(ln) for ln in path.read_text().splitlines()]
        events = [r for r in records if r["kind"] == "event"]
        assert any(
            e.get("k") == "fault" and e.get("point") == "obs.telemetry.demo"
            for e in events
        ), events
    finally:
        obs.disable()
        faults.reset()


def test_non_tty_logger_fallback_stats_line(caplog):
    """When stderr is not a terminal (or rich is unavailable), the
    monitor logs a compact stats line per window through the standard
    logger, identifying hot operators by their plan-node label."""
    import logging

    from pathway_tpu.internals.lowering import Session
    from pathway_tpu.internals.monitoring import attach_monitor

    session = Session()
    t = pw.demo.range_stream(nb_rows=8, input_rate=400)
    session.subscribe(t, on_change=lambda key, row, time, is_addition: None)
    attach_monitor(session, every_n_waves=1, use_tui=False)
    with caplog.at_level(logging.INFO, logger="pathway_tpu.monitor"):
        session.execute()
    lines = [
        r.getMessage() for r in caplog.records
        if r.name == "pathway_tpu.monitor"
    ]
    assert lines, "the non-TTY fallback must log stats lines"
    assert any(
        "rows_out=" in ln and "waves=" in ln and "rate=" in ln
        for ln in lines
    ), lines


def test_stats_monitor_snapshot_distinguishes_same_type_operators():
    """Two groupbys over the same table land as two GroupByNodes; the
    snapshot names them via Node.describe() — plan label + call site +
    id — not the bare class name (they differ at least by id/trace)."""
    from pathway_tpu.internals.lowering import Session
    from pathway_tpu.internals.monitoring import StatsMonitor

    session = Session()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(g=str, h=str, v=int),
        [("a", "x", 1), ("b", "y", 2), ("a", "y", 3)],
    )
    session.capture(t.groupby(t.g).reduce(t.g, n=pw.reducers.count()))
    session.capture(t.groupby(t.h).reduce(t.h, s=pw.reducers.sum(t.v)))
    session.execute()
    mon = StatsMonitor(session)
    snap = mon.snapshot(2)
    ops = [h["op"] for h in snap["hot"]]
    assert all("#" in op for op in ops)
    labeled = [op for op in ops if "[" in op]
    assert labeled, ops
    gb = [
        f"{type(n).__name__}#{n.node_id}" for n in session.graph.nodes
        if type(n).__name__ == "GroupByNode"
    ]
    assert len(gb) == 2 and len(set(gb)) == 2
    described = [
        n.describe() for n in session.graph.nodes
        if type(n).__name__ == "GroupByNode"
    ]
    assert len(set(described)) == 2, described
