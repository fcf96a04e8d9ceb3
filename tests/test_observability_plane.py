"""The unified observability plane (internals/observability.py): wave
tracing spans, the metrics registry + OpenMetrics/statistics endpoints,
the pipeline profiler, and the crash flight recorder — plus the
result-invariance contract (instrumentation on == instrumentation off,
byte for byte)."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import textwrap
import time
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.engine import faults
from pathway_tpu.internals import observability as obs
from pathway_tpu.internals.parse_graph import G

from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_graph_and_plane():
    G.clear()
    yield
    obs.disable()
    faults.reset()
    G.clear()


def _run_small_pipeline() -> list[dict]:
    t = pw.debug.table_from_rows(
        pw.schema_from_types(g=str, v=int),
        [("a", 1), ("b", 2), ("a", 3), ("c", 4)],
    )
    agg = t.groupby(t.g).reduce(t.g, s=pw.reducers.sum(t.v))
    seen: list[dict] = []
    pw.io.subscribe(
        agg,
        on_change=lambda key, row, time, is_addition: seen.append(dict(row)),
    )
    pw.run()
    return seen


# ------------------------------------------------------------ wave tracing


def test_wave_tracing_records_operator_spans():
    """Every fired (operator, wave) leaves a structured span in the ring
    with exec/queue/stash micros and the plan-node label, and feeds the
    per-operator latency histogram."""
    obs.enable()
    _run_small_pipeline()
    waves = [e for e in obs.PLANE.recorder.snapshot() if e["k"] == "wave"]
    assert waves, "wave spans must be recorded"
    for ev in waves:
        assert {"node", "op", "label", "t", "q_us", "x_us", "s_us"} <= set(ev)
    ops = {(e["op"], e["label"]) for e in waves}
    assert ("GroupByNode", "groupby") in ops, ops
    snap = obs.PLANE.metrics.snapshot()
    hist = snap["pathway_operator_wave_seconds"]
    assert hist["type"] == "histogram"
    assert sum(s["count"] for s in hist["series"]) >= len(waves)
    labeled = {s["labels"]["operator"] for s in hist["series"]}
    assert "GroupByNode" in labeled


def test_wave_tracing_on_streaming_pump_includes_queue_wait():
    """The frontier pump's spans carry queue-wait (staging -> fire)."""
    obs.enable()
    t = pw.demo.range_stream(nb_rows=8, input_rate=500)
    agg = t.reduce(n=pw.reducers.count())
    pw.io.subscribe(agg, on_change=lambda key, row, time, is_addition: None)
    pw.run()
    waves = [e for e in obs.PLANE.recorder.snapshot() if e["k"] == "wave"]
    assert waves
    assert any(e["q_us"] > 0 for e in waves), "queue wait must be measured"


def test_straggler_timeline_reconstructable_from_ring():
    """Two causally-independent branches, one slowed per row: the ring's
    wave spans reconstruct each branch's timeline — which operator fired
    at which timestamp, for how long — without rerunning anything."""
    obs.enable()

    def slow_id(v):
        time.sleep(0.002)
        return v

    fast = pw.debug.table_from_rows(
        pw.schema_from_types(v=int),
        [(i, 2 * i + 2, 1) for i in range(6)],
        is_stream=True,
    )
    slow = pw.debug.table_from_rows(
        pw.schema_from_types(v=int),
        [(10 + i, 2 * i + 2, 1) for i in range(6)],
        is_stream=True,
    )
    slow2 = slow.select(v=pw.apply(slow_id, slow.v))
    fa = fast.reduce(n=pw.reducers.count())
    sa = slow2.reduce(n=pw.reducers.count())
    pw.io.subscribe(fa, on_change=lambda key, row, time, is_addition: None)
    pw.io.subscribe(sa, on_change=lambda key, row, time, is_addition: None)
    pw.run()
    waves = [e for e in obs.PLANE.recorder.snapshot() if e["k"] == "wave"]
    # timeline per (operator, slot): ordered (t, exec) — the
    # reconstruction the flight recorder promises for the straggler
    # experiment. An operator's OWN waves fire in time order; remote
    # injections below an exchange node are their own ordered lane
    # (inj=1), which is why the key includes it.
    timelines: dict[tuple, list] = {}
    for ev in waves:
        if isinstance(ev["t"], (int, float)):
            timelines.setdefault((ev["node"], ev["inj"]), []).append(
                (ev["t"], ev["x_us"])
            )
    assert timelines
    for tl in timelines.values():
        assert tl == sorted(tl), "per-operator wave times must be ordered"
    slow_nodes = [
        ev["node"] for ev in waves
        if ev["op"] == "RowwiseNode" and ev["x_us"] >= 2000
    ]
    assert slow_nodes, "the slowed branch's waves must show their latency"


# ------------------------------------------------------- metrics endpoint


# OpenMetrics exposition grammar (the subset we emit): metric lines are
#   name{label="value",...} number
# plus # TYPE / # EOF comment lines.
_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*"'
_VALUE = r"(?:[-+]?[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|[-+]?Inf|NaN)"
_METRIC_RE = re.compile(
    rf"^{_NAME}(?:\{{{_LABEL}(?:,{_LABEL})*\}})? {_VALUE}$"
)
_TYPE_RE = re.compile(
    rf"^# TYPE {_NAME} (?:counter|gauge|histogram|summary|untyped)$"
)


def _assert_openmetrics(body: str) -> list[str]:
    lines = body.splitlines()
    assert lines[-1] == "# EOF"
    for ln in lines[:-1]:
        assert ln, "no blank lines inside the exposition"
        if ln.startswith("#"):
            assert _TYPE_RE.match(ln), f"bad comment line: {ln!r}"
        else:
            assert _METRIC_RE.match(ln), f"bad metric line: {ln!r}"
    return lines


def test_metrics_endpoint_full_scrape_parses_against_grammar():
    """Every exposition line — operator counters, wave-latency histogram
    buckets, watermark gauges, breaker states — parses against the
    OpenMetrics grammar."""
    from pathway_tpu.internals.lowering import Session
    from pathway_tpu.internals.metrics import start_metrics_server
    from pathway_tpu.io import RetryPolicy

    obs.enable()
    policy = RetryPolicy("obs-test", max_attempts=1, breaker_threshold=None)
    policy.call(lambda: 1)
    session = Session()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(g=str, v=int), [("a", 1), ("b", 2)]
    )
    session.capture(t.groupby(t.g).reduce(t.g, n=pw.reducers.count()))
    port = free_port_base()
    start_metrics_server(session, port=port)
    session.execute()
    body = ""
    for _ in range(100):
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5
            ).read().decode()
            break
        except OSError:
            time.sleep(0.1)
    lines = _assert_openmetrics(body)
    joined = "\n".join(lines)
    assert "pathway_operator_rows_in" in joined
    assert "pathway_operator_wave_seconds_bucket" in joined
    assert 'le="+Inf"' in joined
    assert "pathway_operator_wave_seconds_count" in joined
    assert "pathway_breaker_state" in joined
    # per-operator labels carry the plan-node label
    assert 'label="groupby"' in joined


def test_label_values_are_escaped():
    from pathway_tpu.internals.metrics import _escape, _labels

    assert _escape('a"b') == 'a\\"b'
    assert _escape("a\\b") == "a\\\\b"
    assert _escape("a\nb") == "a\\nb"
    rendered = _labels({"name": 'we"ird\\path\nx'})
    assert rendered == '{name="we\\"ird\\\\path\\nx"}'
    # a crafted label value round-trips through the full renderer
    from pathway_tpu.internals.lowering import Session
    from pathway_tpu.internals.metrics import _render_metrics

    session = Session()
    t = pw.debug.table_from_rows(pw.schema_from_types(v=int), [(1,)])
    cap = session.capture(t)
    cap.label = 'odd"label\\with\nstuff'
    session.execute()
    body = _render_metrics(session, time.time())
    _assert_openmetrics(body)
    assert '\\"label' in body


def test_statistics_json_route_and_404():
    from pathway_tpu.internals.lowering import Session
    from pathway_tpu.internals.metrics import start_metrics_server

    obs.enable()
    session = Session()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(g=str, v=int), [("a", 1), ("a", 2), ("b", 3)]
    )
    session.capture(t.groupby(t.g).reduce(t.g, n=pw.reducers.count()))
    port = free_port_base()
    start_metrics_server(session, port=port)
    session.execute()
    stats = None
    for _ in range(100):
        try:
            stats = json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/statistics", timeout=5
                ).read()
            )
            break
        except OSError:
            time.sleep(0.1)
    assert stats is not None
    assert stats["run_id"] == obs.PLANE.run_id
    ops = stats["operators"]
    assert any(o["label"] == "groupby" and o["rows_in"] for o in ops)
    assert all("name" in o and "latency_ms" in o for o in ops)
    assert "pathway_operator_wave_seconds" in stats["metrics"]
    with pytest.raises(urllib.request.HTTPError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/nope", timeout=5
        )


def test_watermark_lag_and_frontier_age_gauges():
    """The streaming pump publishes per-source watermark lag + frontier
    age through the registry."""
    obs.enable()
    t = pw.demo.range_stream(nb_rows=10, input_rate=200)
    agg = t.reduce(n=pw.reducers.count())
    pw.io.subscribe(agg, on_change=lambda key, row, time, is_addition: None)
    pw.run()
    snap = obs.PLANE.metrics.snapshot()
    assert "pathway_source_watermark_lag_seconds" in snap
    series = snap["pathway_source_watermark_lag_seconds"]["series"]
    assert all("source" in s["labels"] for s in series)
    assert "pathway_frontier_age_seconds" in snap


# ------------------------------------------------------------- profiler


def test_profiler_attributes_wall_clock(tmp_path):
    prof_path = str(tmp_path / "profile.json")
    inp = tmp_path / "in.jsonl"
    inp.write_text(
        "\n".join('{"g": "g%d", "v": %d}' % (i % 7, i) for i in range(5000))
        + "\n"
    )
    t = pw.io.fs.read(
        str(inp), format="json",
        schema=pw.schema_from_types(g=str, v=int), mode="static",
    )
    agg = t.groupby(t.g).reduce(t.g, s=pw.reducers.sum(t.v))
    pw.io.csv.write(agg, str(tmp_path / "out.csv"))
    pw.run(profile=prof_path)
    with open(prof_path) as f:
        rep = json.load(f)
    assert rep["attributed_pct"] >= 90.0, rep["stages"]
    assert rep["total_s"] > 0
    assert 0.0 <= rep["ingest_share"] <= 1.0
    stages = rep["stages"]
    assert {"ingest", "compute", "emit", "build", "unattributed"} <= set(stages)
    ops = rep["operators"]
    assert any(o["operator"] == "GroupByNode" and o["stage"] == "compute"
               for o in ops)
    assert any(o["label"] == "output" and o["stage"] == "emit" for o in ops)
    # shares are consistent: attributed fraction matches the stage sum
    assert abs(
        sum(v for k, v in stages.items() if k != "unattributed")
        + stages["unattributed"] - rep["total_s"]
    ) < 0.05 * rep["total_s"] + 0.01


# ------------------------------------------------------- flight recorder


def test_flight_recorder_dump_contains_fired_faults(tmp_path):
    obs.enable(flight_dir=str(tmp_path))
    faults.install("obs.test.point@1,2;obs.test.other@1")
    assert faults.fire("obs.test.point") is True
    assert faults.fire("obs.test.point") is True
    assert faults.fire("obs.test.point") is False
    with pytest.raises(faults.FaultInjected):
        faults.check("obs.test.other")
    path = obs.dump_flight("test")
    assert path and os.path.exists(path)
    with open(path) as f:
        payload = json.load(f)
    fired = {tuple(x) for x in payload["faults_fired"]}
    assert ("obs.test.point", 1) in fired and ("obs.test.other", 1) in fired
    events = {
        (e["point"], e["hit"])
        for e in payload["events"] if e["k"] == "fault"
    }
    assert fired <= events, (fired, events)
    assert payload["run_id"] == obs.PLANE.run_id


def test_flight_recorder_ring_is_bounded(tmp_path):
    plane = obs.enable(ring_size=16, flight_dir=str(tmp_path))
    for i in range(100):
        plane.record("tick", i=i)
    events = plane.recorder.snapshot()
    assert len(events) == 16
    assert events[-1]["i"] == 99  # newest kept, oldest dropped


def test_runtime_error_dumps_flight_recorder(tmp_path):
    """A run that dies mid-stream leaves a postmortem dump with the wave
    context that preceded the error."""
    obs.enable(flight_dir=str(tmp_path))

    def boom(v):
        raise RuntimeError("wave bomb")

    t = pw.demo.range_stream(nb_rows=4, input_rate=500)
    bad = t.select(v=pw.apply(boom, t.value))
    pw.io.subscribe(bad, on_change=lambda key, row, time, is_addition: None)
    with pytest.raises(RuntimeError):
        pw.run(terminate_on_error=True, observability=True)
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight-")]
    assert dumps, "runtime error must dump the flight recorder"
    with open(tmp_path / dumps[0]) as f:
        payload = json.load(f)
    kinds = {e["k"] for e in payload["events"]}
    assert "runtime.error" in kinds or "wave" in kinds


# --------------------------------------------------- breaker/retry events


def test_retry_and_breaker_feed_the_spine():
    from pathway_tpu.io import RetryPolicy

    obs.enable()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        raise ConnectionError("nope")

    policy = RetryPolicy(
        "spine-test", max_attempts=2, initial_delay_ms=1, jitter_ms=0,
        breaker_threshold=2, breaker_reset_ms=10_000,
    )
    with pytest.raises(ConnectionError):
        policy.call(flaky)
    assert policy.state == "open"
    kinds = [e["k"] for e in obs.PLANE.recorder.snapshot()]
    assert "retry.failure" in kinds and "breaker.open" in kinds
    snap = obs.PLANE.metrics.snapshot()
    assert "pathway_retry_failures_total" in snap
    assert "pathway_breaker_opens_total" in snap
    assert policy in obs.retry_policies()


# ------------------------------------------------------ result invariance


_AB_SCRIPT = textwrap.dedent(
    """
    import sys
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw

    INP, OUT = sys.argv[1], sys.argv[2]
    t = pw.io.fs.read(
        INP, format="json",
        schema=pw.schema_from_types(g=str, v=int), mode="static",
    )
    agg = t.groupby(t.g).reduce(
        t.g, s=pw.reducers.sum(t.v), n=pw.reducers.count()
    )
    pw.io.csv.write(agg, OUT)
    pw.run()
    """
)


def test_instrumentation_is_result_invariant(tmp_path):
    """Full instrumentation (plane + profiler + telemetry + flight dir)
    must leave pipeline output byte-identical to an uninstrumented run —
    the observability leg's core contract."""
    inp = tmp_path / "in.jsonl"
    inp.write_text(
        "\n".join('{"g": "g%d", "v": %d}' % (i % 11, i) for i in range(4000))
        + "\n"
    )
    outs = {}
    for mode, extra_env in (
        ("off", {}),
        ("on", {
            "PATHWAY_OBSERVABILITY": "1",
            "PATHWAY_PROFILE": str(tmp_path / "prof.json"),
            "PATHWAY_FLIGHT_DIR": str(tmp_path / "flight"),
            "PATHWAY_TELEMETRY_FILE": str(tmp_path / "tel.jsonl"),
        }),
    ):
        out = tmp_path / f"out_{mode}.csv"
        env = {**os.environ, "JAX_PLATFORMS": "cpu", **extra_env}
        env.pop("PATHWAY_OBSERVABILITY", None) if mode == "off" else None
        r = subprocess.run(
            [sys.executable, "-c", _AB_SCRIPT.format(repo=REPO),
             str(inp), str(out)],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        outs[mode] = out.read_bytes()
    assert outs["on"] == outs["off"]
    # the instrumented run actually instrumented: profile written, spans
    # in the telemetry file
    assert (tmp_path / "prof.json").exists()
    assert (tmp_path / "tel.jsonl").exists()


# -------------------------------------------------- cross-worker tracing


_MESH_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw
    from pathway_tpu.internals import observability as obs
    from pathway_tpu.io.python import ConnectorSubject

    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class Part(ConnectorSubject):
        def __init__(self, lo, hi):
            self.lo, self.hi = lo, hi
        def run(self):
            import time
            for i in range(self.lo, self.hi):
                self.next(g=f"g{{i % 4}}", v=i)
                time.sleep(0.002)

    a = pw.io.python.read(
        Part(0, 20), schema=pw.schema_from_types(g=str, v=int), name="a")
    b = pw.io.python.read(
        Part(20, 40), schema=pw.schema_from_types(g=str, v=int), name="b")
    t = a.concat_reindex(b)
    agg = t.groupby(t.g).reduce(t.g, total=pw.reducers.sum(t.v))
    pw.io.subscribe(agg, on_change=lambda key, row, time, is_addition: None)
    pw.run()
    obs.dump_flight("mesh-end")
    """
)


@pytest.mark.slow
def test_mesh_frames_carry_trace_context(tmp_path):
    """Data frames crossing the process mesh are tagged with trace
    context; joining both workers' dumps on (run, seq) reconstructs the
    cross-worker wave path."""
    base = free_port_base(2)
    flight = {p: str(tmp_path / f"flight{p}") for p in range(2)}
    procs = []
    for pid in range(2):
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PATHWAY_PROCESSES": "2",
            "PATHWAY_PROCESS_ID": str(pid),
            "PATHWAY_FIRST_PORT": str(base),
            "PATHWAY_OBSERVABILITY": "1",
            "PATHWAY_FLIGHT_DIR": flight[pid],
        }
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _MESH_SCRIPT.format(repo=REPO)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
        )
    for p in procs:
        _o, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-3000:]
    events: dict[int, list] = {}
    run_id: dict[int, str] = {}
    for pid in range(2):
        evs = []
        for fn in os.listdir(flight[pid]):
            with open(os.path.join(flight[pid], fn)) as f:
                payload = json.load(f)
            evs.extend(payload["events"])
            run_id[pid] = payload["run_id"]
        events[pid] = evs
    sent_by_1 = [e for e in events[1] if e["k"] == "mesh.send"]
    recv_by_0 = [e for e in events[0] if e["k"] == "mesh.recv"]
    assert sent_by_1, "worker 1 must have sent tagged frames"
    assert recv_by_0, "worker 0 must have received tagged frames"
    # the join: a frame worker 1 sent shows up on worker 0 under worker
    # 1's run id + sequence number — the cross-worker reconstruction key
    sent_keys = {(run_id[1], e["seq"]) for e in sent_by_1 if e["to"] == 0}
    recv_keys = {(e["run"], e["seq"]) for e in recv_by_0 if e["frm"] == 1}
    assert sent_keys & recv_keys, (sorted(sent_keys)[:5], sorted(recv_keys)[:5])


def test_profiler_pretimes_do_not_leak_across_runs(tmp_path):
    """A second profiled pw.run in the same process must not re-count
    the first run's static-ingest parse time (pretimes are consumed per
    report)."""
    inp = tmp_path / "in.jsonl"
    inp.write_text(
        "\n".join('{"v": %d}' % i for i in range(20000)) + "\n"
    )
    t = pw.io.fs.read(
        str(inp), format="json",
        schema=pw.schema_from_types(v=int), mode="static",
    )
    pw.io.csv.write(
        t.reduce(s=pw.reducers.sum(pw.this.v)), str(tmp_path / "o1.csv")
    )
    pw.run(profile=str(tmp_path / "p1.json"))
    with open(tmp_path / "p1.json") as f:
        rep1 = json.load(f)
    assert rep1["stages"]["ingest"] > 0
    G.clear()
    # second run has NO static fs ingest at all
    t2 = pw.debug.table_from_rows(pw.schema_from_types(v=int), [(1,), (2,)])
    pw.io.csv.write(
        t2.reduce(s=pw.reducers.sum(pw.this.v)), str(tmp_path / "o2.csv")
    )
    pw.run(profile=str(tmp_path / "p2.json"))
    with open(tmp_path / "p2.json") as f:
        rep2 = json.load(f)
    assert rep2["stages"].get("ingest", 0.0) < rep1["stages"]["ingest"] / 10
