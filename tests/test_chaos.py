"""Chaos plane: deterministic fault injection (engine/faults.py), the
unified retry/degradation policy (pw.io.RetryPolicy), device-plane
quarantine, supervised mesh recovery, and the crash-recovery equivalence
drills (scripts/chaos_drill.py) — the persistence layer's exactly-once
claim as a regression-tested invariant."""

from __future__ import annotations

import json
import os
import sys
import textwrap
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.engine import faults

from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import chaos_drill  # noqa: E402


@pytest.fixture(autouse=True)
def _no_lingering_faults():
    faults.reset()
    yield
    faults.reset()


# --------------------------------------------------------- fault schedule


def test_fault_schedule_hits_and_ranges():
    s = faults.FaultSchedule("a.b@2,5;c@3+2")
    assert [s.decide("a.b") for _ in range(6)] == [
        False, True, False, False, True, False,
    ]
    assert [s.decide("c") for _ in range(8)] == [
        False, False, True, False, True, False, True, False,
    ]
    assert not any(s.decide("unlisted") for _ in range(10))
    assert ("a.b", 2) in s.fired and ("c", 3) in s.fired


def test_fault_schedule_glob_and_seeded_probability():
    a = faults.FaultSchedule("seed=7;io.*~0.5")
    b = faults.FaultSchedule("seed=7;io.*~0.5")
    seq_a = [a.decide("io.retry.x") for _ in range(32)]
    seq_b = [b.decide("io.retry.x") for _ in range(32)]
    assert seq_a == seq_b, "same seed must replay identically"
    assert any(seq_a) and not all(seq_a)
    c = faults.FaultSchedule("seed=8;io.*~0.5")
    assert [c.decide("io.retry.x") for _ in range(32)] != seq_a
    assert not any(a.decide("device.dispatch.z") for _ in range(8))


def test_faults_disabled_is_noop(monkeypatch):
    monkeypatch.setenv("PATHWAY_FAULTS", "0")
    faults.reset()
    assert not faults.active()
    assert not faults.fire("anything")
    faults.check("anything")  # must not raise
    faults.crash("anything")  # must not exit


def test_fault_check_raises_connection_error_family():
    faults.install("p@1")
    with pytest.raises(ConnectionError) as ei:
        faults.check("p")
    assert isinstance(ei.value, faults.FaultInjected)
    assert ei.value.point == "p" and ei.value.hit == 1


# ------------------------------------------------------------ RetryPolicy


def _policy(**kw):
    kw.setdefault("initial_delay_ms", 1)
    kw.setdefault("jitter_ms", 0)
    return pw.io.RetryPolicy("test", **kw)


def test_retry_policy_retries_then_succeeds():
    p = _policy(max_attempts=4)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("flap")
        return "ok"

    assert p.call(flaky) == "ok"
    assert calls["n"] == 3 and p.retries_total == 2


def test_retry_policy_exhausts_and_raises():
    p = _policy(max_attempts=3)
    with pytest.raises(ValueError, match="always"):
        p.call(lambda: (_ for _ in ()).throw(ValueError("always")))
    assert p.attempts_total == 3


def test_retry_policy_non_retryable_propagates_immediately():
    p = _policy(max_attempts=5, retry_on=(ConnectionError,))
    calls = {"n": 0}

    def typed():
        calls["n"] += 1
        raise KeyError("fatal")

    with pytest.raises(KeyError):
        p.call(typed)
    assert calls["n"] == 1


def test_retry_policy_breaker_opens_fails_fast_then_recovers():
    opened = []
    p = pw.io.RetryPolicy(
        "brk", max_attempts=1, initial_delay_ms=1, jitter_ms=0,
        breaker_threshold=3, breaker_reset_ms=50,
        on_breaker_open=opened.append,
    )
    for _ in range(3):
        with pytest.raises(ConnectionError):
            p.call(lambda: (_ for _ in ()).throw(ConnectionError("down")))
    assert p.state == "open" and len(opened) == 1
    # fail fast: the function is NOT attempted while open
    calls = {"n": 0}

    def count():
        calls["n"] += 1
        return "up"

    with pytest.raises(pw.io.CircuitOpen):
        p.call(count)
    assert calls["n"] == 0
    time.sleep(0.06)  # cooldown elapses -> half-open probe admitted
    assert p.call(count) == "up"
    assert p.state == "closed" and calls["n"] == 1


def test_retry_policy_half_open_probe_non_retryable_reopens():
    """A non-retryable error from the half-open probe must flip the
    breaker back to open (escalated cooldown), not wedge it in half_open
    where every later call fails fast forever."""
    p = pw.io.RetryPolicy(
        "halfwedge", max_attempts=1, initial_delay_ms=1, jitter_ms=0,
        breaker_threshold=1, breaker_reset_ms=10,
        retry_on=(ConnectionError,),
    )
    with pytest.raises(ConnectionError):
        p.call(lambda: (_ for _ in ()).throw(ConnectionError("down")))
    assert p.state == "open"
    time.sleep(0.02)  # cooldown elapses: next call is the half-open probe
    with pytest.raises(ValueError):
        p.call(lambda: (_ for _ in ()).throw(ValueError("fatal")))
    assert p.state == "open", "probe failure must re-open, not wedge"
    time.sleep(0.03)  # escalated (2x) cooldown elapses
    assert p.call(lambda: "up") == "up"
    assert p.state == "closed"


def test_retry_policy_backoff_caps_and_jitters():
    p = pw.io.RetryPolicy(
        "bo", initial_delay_ms=100, backoff_factor=2.0,
        max_delay_ms=300, jitter_ms=50,
    )
    d = [p.delay_for(a) for a in range(1, 6)]
    assert 0.1 <= d[0] <= 0.15 and 0.2 <= d[1] <= 0.25
    assert all(0.3 <= x <= 0.35 for x in d[2:]), f"cap not applied: {d}"


def test_retry_policy_fault_injectable():
    faults.install("io.retry.test@1")
    p = _policy(max_attempts=3)
    assert p.call(lambda: "v") == "v"
    assert p.retries_total == 1, "injected fault must consume one attempt"


def test_retry_policy_async_invoke_protocol():
    import asyncio

    p = _policy(max_attempts=3)
    calls = {"n": 0}

    async def flaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise ConnectionError("flap")
        return 42

    async def run():
        return await p.invoke(flaky)

    assert asyncio.run(run()) == 42
    assert calls["n"] == 2


# --------------------------------------------- device-plane degradation


def test_device_program_quarantine_fallback_and_reprobe(monkeypatch):
    import numpy as np

    from pathway_tpu.engine.device_plane import DeviceProgram

    monkeypatch.setattr(DeviceProgram, "PROBE_BASE_S", 0.04)
    faults.install("device.dispatch.q-test@1,2")
    prog = DeviceProgram("q-test", lambda x: x * 3)
    x = np.arange(4.0)
    # dispatch 1: injected failure -> quarantined, host path, right answer
    assert np.allclose(prog(x, bucket=4), x * 3)
    assert prog.quarantine[4]["failures"] == 1 and prog.host_fallbacks == 1
    # still cooling: host path again, no probe consumed
    assert np.allclose(prog(x, bucket=4), x * 3)
    assert prog.host_fallbacks == 2
    time.sleep(0.06)
    # re-probe admitted -> injected failure #2 -> cooldown doubles
    prog(x, bucket=4)
    assert prog.quarantine[4]["failures"] == 2
    time.sleep(0.1)
    # re-probe succeeds -> quarantine lifted, compile charged exactly once
    assert np.allclose(prog(x, bucket=4), x * 3)
    assert not prog.quarantine
    assert prog.compile_counts == {4: 1}


def test_device_plane_quarantined_accessor():
    import numpy as np

    from pathway_tpu.engine.device_plane import DevicePlane

    plane = DevicePlane()
    faults.install("device.dispatch.acc@1")
    prog = plane.program("acc", lambda x: x + 1)
    prog(np.ones(2), bucket=2)
    q = plane.quarantined()
    assert ("acc", 2) in q and q[("acc", 2)]["failures"] == 1


# ----------------------------------------------------------- sink retries


def test_output_sink_flaky_write_succeeds_on_retry():
    from pathway_tpu.internals.parse_graph import G

    t = pw.debug.table_from_rows(pw.schema_from_types(v=int), [(1,), (2,)])
    state = {"fails": 2, "rows": []}

    def write_batch(time_, entries):
        if state["fails"] > 0:
            state["fails"] -= 1
            raise ConnectionError("sink down")
        state["rows"].extend(row for _k, row, d in entries if d > 0)

    G.add_sink("output", t, write_batch=write_batch)
    pw.run()
    assert sorted(state["rows"]) == [(1,), (2,)]
    assert state["fails"] == 0


def test_logstash_flaky_sink_succeeds_on_retry(monkeypatch):
    """Satellite: pw.io.logstash.write(retry_policy=...) is honored — a
    sink that refuses the first two requests still delivers every row."""
    import requests

    seen: list[dict] = []
    state = {"fails": 2}

    def fake_request(method, url, json=None, headers=None, timeout=None):
        assert method == "POST" and url == "http://logstash.test/in"
        if state["fails"] > 0:
            state["fails"] -= 1
            raise ConnectionError("connection refused")
        seen.append(json)

    monkeypatch.setattr(requests, "request", fake_request)
    policy = pw.io.RetryPolicy(
        "logstash", max_attempts=4, initial_delay_ms=1, jitter_ms=0,
    )
    t = pw.debug.table_from_rows(
        pw.schema_from_types(word=str, n=int), [("a", 1), ("b", 2)]
    )
    pw.io.logstash.write(t, "http://logstash.test/in", retry_policy=policy)
    pw.run()
    assert sorted((d["word"], d["n"]) for d in seen) == [("a", 1), ("b", 2)]
    assert all("time" in d and "diff" in d for d in seen)
    assert policy.retries_total == 2, "the flaps must be absorbed by retry"


# ------------------------------------------- crash-recovery equivalence


def test_chaos_equivalence_matrix(tmp_path):
    """THE acceptance drill: every fault kind x 3 seeds — engine windows
    AND the transactional-sink windows (pre-seal, post-seal, torn
    mid-flush) — recovers to DELIVERED sink output (fs + kafka-mock +
    http, post-replay, post-dedup) byte-identical to the fault-free
    baseline."""
    report = chaos_drill.run_matrix(
        sorted(chaos_drill.KINDS), [0, 1, 2], workdir=str(tmp_path)
    )
    assert report["ok"], "\n".join(report.get("failures", []))
    expected_kinds = 10 if report["exactly_once"] else 7
    assert len(report["cases"]) >= expected_kinds * 3
    crashed = [c for c in report["cases"] if c["generations"] > 1]
    min_crash = (8 if report["exactly_once"] else 5) * 3
    assert len(crashed) >= min_crash, "crash kinds must actually crash"
    base = report["baseline"]
    if report["exactly_once"]:
        assert set(base) == {"fs", "kafka", "http"}
    for case in report["cases"]:
        assert case["outputs"] == base, (case["kind"], case["seed"])


# --------------------------------------------- supervised mesh recovery


MESH_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw
    from pathway_tpu.io.python import ConnectorSubject

    OUT, PDIR = sys.argv[1], sys.argv[2]
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class Part(ConnectorSubject):
        def __init__(self, lo, hi):
            self.lo, self.hi = lo, hi
        def run(self):
            import time
            for i in range(self.lo, self.hi):
                self.next(g=f"g{{i % 5}}", v=i)
                time.sleep(0.004)

    a = pw.io.python.read(Part(0, 30), schema=pw.schema_from_types(g=str, v=int), name="a")
    b = pw.io.python.read(Part(30, 60), schema=pw.schema_from_types(g=str, v=int), name="b")
    t = a.concat_reindex(b)
    agg = t.groupby(t.g).reduce(t.g, total=pw.reducers.sum(t.v), n=pw.reducers.count())
    sink = open(OUT + f".{{PID}}.jsonl", "a")
    sink.write("\\n")  # newline guard: terminate a torn pre-crash line
    def on_change(key, row, time, is_addition):
        sink.write(json.dumps({{"g": row["g"], "t": row["total"], "n": row["n"],
                                "add": is_addition}}) + "\\n")
        sink.flush()
    pw.io.subscribe(agg, on_change=on_change)
    pw.run(persistence_config=pw.persistence.Config(
        pw.persistence.Backend.filesystem(PDIR)))
    """
)


def _consolidate_mesh(out_base: str, n: int) -> dict:
    combined: dict = {}
    for pid in range(n):
        state: dict = {}
        path = f"{out_base}.{pid}.jsonl"
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                if not line.strip():
                    continue  # generation-boundary newline guard
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue  # torn line from the crash
                if ev["add"]:
                    state[ev["g"]] = (ev["t"], ev["n"])
                elif state.get(ev["g"]) == (ev["t"], ev["n"]):
                    del state[ev["g"]]
        for g, v in state.items():
            combined[g] = v
    return combined


def test_supervised_mesh_restarts_after_worker_crash(tmp_path):
    """A worker dying mid-wave must not hang the mesh: peers abort with
    WorkerLost, the supervisor restarts the generation, and the restarted
    mesh resumes from the negotiated checkpoint epoch to EXACT results."""
    from pathway_tpu.parallel.supervisor import run_supervised

    out = str(tmp_path / "mesh-out")
    pdir = str(tmp_path / "mesh-pdir")
    base = free_port_base(2)
    result = run_supervised(
        [sys.executable, "-c", MESH_SCRIPT.format(repo=REPO), out, pdir],
        n_processes=2,
        first_port=base,
        max_restarts=3,
        env={
            "JAX_PLATFORMS": "cpu",
            # hit 3 of 5-6 firing rounds per worker on a quiet 2-CPU box
            # (the point probes inside _pump_mesh, so fence-quiesce waves
            # count too) — low enough to fire even when load coalesces
            # events into fewer, bigger waves
            "PATHWAY_FAULTS": "runtime.mesh.wave@3",
        },
        timeout_s=300.0,
    )
    assert result["generations"] >= 2, "the injected crash never fired"
    expected: dict = {}
    for i in range(60):
        g = f"g{i % 5}"
        t0, n0 = expected.get(g, (0, 0))
        expected[g] = (t0 + i, n0 + 1)
    combined = _consolidate_mesh(out, 2)
    assert combined == expected, (combined, expected)
