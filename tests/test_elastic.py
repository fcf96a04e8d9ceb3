"""Elastic mesh membership + blue/green plan swaps (parallel/membership.py,
parallel/bluegreen.py).

The contract under test:

* a worker JOIN or LEAVE announced mid-run quiesces the generation to a
  checkpoint fence, rebalances only the moved state shards (journals,
  operator snapshots, spilled runs — metadata moves, no whole-journal
  replay), and resumes at the new width with the SAME delivered output a
  never-rescaled mesh produces;
* a blue/green whole-plan swap commits only when the green run's
  fence-epoch replay is byte-identical to the baseline AND the verifier's
  swap contract holds — any abort leaves the blue root byte-for-byte
  untouched;
* outbox delivery watermarks and connector offsets ride the swap.

Consolidation note: group ownership MOVES across worker output files at
a rebalance, so delivered events must be replayed in global delivery
order (each event carries a wall-clock stamp) — per-file order would let
a retired owner's stale final state shadow the new owner's.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a streaming groupby workload; each delivery is stamped with wall time
# so the harness can consolidate across ownership moves (module note)
MESH_WORKER = textwrap.dedent(
    """
    import json, os, sys, time
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw
    from pathway_tpu.io.python import ConnectorSubject

    PDIR, OUT, READY, N = sys.argv[1], sys.argv[2], sys.argv[3], int(sys.argv[4])
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class Nums(ConnectorSubject):
        def run(self):
            for i in range(N):
                self.next(g=f"g{{i % 4}}", v=i)
                if i == 5:
                    open(READY + f".{{PID}}", "w").write("up")
                time.sleep(0.01)

    t = pw.io.python.read(
        Nums(), schema=pw.schema_from_types(g=str, v=int), name="nums"
    )
    agg = t.groupby(t.g).reduce(
        t.g, total=pw.reducers.sum(t.v), n=pw.reducers.count()
    )
    sink = open(OUT + f".{{PID}}", "a")
    def on_change(key, row, time, is_addition):
        sink.write(json.dumps({{**row, "add": is_addition,
                               "ts": __import__("time").time()}}) + "\\n")
        sink.flush()
    pw.io.subscribe(agg, on_change=on_change)
    pw.run(persistence_config=pw.persistence.Config(
        pw.persistence.Backend.filesystem(PDIR)))
    """
).format(repo=REPO)

N_EVENTS = 160

# the rebalance tests are ABOUT elastic-on; under the kill-switch CI leg
# (scripts/test_both_planes.py elastic-off, PATHWAY_ELASTIC=0) they do
# not apply — the bypass contract is test_elastic_off_is_a_bypass
requires_elastic = pytest.mark.skipif(
    os.environ.get("PATHWAY_ELASTIC") == "0",
    reason="elastic disabled (PATHWAY_ELASTIC=0 leg)",
)


def _consolidate(out_prefix: str, max_pids: int) -> dict:
    """Final table from the delivered add/remove stream, replayed in
    GLOBAL delivery order across all worker files."""
    events = []
    for pid in range(max_pids):
        path = out_prefix + f".{pid}"
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for i, line in enumerate(f):
                ev = json.loads(line)
                events.append((ev["ts"], pid, i, ev))
    events.sort(key=lambda e: (e[0], e[1], e[2]))
    state: dict = {}
    for _, _, _, ev in events:
        if ev["add"]:
            state[ev["g"]] = (ev["total"], ev["n"])
        elif state.get(ev["g"]) == (ev["total"], ev["n"]):
            del state[ev["g"]]
    return state


def _expected(n_events: int) -> dict:
    exp: dict = {}
    for i in range(n_events):
        g = f"g{i % 4}"
        t0, n0 = exp.get(g, (0, 0))
        exp[g] = (t0 + i, n0 + 1)
    return exp


def _run_elastic(tmp_path, start_n: int, announce):
    """run_supervised with `announce(state_dir)` fired once the source
    is up; returns (result, consolidated final state)."""
    from pathway_tpu.parallel.supervisor import run_supervised

    os.makedirs(tmp_path, exist_ok=True)
    pdir = str(tmp_path / "pstate")
    out = str(tmp_path / "deliveries")
    ready = str(tmp_path / "ready")
    base = free_port_base(max(start_n, start_n + 1))
    argv = [sys.executable, "-c", MESH_WORKER, pdir, out, ready,
            str(N_EVENTS)]

    def _announcer():
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not os.path.exists(ready + ".0"):
            time.sleep(0.05)
        time.sleep(0.4)  # let a few checkpoint epochs land first
        announce(pdir)

    th = threading.Thread(target=_announcer)
    th.start()
    try:
        res = run_supervised(
            argv, start_n, base,
            env={"JAX_PLATFORMS": "cpu", "PATHWAY_THREADS": "2"},
            timeout_s=240, state_dir=pdir,
        )
    finally:
        th.join()
    return res, _consolidate(out, start_n + 2), pdir


# ------------------------------------------------ membership protocol units


def test_membership_intents_fold_and_cancel(tmp_path):
    from pathway_tpu.parallel import membership as mb

    root = str(tmp_path)
    mb.announce_join(root)
    mb.announce_join(root)
    mb.announce_leave(root)
    assert mb.pending_intents(root) == (2, 1)
    assert mb.plan_membership(root, current_n=2) == 3
    rec = mb.load_membership(root)
    assert rec is not None and rec["n"] == 3 and rec["prev_n"] == 2
    assert not rec["rebalanced"]
    # intents survive the plan: they are only cleared when the rebalance
    # COMMITS (a generation crashing pre-quiesce must not lose them)
    assert mb.pending_intents(root) == (2, 1)
    mb.clear_intents(root)

    # a join+leave pair cancels out: planning is a no-op and the spent
    # intents are dropped immediately
    mb.announce_join(root)
    mb.announce_leave(root)
    assert mb.plan_membership(root, current_n=3) == 3
    assert mb.pending_intents(root) == (0, 0)


def test_membership_never_plans_below_min(tmp_path):
    from pathway_tpu.parallel import membership as mb

    root = str(tmp_path)
    for _ in range(5):
        mb.announce_leave(root)
    assert mb.plan_membership(root, current_n=3) == mb.MIN_MEMBERS


def test_elastic_kill_switch(monkeypatch, tmp_path):
    from pathway_tpu.parallel import membership as mb

    monkeypatch.setenv("PATHWAY_ELASTIC", "0")
    assert not mb.elastic_enabled()
    monkeypatch.delenv("PATHWAY_ELASTIC", raising=False)
    assert mb.elastic_enabled()


def test_quiesce_request_lifecycle(tmp_path):
    from pathway_tpu.parallel import membership as mb

    root = str(tmp_path)
    assert not mb.quiesce_requested(root)
    mb.request_quiesce(root)
    assert mb.quiesce_requested(root)
    mb.clear_quiesce(root)
    assert not mb.quiesce_requested(root)


def test_recover_rebalance_discards_stale_staging(tmp_path):
    from pathway_tpu.parallel import membership as mb

    root = str(tmp_path)
    os.makedirs(os.path.join(root, "proc-0.stage"))
    assert mb.recover_rebalance(root) is False
    # no commit marker: abandoned staging is garbage, never promoted
    assert not os.path.isdir(os.path.join(root, "proc-0.stage"))


def test_member_fault_points_probe(monkeypatch, tmp_path):
    from pathway_tpu.engine import faults
    from pathway_tpu.parallel import membership as mb

    monkeypatch.setenv("PATHWAY_FAULTS", "mesh.member.join@1")
    faults.reset()
    with pytest.raises(ConnectionError):
        mb.announce_join(str(tmp_path))
    monkeypatch.setenv("PATHWAY_FAULTS", "0")
    faults.reset()


# --------------------------------------------- elastic rebalance, A/B


@requires_elastic
def test_elastic_join_matches_static_mesh(tmp_path):
    """GROW 2->3 mid-run: the rebalanced mesh's delivered output must
    equal both the analytic table and a never-rescaled static mesh's."""
    from pathway_tpu.parallel import membership as mb
    from pathway_tpu.parallel.supervisor import run_supervised

    res, state, pdir = _run_elastic(
        tmp_path / "elastic", start_n=2, announce=mb.announce_join
    )
    assert res["rebalances"] == 1 and res["members"] == 3
    rec = mb.load_membership(pdir)
    assert rec is not None and rec["n"] == 3 and rec["rebalanced"]

    # static control: same workload, same width it STARTED at, no join
    sdir = tmp_path / "static"
    os.makedirs(sdir)
    base = free_port_base(2)
    argv = [sys.executable, "-c", MESH_WORKER, str(sdir / "pstate"),
            str(sdir / "deliveries"), str(sdir / "ready"), str(N_EVENTS)]
    sres = run_supervised(
        argv, 2, base,
        env={"JAX_PLATFORMS": "cpu", "PATHWAY_THREADS": "2"},
        timeout_s=240,
    )
    assert sres["generations"] == 1
    static_state = _consolidate(str(sdir / "deliveries"), 2)

    assert state == _expected(N_EVENTS)
    assert state == static_state


@requires_elastic
@pytest.mark.slow
def test_elastic_leave_matches_static_mesh(tmp_path):
    """SHRINK 3->2 mid-run: retired-process shards (journals, snapshots)
    re-home as metadata moves and the output stays identical."""
    from pathway_tpu.parallel import membership as mb

    res, state, pdir = _run_elastic(
        tmp_path / "elastic", start_n=3, announce=mb.announce_leave
    )
    assert res["rebalances"] == 1 and res["members"] == 2
    rec = mb.load_membership(pdir)
    assert rec is not None and rec["n"] == 2 and rec["rebalanced"]
    assert state == _expected(N_EVENTS)
    # the retired slot's root is renamed aside, not deleted (debuggable,
    # and crash-redoable roll-forward depends on the rename pair)
    assert os.path.isdir(os.path.join(pdir, "proc-2.retired"))


def test_elastic_off_is_a_bypass(tmp_path, monkeypatch):
    """PATHWAY_ELASTIC=0: intents are ignored, no quiesce, one
    generation, byte-identical output — the kill-switch contract."""
    from pathway_tpu.parallel import membership as mb

    monkeypatch.setenv("PATHWAY_ELASTIC", "0")
    res, state, pdir = _run_elastic(
        tmp_path / "off", start_n=2, announce=mb.announce_join
    )
    assert res["generations"] == 1 and res.get("rebalances", 0) == 0
    assert mb.load_membership(pdir) is None
    assert state == _expected(N_EVENTS)


# --------------------------------------------------- blue/green swaps

SOLO_WORKER = textwrap.dedent(
    """
    import json, os, sys, time
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
    # a REAL sink through the transactional outbox: its delivery
    # watermark must ride the swap (metadata outbox carry-forward)
    pw.io.jsonlines.write(agg, OUT)
    pw.run(persistence_config=pw.persistence.Config(
        pw.persistence.Backend.filesystem(ROOT)))
    """
).format(repo=REPO)


def _run_solo(root: str, out: str, n: int) -> None:
    r = subprocess.run(
        [sys.executable, "-c", SOLO_WORKER, root, out, str(n)],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PATHWAY_THREADS": "1"},
    )
    assert r.returncode == 0, r.stderr[-3000:]


def _sink_state(path: str) -> dict:
    state: dict = {}
    if os.path.exists(path):
        for line in open(path):
            rec = json.loads(line)
            if rec["diff"] > 0:
                state[rec["g"]] = (rec["total"], rec["n"])
            elif state.get(rec["g"]) == (rec["total"], rec["n"]):
                del state[rec["g"]]
    return state


def _tree_snapshot(root: str) -> list:
    out = []
    for dp, _dirs, files in os.walk(root):
        for f in files:
            p = os.path.join(dp, f)
            st = os.stat(p)
            out.append((os.path.relpath(p, root), st.st_size, st.st_mtime_ns))
    return sorted(out)


def test_swap_commits_and_carries_offsets(tmp_path):
    """A healthy green (same plan, longer stream) warms from the clone,
    replays, passes both gates, and commits at the rename — with the
    connector offset and outbox watermark advanced, never regressed."""
    from pathway_tpu.parallel import bluegreen as bg
    from pathway_tpu.persistence import MetadataStore

    blue = str(tmp_path / "blue")
    _run_solo(blue, str(tmp_path / "blue.jsonl"), 40)
    blue_meta = MetadataStore(blue).load()
    assert blue_meta is not None
    blue_off = int(blue_meta["offsets"]["nums"])
    assert blue_off == 40
    blue_outbox = dict(blue_meta.get("outbox") or {})
    assert blue_outbox, "jsonlines sink must seal through the outbox"

    def green(stage):
        _run_solo(stage, str(tmp_path / "green.jsonl"), 80)
        return _sink_state(str(tmp_path / "green.jsonl"))

    res = bg.swap_plan(blue, green, baseline=_expected(80))
    assert res["committed"], res["reason"]
    meta = MetadataStore(blue).load()
    assert meta is not None
    assert int(meta["offsets"]["nums"]) == 80
    for sink, off in blue_outbox.items():
        assert int(meta["outbox"][sink]) >= int(off)
    assert os.path.isdir(blue + ".blue-retired")
    assert not os.path.exists(blue + ".swap.commit")


def test_swap_abort_leaves_blue_untouched(tmp_path):
    """A tampered green (metadata wrecked = never warmed) must fail the
    verifier's swap contract; blue stays byte-for-byte as it was."""
    from pathway_tpu.parallel import bluegreen as bg

    blue = str(tmp_path / "blue")
    _run_solo(blue, str(tmp_path / "blue.jsonl"), 40)
    before = _tree_snapshot(blue)

    def tampered(stage):
        os.unlink(os.path.join(stage, "metadata.json"))
        return _expected(40)

    res = bg.swap_plan(blue, tampered, baseline=_expected(40))
    assert not res["committed"]
    assert "swap contract" in res["reason"]
    assert _tree_snapshot(blue) == before
    assert not os.path.isdir(blue + ".green")
    assert not os.path.isdir(blue + ".blue-retired")


def test_swap_divergent_replay_aborts(tmp_path):
    """Gate A: a green whose replayed output differs from the baseline
    aborts with blue still serving — including via the injectable
    swap.replay.divergent fault point."""
    from pathway_tpu.engine import faults
    from pathway_tpu.parallel import bluegreen as bg

    blue = str(tmp_path / "blue")
    _run_solo(blue, str(tmp_path / "blue.jsonl"), 40)
    before = _tree_snapshot(blue)

    res = bg.swap_plan(blue, lambda stage: {"bogus": 1},
                       baseline=_expected(40), verify=False)
    assert not res["committed"] and "diverged" in res["reason"]
    assert _tree_snapshot(blue) == before

    os.environ["PATHWAY_FAULTS"] = "swap.replay.divergent@1"
    faults.reset()
    try:
        res2 = bg.swap_plan(blue, lambda stage: _expected(40),
                            baseline=_expected(40), verify=False)
    finally:
        os.environ["PATHWAY_FAULTS"] = "0"
        faults.reset()
    assert not res2["committed"] and "injected" in res2["reason"]
    assert _tree_snapshot(blue) == before


def test_swap_mid_commit_crash_rolls_forward(tmp_path):
    """A crash inside the commit window (marker durable, renames maybe
    partial) is rolled FORWARD by recover_swap: the verified green ends
    up serving, the marker is gone."""
    from pathway_tpu.parallel import bluegreen as bg

    blue = str(tmp_path / "blue")
    _run_solo(blue, str(tmp_path / "blue.jsonl"), 40)

    crasher = textwrap.dedent(
        """
        import sys
        sys.path.insert(0, {repo!r})
        from pathway_tpu.parallel import bluegreen as bg
        bg.swap_plan(sys.argv[1], lambda stage: None, verify=False)
        """
    ).format(repo=REPO)
    r = subprocess.run(
        [sys.executable, "-c", crasher, blue],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "PATHWAY_FAULTS": "swap.mid_commit@1",
             "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 17, r.stderr[-2000:]
    assert os.path.exists(blue + ".swap.commit")
    assert bg.recover_swap(blue) == "completed"
    assert os.path.isdir(blue)
    assert not os.path.exists(blue + ".swap.commit")
    assert not os.path.isdir(blue + ".green")
