"""Token-resident iterate (engine/runtime.py IterateNode, docs/iterate.md).

Equivalence matrix: the graph algorithms (pagerank, bellman_ford,
connected_components, louvain) must produce BYTE-IDENTICAL outputs with
the token plane forced on and off (PATHWAY_ITERATE_NATIVE kill switch,
read at lowering time so it flips in-process), across the full-object
engine (PATHWAY_TPU_NATIVE=0, subprocess legs), under a 2-process mesh,
and across a persistence save/restore cycle. Plus the acceptance
counter: the pagerank fixpoint loop performs ZERO per-round
materialize()/intern_row round-trips (counter hook on InternTable).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

import pathway_tpu as pw
from pathway_tpu.internals import expression as ex
from pathway_tpu.internals.lowering import Session
from pathway_tpu.engine.runtime import IterateNode
from pathway_tpu.stdlib.graphs import (
    Graph,
    bellman_ford,
    connected_components,
    louvain_level,
    pagerank,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _native_on() -> bool:
    from pathway_tpu.engine.native import dataplane

    return dataplane.available()


# ----------------------------------------------------------- fixtures


def _edges_md(update: bool = True) -> str:
    """Two components: a 12-ring (static) and a triangle whose closing
    edge arrives at t=4 (the O(affected) update wave)."""
    lines = ["u | w | __time__ | __diff__"]
    for i in range(12):
        lines.append(f"a{i} | a{(i + 1) % 12} | 2 | 1")
    lines += ["b0 | b1 | 2 | 1", "b1 | b2 | 2 | 1"]
    if update:
        lines.append("b2 | b0 | 4 | 1")
    return "\n".join(lines)


def _edges_table():
    t = pw.debug.table_from_markdown(_edges_md()).with_id_from(
        pw.this.u, pw.this.w
    )
    return t.select(u=t.u, v=t.w)


def _capture_form(table) -> list:
    """Canonical, order-insensitive form of a pipeline's full update
    stream + final state (byte-exact: repr of every value)."""
    session = Session()
    cap = session.capture(table)
    session.execute()
    stream = sorted(
        (t, k.value, repr(row), d) for (t, k, row, d) in cap.stream
    )
    state = sorted((k.value, repr(row)) for k, row in cap.state.rows.items())
    return [stream, state]


def _algo(name: str):
    if name == "pagerank":
        return pagerank(_edges_table(), steps=200)
    if name == "bellman_ford":
        md = """
        vid | is_source | __time__ | __diff__
        s   | True      | 2        | 1
        m   | False     | 2        | 1
        t   | False     | 2        | 1
        u   | False     | 4        | 1
        """
        v = pw.debug.table_from_markdown(md).with_id_from(pw.this.vid)
        emd = """
        a | b | dist | __time__ | __diff__
        s | m | 1.0  | 2        | 1
        m | t | 2.0  | 2        | 1
        s | t | 9.0  | 2        | 1
        m | u | 1.5  | 4        | 1
        """
        e = pw.debug.table_from_markdown(emd)
        e2 = e.select(
            u=e.pointer_from(e.a), v=e.pointer_from(e.b), dist=e.dist
        )
        return bellman_ford(v.select(is_source=v.is_source), e2)
    if name == "connected_components":
        return connected_components(_edges_table())
    if name == "louvain":
        md = """
        u | w | weight | __time__ | __diff__
        a | b | 1.0    | 2        | 1
        b | a | 1.0    | 2        | 1
        b | c | 1.0    | 2        | 1
        c | b | 1.0    | 2        | 1
        a | c | 1.0    | 2        | 1
        c | a | 1.0    | 2        | 1
        c | d | 1.0    | 4        | 1
        d | c | 1.0    | 4        | 1
        d | e | 1.0    | 2        | 1
        e | d | 1.0    | 2        | 1
        e | f | 1.0    | 2        | 1
        f | e | 1.0    | 2        | 1
        d | f | 1.0    | 2        | 1
        f | d | 1.0    | 2        | 1
        """
        E = pw.debug.table_from_markdown(md).with_id_from(
            pw.this.u, pw.this.w
        )
        ids = E.select(x=E.u).concat_reindex(E.select(x=E.w))
        V = ids.groupby(ids.x).reduce(vid=ids.x).with_id_from(ex.this.vid)
        E2 = E.select(
            u=V.pointer_from(E.u), v=V.pointer_from(E.w), weight=E.weight
        )
        return louvain_level(Graph(V, E2), iteration_limit=40)
    raise AssertionError(name)


ALGOS = ["pagerank", "bellman_ford", "connected_components", "louvain"]


# --------------------------------------------- kill-switch equivalence


@pytest.mark.parametrize("algo", ALGOS)
def test_token_vs_object_iterate_byte_identical(algo, monkeypatch):
    """PATHWAY_ITERATE_NATIVE=0 (today's object plumbing) and the token
    plane produce byte-identical streams and final states."""
    monkeypatch.delenv("PATHWAY_ITERATE_NATIVE", raising=False)
    on = _capture_form(_algo(algo))
    monkeypatch.setenv("PATHWAY_ITERATE_NATIVE", "0")
    off = _capture_form(_algo(algo))
    assert on == off


_SUBPROC_SCRIPT = textwrap.dedent(
    """
    import json, sys
    sys.path.insert(0, {repo!r})
    sys.path.insert(0, {repo!r} + "/tests")
    import test_iterate_native as tin
    print("FORM " + json.dumps(tin._capture_form(tin._algo({algo!r}))))
    """
)


def _subprocess_form(algo: str, env_extra: dict) -> list:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROC_SCRIPT.format(repo=REPO, algo=algo)],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    for line in r.stdout.splitlines():
        if line.startswith("FORM "):
            return json.loads(line[5:])
    raise AssertionError(f"no FORM: {r.stdout[-300:]} {r.stderr[-1500:]}")


@pytest.mark.parametrize("algo", ["pagerank", "connected_components"])
def test_full_object_engine_byte_identical(algo):
    """The whole-engine kill switch (PATHWAY_TPU_NATIVE=0, flippable only
    per process) agrees byte-for-byte with the token engine — integer
    fixpoints make the iterate results summation-order independent."""
    native = _subprocess_form(algo, {})
    obj = _subprocess_form(algo, {"PATHWAY_TPU_NATIVE": "0"})
    assert native == obj


# ------------------------------------------------ acceptance counters


@pytest.mark.skipif(
    not _native_on() or os.environ.get("PATHWAY_ITERATE_NATIVE") == "0",
    reason="token-resident iterate off (plane unavailable or kill switch)",
)
def test_pagerank_scope_zero_roundtrips():
    """The acceptance gate: the pagerank bench shape performs ZERO
    per-round materialize()/intern_row round-trips inside the iterate
    scope — the InternTable counter hooks sampled by the IterateNode
    stay at zero across the cold fixpoint AND the warm update wave."""
    ranks = pagerank(_edges_table(), steps=500)
    session = Session()
    cap = session.capture(ranks)
    session.execute()
    its = [n for n in session.graph.nodes if isinstance(n, IterateNode)]
    assert len(its) == 1
    it = its[0]
    assert it._tok, "iterate scope fell off the token plane"
    assert it.plane_stats["rounds"] > 0
    # the scope never decoded a row to Python objects...
    assert it.plane_stats["scope_materialize_rows"] == 0, it.plane_stats
    # ...and the boundary plumbing never interned or materialized one
    assert it.plane_stats["boundary_intern_rows"] == 0, it.plane_stats
    assert it.plane_stats["boundary_materialize_rows"] == 0, it.plane_stats
    # the capture log carried ONLY native segments (no 4-tuples)
    for name, c in it.captures.items():
        assert getattr(c, "_tok", False), f"capture {name} demoted"
    # sanity: the pipeline actually produced ranks
    assert len(cap.state.rows) == 15


def test_exotic_rows_demote_scope_and_stay_correct():
    """The fallback ladder: a body emitting plane-unrepresentable rows
    (tuple-valued column) demotes the scope mid-run; results match the
    kill-switch run exactly."""

    def build():
        def stepfn(t):
            return {
                "t": t.select(
                    a=pw.if_else(t.a >= 64, t.a, t.a * 2),
                    trail=pw.apply_with_type(
                        lambda tr, a: tuple(list(tr) + [a]) if a < 64 else tr,
                        tuple, pw.this.trail, pw.this.a,
                    ),
                )
            }

        t = pw.debug.table_from_markdown(
            """
            a | __time__ | __diff__
            3 | 2        | 1
            5 | 4        | 1
            """
        ).with_id_from(pw.this.a)
        t2 = t.select(a=t.a, trail=pw.apply_with_type(lambda: (), tuple))
        return pw.iterate(stepfn, t=t2)

    on = _capture_form(build())
    os.environ["PATHWAY_ITERATE_NATIVE"] = "0"
    try:
        off = _capture_form(build())
    finally:
        del os.environ["PATHWAY_ITERATE_NATIVE"]
    assert on == off


# ------------------------------------------------------- persistence


@pytest.mark.parametrize("iterate_native", ["1", "0"])
def test_iterate_persistence_roundtrip(tmp_path, monkeypatch, iterate_native):
    """Iterate scope snapshots (fed mirrors, capture logs, body-node
    states) round-trip through a checkpoint on BOTH plumbing planes —
    token-mode state always exports the portable OBJECT form. (A
    checkpoint is pinned to its plane by the persist signature, same as
    the join/groupby native-kernel policy.)"""
    from pathway_tpu.persistence import Backend, CheckpointManager, Config

    monkeypatch.setenv("PATHWAY_ITERATE_NATIVE", iterate_native)

    def build():
        return pagerank(_edges_table(), steps=200)

    cfg = Config(Backend.filesystem(str(tmp_path)))
    s1 = Session()
    cap1 = s1.capture(build())
    s1.execute()
    m1 = CheckpointManager(s1, cfg)
    m1.checkpoint(finalized_time=100)

    s2 = Session()
    cap2 = s2.capture(build())
    m2 = CheckpointManager(s2, cfg)
    assert m2.signature == m1.signature
    m2.restore()
    assert m2.restored
    got = {k.value: repr(r) for k, r in cap2.state.rows.items()}
    want = {k.value: repr(r) for k, r in cap1.state.rows.items()}
    assert got == want


# ------------------------------------------------------- 2-proc mesh


_MESH_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    sys.path.insert(0, {repo!r} + "/tests")
    import test_iterate_native as tin
    import pathway_tpu as pw
    from pathway_tpu.internals.lowering import Session

    table = tin._algo("pagerank")
    session = Session()
    cap = session.capture(table)
    session.execute()
    # downstream exchanges shard the final select's rows across the
    # processes: every process writes ITS capture shard; the test
    # compares the union against the single-process state
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    state = sorted(
        (k.value, repr(row)) for k, row in cap.state.rows.items()
    )
    with open(sys.argv[1] + "." + str(pid), "w") as f:
        json.dump(state, f)
    """
)


@pytest.mark.slow
def test_pagerank_mesh_two_process_invariance(tmp_path):
    """PATHWAY_PROCESSES=2: the iterate scope runs whole on process 0
    behind exchange wires (protocol-5 zero-copy frames); the final state
    is byte-identical to the single-process run."""
    from conftest import free_port_base

    base = free_port_base(2)

    single = _subprocess_form("pagerank", {})[1]
    out = str(tmp_path / "mesh_state.json")
    procs = []
    for pid in range(2):
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PATHWAY_PROCESSES": "2",
            "PATHWAY_PROCESS_ID": str(pid),
            "PATHWAY_FIRST_PORT": str(base),
        }
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-c",
                    _MESH_SCRIPT.format(repo=REPO), out,
                ],
                env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
        )
    for p in procs:
        try:
            p.wait(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
    for p in procs:
        assert p.returncode == 0, (p.stdout.read(), p.stderr.read())
    mesh_state: set = set()
    for pid in range(2):
        with open(f"{out}.{pid}") as f:
            mesh_state |= {tuple(x) for x in json.load(f)}
    assert sorted(mesh_state) == [tuple(x) for x in single]


# ------------------------------------------------- wire form (proto 5)


def test_native_wire_protocol5_and_legacy_roundtrip():
    """NativeBatch wire tuples survive pickle protocol 5 with
    out-of-band buffers AND the legacy all-bytes form (supervisor
    restart compatibility)."""
    import pickle

    import numpy as np

    from pathway_tpu.engine.native import dataplane as dp

    if not dp.available():
        pytest.skip("native plane unavailable")
    tab = dp.default_table()
    toks = [tab.intern_row((i, f"s{i}")) for i in range(8)]
    nb = dp.NativeBatch(
        tab,
        np.arange(8, dtype=np.uint64),
        np.zeros(8, np.uint64),
        np.asarray(toks, np.uint64),
        np.ones(8, np.int64),
    )
    wire = nb.to_wire()
    # protocol-5 out-of-band round trip (the mesh frame path)
    bufs: list = []
    body = pickle.dumps(wire, protocol=5, buffer_callback=bufs.append)
    assert bufs, "flat columns must ship out-of-band"
    wire2 = pickle.loads(body, buffers=[b.raw() for b in bufs])
    back = dp.NativeBatch.from_wire(wire2)
    assert back.materialize() == nb.materialize()
    # legacy frame: every field as bytes (pre-protocol-5 wire form)
    legacy = tuple(
        w.tobytes() if isinstance(w, np.ndarray) else bytes(w) for w in wire
    )
    back2 = dp.NativeBatch.from_wire(legacy)
    assert back2.materialize() == nb.materialize()
