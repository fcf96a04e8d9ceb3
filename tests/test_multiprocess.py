"""Multi-process execution: cooperating processes over the TCP mesh.

Reference parity: the reference's worker architecture (docs
10.worker-architecture.md) — every process builds the same dataflow,
sources are partitioned, and records hash-exchange between processes so
each key's state lives on exactly one worker. These tests spawn real OS
processes via the cli spawn contract and assert (a) combined outputs
equal the single-process results and (b) rows genuinely crossed the
process boundary.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw
    from pathway_tpu.io.python import ConnectorSubject

    OUT = sys.argv[1]
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class Part(ConnectorSubject):
        # each process's connector instance reads a DIFFERENT slice of the
        # global stream (sources are partitioned: this connector only runs
        # on its owner process; a second connector covers the other slice)
        def __init__(self, lo, hi):
            self.lo, self.hi = lo, hi

        def run(self):
            import time
            for i in range(self.lo, self.hi):
                self.next(g=f"g{{i % 5}}", v=i)
                time.sleep(0.002)

    # two sources -> round-robin ownership across the 2 processes
    a = pw.io.python.read(Part(0, 30), schema=pw.schema_from_types(g=str, v=int), name="a")
    b = pw.io.python.read(Part(30, 60), schema=pw.schema_from_types(g=str, v=int), name="b")
    t = a.concat_reindex(b)
    agg = t.groupby(t.g).reduce(t.g, total=pw.reducers.sum(t.v), n=pw.reducers.count())
    out = open(OUT + f".{{PID}}", "w")
    rows = {{}}
    def on_change(key, row, time, is_addition):
        if is_addition:
            rows[row["g"]] = (row["total"], row["n"])
        elif rows.get(row["g"]) == (row["total"], row["n"]):
            del rows[row["g"]]
    pw.io.subscribe(agg, on_change=on_change)
    pw.run()
    json.dump(rows, out)
    out.close()
    """
)


def test_two_processes_cooperate_exact_results(tmp_path):
    out = str(tmp_path / "out.json")
    base = free_port_base(2)
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
                [sys.executable, "-c", SCRIPT.format(repo=REPO), out],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
        )
    for p in procs:
        try:
            _stdout, stderr = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, stderr[-3000:]

    # combined per-process shares = exact global aggregates
    combined: dict = {}
    shares = []
    for pid in range(2):
        with open(out + f".{pid}") as f:
            share = json.load(f)
        shares.append(share)
        for g, (total, n) in share.items():
            assert g not in combined, f"group {g} on two processes"
            combined[g] = (total, n)
    expected = {}
    for i in range(60):
        g = f"g{i % 5}"
        t0, n0 = expected.get(g, (0, 0))
        expected[g] = (t0 + i, n0 + 1)
    assert combined == expected, (combined, expected)
    # the work was actually split: both processes own some groups
    assert all(shares), f"one process owned everything: {shares}"


def test_processes_times_threads(tmp_path):
    """2 processes x 2 thread shards: the exchanges compose — exact
    results with state partitioned at both levels."""
    out = str(tmp_path / "out.json")
    base = free_port_base(2)
    procs = []
    for pid in range(2):
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PATHWAY_PROCESSES": "2",
            "PATHWAY_PROCESS_ID": str(pid),
            "PATHWAY_FIRST_PORT": str(base),
            "PATHWAY_THREADS": "2",
        }
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", SCRIPT.format(repo=REPO), out],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
        )
    for p in procs:
        _stdout, stderr = p.communicate(timeout=180)
        assert p.returncode == 0, stderr[-3000:]
    combined: dict = {}
    for pid in range(2):
        with open(out + f".{pid}") as f:
            combined.update(json.load(f))
    assert sum(n for (_t, n) in combined.values()) == 60
    assert sum(t for (t, _n) in combined.values()) == sum(range(60))


def test_spawn_cli_contract(tmp_path):
    """`python -m pathway_tpu spawn -n 2` launches cooperating processes."""
    out = str(tmp_path / "out.json")
    base = free_port_base(2)
    script = tmp_path / "pipeline.py"
    script.write_text(SCRIPT.format(repo=REPO).replace("sys.argv[1]", repr(out)))
    r = subprocess.run(
        [
            sys.executable, "-m", "pathway_tpu", "spawn",
            "-n", "2", "--first-port", str(base),
            "--", str(script),
        ],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
        cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    combined = {}
    for pid in range(2):
        with open(out + f".{pid}") as f:
            combined.update(json.load(f))
    assert sum(n for (_t, n) in combined.values()) == 60


ITERATE_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw

    OUT = sys.argv[1]
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    def collatz_step(t):
        return {{"t": t.select(
            a=pw.if_else(t.a == 1, 1,
                         pw.if_else(t.a % 2 == 0, t.a // 2, 3 * t.a + 1)))}}

    start = pw.debug.table_from_markdown("a\\n3\\n7\\n27").with_id_from(pw.this.a)
    res = pw.iterate(collatz_step, t=start)
    rows = []
    pw.io.subscribe(res, on_change=lambda key, row, time, is_addition:
                    rows.append(row["a"]) if is_addition else None)
    pw.run()
    json.dump(rows, open(OUT + f".{{PID}}", "w"))
    """
)


def test_iterate_under_two_processes(tmp_path):
    """pw.iterate pins its body to process 0; the other process must not
    deadlock on phantom exchange barriers inside the loop."""
    out = str(tmp_path / "it.json")
    base = free_port_base(2)
    procs = []
    for pid in range(2):
        env = {
            **os.environ, "JAX_PLATFORMS": "cpu",
            "PATHWAY_PROCESSES": "2", "PATHWAY_PROCESS_ID": str(pid),
            "PATHWAY_FIRST_PORT": str(base),
        }
        procs.append(subprocess.Popen(
            [sys.executable, "-c", ITERATE_SCRIPT.format(repo=REPO), out],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    for p in procs:
        _stdout, stderr = p.communicate(timeout=120)
        assert p.returncode == 0, stderr[-3000:]
    all_rows = []
    for pid in range(2):
        with open(out + f".{pid}") as f:
            all_rows.extend(json.load(f))
    assert sorted(all_rows) == [1, 1, 1], all_rows


SLOW_SCRIPT = textwrap.dedent(
    """
    import os, sys, time
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw
    from pathway_tpu.io.python import ConnectorSubject

    READY = sys.argv[1]
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class Slow(ConnectorSubject):
        def run(self):
            for i in range(100000):
                self.next(g=f"g{{i % 5}}", v=i)
                if i == 5:
                    open(READY + f".{{PID}}", "w").write("up")
                time.sleep(0.05)

    t = pw.io.python.read(Slow(), schema=pw.schema_from_types(g=str, v=int), name="slow")
    agg = t.groupby(t.g).reduce(t.g, total=pw.reducers.sum(t.v))
    pw.io.subscribe(agg, on_change=lambda key, row, time, is_addition: None)
    pw.run()
    """
)


def test_worker_failure_detected_not_hung(tmp_path):
    """Killing one process mid-run must surface a clear peer-death error
    on the survivor (failure detection), never an indefinite hang."""
    ready = str(tmp_path / "ready")
    base = free_port_base(2)
    procs = []
    for pid in range(2):
        env = {
            **os.environ, "JAX_PLATFORMS": "cpu",
            "PATHWAY_PROCESSES": "2", "PATHWAY_PROCESS_ID": str(pid),
            "PATHWAY_FIRST_PORT": str(base),
        }
        procs.append(subprocess.Popen(
            [sys.executable, "-c", SLOW_SCRIPT.format(repo=REPO), ready],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    import time as _time

    # the single source lives on process 0; once it streams, lockstep
    # control rounds prove BOTH meshes are up
    deadline = _time.monotonic() + 60
    while _time.monotonic() < deadline:
        if os.path.exists(ready + ".0"):
            break
        _time.sleep(0.1)
    else:
        for p in procs:
            p.kill()
        raise AssertionError("workers did not come up")
    _time.sleep(0.5)  # let a few more waves cross the mesh
    procs[1].kill()
    t0 = _time.monotonic()
    _stdout, stderr = procs[0].communicate(timeout=120)
    detect_s = _time.monotonic() - t0
    procs[1].wait()
    assert procs[0].returncode != 0
    assert "died" in stderr or "peer" in stderr, stderr[-1500:]
    # detection is prompt (socket EOF), not a timeout expiry
    assert detect_s < 30, f"took {detect_s:.1f}s to notice the dead peer"


PERSIST_SCRIPT = textwrap.dedent(
    """
    import json, os, sys, time
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw
    from pathway_tpu.io.python import ConnectorSubject

    PDIR, OUT, READY = sys.argv[1], sys.argv[2], sys.argv[3]
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class Nums(ConnectorSubject):
        def run(self):
            for i in range(200):
                self.next(g=f"g{{i % 4}}", v=i)
                if i == 5:
                    open(READY + f".{{PID}}", "w").write("up")
                time.sleep(0.01)

    t = pw.io.python.read(Nums(), schema=pw.schema_from_types(g=str, v=int), name="nums")
    agg = t.groupby(t.g).reduce(t.g, total=pw.reducers.sum(t.v), n=pw.reducers.count())
    sink = open(OUT + f".{{PID}}", "a")
    def on_change(key, row, time, is_addition):
        sink.write(json.dumps({{**row, "add": is_addition}}) + "\\n"); sink.flush()
    pw.io.subscribe(agg, on_change=on_change)
    pw.run(persistence_config=pw.persistence.Config(
        pw.persistence.Backend.filesystem(PDIR)))
    """
)


def test_multiprocess_kill_both_and_resume_exact(tmp_path):
    """Both cooperating processes die mid-run (possibly between each
    other's checkpoint commits); restart negotiates the minimum common
    epoch and resumes to EXACT global aggregates."""
    import time as _time

    pdir = str(tmp_path / "pstate")
    out = str(tmp_path / "deliveries")
    ready = str(tmp_path / "ready")
    base = free_port_base(2)

    def launch():
        procs = []
        for pid in range(2):
            env = {
                **os.environ, "JAX_PLATFORMS": "cpu",
                "PATHWAY_PROCESSES": "2", "PATHWAY_PROCESS_ID": str(pid),
                "PATHWAY_FIRST_PORT": str(base),
            }
            procs.append(subprocess.Popen(
                [sys.executable, "-c", PERSIST_SCRIPT.format(repo=REPO),
                 pdir, out, ready],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            ))
        return procs

    # phase 1: run until waves flow, then SIGKILL both (at slightly
    # different instants — the window between peers' checkpoint commits)
    procs = launch()
    deadline = _time.monotonic() + 60
    while _time.monotonic() < deadline and not os.path.exists(ready + ".0"):
        _time.sleep(0.1)
    assert os.path.exists(ready + ".0"), "phase 1 did not come up"
    _time.sleep(1.0)
    procs[0].kill()
    _time.sleep(0.05)
    procs[1].kill()
    for p in procs:
        p.wait()

    # phase 2: resume with the same dirs; must run to completion
    os.unlink(ready + ".0")
    procs = launch()
    for p in procs:
        _stdout, stderr = p.communicate(timeout=180)
        assert p.returncode == 0, stderr[-3000:]

    # reconstruct per-group finals from the accumulated delivery streams
    state: dict = {}
    for pid in range(2):
        with open(out + f".{pid}") as f:
            for line in f:
                ev = json.loads(line)
                if ev["add"]:
                    state[ev["g"]] = (ev["total"], ev["n"])
                elif state.get(ev["g"]) == (ev["total"], ev["n"]):
                    del state[ev["g"]]
    expected: dict = {}
    for i in range(200):
        g = f"g{i % 4}"
        t0, n0 = expected.get(g, (0, 0))
        expected[g] = (t0 + i, n0 + 1)
    assert state == expected, (state, expected)


CRASH_SCRIPT = textwrap.dedent(
    """
    import json, os, sys, threading, time
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw
    from pathway_tpu.io.python import ConnectorSubject

    OUT = sys.argv[1]       # deliveries jsonl, appended across runs
    PDIR = sys.argv[2]
    MODE = sys.argv[3]      # 'crash' or 'finish'
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class Part(ConnectorSubject):
        def __init__(self, lo, hi):
            self.lo, self.hi = lo, hi

        def run(self):
            for i in range(self.lo, self.hi):
                self.next(g=f"g{{i % 5}}", v=i)
                time.sleep(0.002)

    a = pw.io.python.read(Part(0, 200), schema=pw.schema_from_types(g=str, v=int), name="a")
    b = pw.io.python.read(Part(200, 400), schema=pw.schema_from_types(g=str, v=int), name="b")
    t = a.concat_reindex(b)
    agg = t.groupby(t.g).reduce(t.g, total=pw.reducers.sum(t.v), n=pw.reducers.count())
    sink = open(OUT + f".{{PID}}", "a")
    def on_change(key, row, time, is_addition):
        sink.write(json.dumps(
            {{"g": row["g"], "total": row["total"], "n": row["n"], "add": is_addition}}
        ) + "\\n")
        sink.flush()
    pw.io.subscribe(agg, on_change=on_change)

    if MODE == "crash" and PID == 1:
        def crasher():
            # kill -9 semantics AFTER both processes committed an epoch
            metas = [os.path.join(PDIR, f"proc-{{p}}", "metadata.json") for p in (0, 1)]
            deadline = time.time() + 60
            while time.time() < deadline:
                if all(os.path.exists(m) for m in metas):
                    os._exit(9)
                time.sleep(0.005)
            os._exit(3)
        threading.Thread(target=crasher, daemon=True).start()

    pw.run(persistence_config=pw.persistence.Config(
        pw.persistence.Backend.filesystem(PDIR),
        snapshot_interval_ms=60))
    """
)


def _consolidate_deliveries(path):
    state = {}
    if not os.path.exists(path):
        return state
    with open(path) as f:
        for line in f:
            ev = json.loads(line)
            if ev["add"]:
                state[ev["g"]] = (ev["total"], ev["n"])
            elif state.get(ev["g"]) == (ev["total"], ev["n"]):
                del state[ev["g"]]
    return state


def _spawn_mesh(out, pdir, mode, base, n=2):
    procs = []
    for pid in range(n):
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PATHWAY_PROCESSES": str(n),
            "PATHWAY_PROCESS_ID": str(pid),
            "PATHWAY_FIRST_PORT": str(base),
        }
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", CRASH_SCRIPT.format(repo=REPO), out, pdir, mode],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
        )
    return procs


def test_mesh_kill9_coordinated_recovery(tmp_path):
    """Fault injection (the wordcount test_recovery pattern): kill -9 one
    process of a 2-process mesh mid-stream after a committed epoch, kill
    the stalled survivor, restart the mesh on the same persistence roots
    — coordinated min-epoch recovery yields EXACT aggregates."""
    out = str(tmp_path / "deliv")
    pdir = str(tmp_path / "pstorage")
    base = free_port_base(2)

    procs = _spawn_mesh(out, pdir, "crash", base)
    # process 1 self-kills (os._exit(9)) after both epochs commit
    try:
        _o, err1 = procs[1].communicate(timeout=120)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        raise
    assert procs[1].returncode == 9, (procs[1].returncode, err1[-2000:])
    # the survivor is now stuck/broken on the dead peer: kill -9 it too
    try:
        procs[0].wait(timeout=5)
    except subprocess.TimeoutExpired:
        procs[0].kill()
        procs[0].wait()

    # restart the whole mesh on fresh ports, same persistence roots
    base2 = free_port_base(2)
    procs2 = _spawn_mesh(out, pdir, "finish", base2)
    for p in procs2:
        try:
            _o, err = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs2:
                q.kill()
            raise
        assert p.returncode == 0, err[-3000:]

    combined: dict = {}
    for pid in range(2):
        share = _consolidate_deliveries(out + f".{pid}")
        for g, tn in share.items():
            assert g not in combined, f"group {g} delivered on two processes"
            combined[g] = tn
    expected: dict = {}
    for i in range(400):
        g = f"g{i % 5}"
        t0, n0 = expected.get(g, (0, 0))
        expected[g] = (t0 + i, n0 + 1)
    assert combined == expected, (combined, expected)


NATIVE_WIRE_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw

    OUT = sys.argv[1]
    INPUT = sys.argv[2]
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class S(pw.Schema):
        word: str

    # one fs source (owned by process 0); the groupby exchange ships the
    # token batches to their owner processes in wire form
    t = pw.io.fs.read(INPUT, format="json", schema=S, mode="streaming",
                      autocommit_duration_ms=20, _single_pass=True)
    agg = t.groupby(t.word).reduce(t.word, n=pw.reducers.count())
    rows = {{}}
    def on_change(key, row, time, is_addition):
        if is_addition:
            rows[row["word"]] = row["n"]
        elif rows.get(row["word"]) == row["n"]:
            del rows[row["word"]]
    pw.io.subscribe(agg, on_change=on_change)
    pw.run()
    json.dump(rows, open(OUT + f".{{PID}}", "w"))
    """
)


def test_native_batches_cross_process_wire(tmp_path):
    """Token-resident fs ingest under a 2-process mesh: batches split in
    C and cross the TCP mesh in wire form; combined counts are exact."""
    inp = tmp_path / "in.jsonl"
    with open(inp, "w") as f:
        for i in range(900):
            f.write('{"word": "w%d"}\n' % (i % 6))
    out = str(tmp_path / "out")
    base = free_port_base(2)
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
                [sys.executable, "-c",
                 NATIVE_WIRE_SCRIPT.format(repo=REPO), out, str(inp)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
        )
    for p in procs:
        try:
            _o, err = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, err[-3000:]
    combined = {}
    shares = []
    for pid in range(2):
        share = json.load(open(out + f".{pid}"))
        shares.append(share)
        for w, n in share.items():
            assert w not in combined
            combined[w] = n
    assert combined == {f"w{i}": 150 for i in range(6)}
    assert all(shares), f"one process owned everything: {shares}"
