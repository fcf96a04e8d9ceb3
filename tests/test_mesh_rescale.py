"""Cross-feature acceptance: a 2-process mesh is SIGKILLed mid-stream
and resumed with a DIFFERENT PATHWAY_THREADS — coordinated min-epoch
recovery and the shard-rescale protocol must compose to exact global
aggregates. (tests/test_multiprocess.py covers each alone.)"""

import json
import os
import subprocess
import sys
import textwrap
import time

from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent(
    """
    import json, os, sys, time
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw
    from pathway_tpu.io.python import ConnectorSubject

    PDIR, OUT, READY = sys.argv[1], sys.argv[2], sys.argv[3]
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class Nums(ConnectorSubject):
        def run(self):
            for i in range(160):
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
        sink.write(json.dumps({{**row, "add": is_addition}}) + "\\n")
        sink.flush()
    pw.io.subscribe(agg, on_change=on_change)
    pw.run(persistence_config=pw.persistence.Config(
        pw.persistence.Backend.filesystem(PDIR)))
    """
)


def test_mesh_crash_resume_with_different_thread_count(tmp_path):
    pdir = str(tmp_path / "pstate")
    out = str(tmp_path / "deliveries")
    ready = str(tmp_path / "ready")
    base = free_port_base(2)

    def launch(threads: int):
        procs = []
        for pid in range(2):
            env = {
                **os.environ, "JAX_PLATFORMS": "cpu",
                "PATHWAY_PROCESSES": "2", "PATHWAY_PROCESS_ID": str(pid),
                "PATHWAY_FIRST_PORT": str(base),
                "PATHWAY_THREADS": str(threads),
            }
            procs.append(subprocess.Popen(
                [sys.executable, "-c", SCRIPT.format(repo=REPO),
                 pdir, out, ready],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            ))
        return procs

    # phase 1 at THREADS=3: run until waves flow, then SIGKILL both
    procs = launch(3)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and not os.path.exists(ready + ".0"):
        time.sleep(0.1)
    assert os.path.exists(ready + ".0"), "phase 1 did not come up"
    time.sleep(1.0)
    procs[0].kill()
    time.sleep(0.05)
    procs[1].kill()
    for p in procs:
        p.wait()

    # phase 2 at THREADS=2: min-epoch recovery + per-operator rescale
    os.unlink(ready + ".0")
    procs = launch(2)
    for p in procs:
        _stdout, stderr = p.communicate(timeout=180)
        assert p.returncode == 0, stderr[-3000:]

    state: dict = {}
    for pid in range(2):
        path = out + f".{pid}"
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                ev = json.loads(line)
                if ev["add"]:
                    state[ev["g"]] = (ev["total"], ev["n"])
                elif state.get(ev["g"]) == (ev["total"], ev["n"]):
                    del state[ev["g"]]
    expected: dict = {}
    for i in range(160):
        g = f"g{i % 4}"
        t0, n0 = expected.get(g, (0, 0))
        expected[g] = (t0 + i, n0 + 1)
    assert state == expected, (state, expected)
