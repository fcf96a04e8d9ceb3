"""The examples/ projects stay runnable: each is executed as a user
would (subprocess, --once / live server) and its output checked."""

import json
import os
import subprocess
import sys
import time
import urllib.request

from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}


def _run(script, *args, timeout=120):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=ENV,
    )


def test_wordcount_example_with_restart(tmp_path):
    inbox = tmp_path / "inbox"
    inbox.mkdir()
    with open(inbox / "a.jsonl", "w") as f:
        for w in ["x", "y", "x"]:
            f.write(json.dumps({"word": w}) + "\n")
    out = str(tmp_path / "counts.csv")
    state = str(tmp_path / "state")
    r = _run("wordcount/app.py", str(inbox), out, state, "--once")
    assert r.returncode == 0, r.stderr[-1500:]

    def counts():
        cur = {}
        import csv

        with open(out) as f:
            for rec in csv.DictReader(f):
                if int(rec["diff"]) == 1:
                    cur[rec["word"]] = int(rec["count"])
                elif cur.get(rec["word"]) == int(rec["count"]):
                    del cur[rec["word"]]
        return cur

    assert counts() == {"x": 2, "y": 1}
    # append + restart: resumes from state and emits ONLY the delta —
    # x moves 2 -> 3, unchanged y is not re-emitted (exact resume)
    with open(inbox / "b.jsonl", "w") as f:
        f.write(json.dumps({"word": "x"}) + "\n")
    out2 = str(tmp_path / "counts2.csv")
    r = _run("wordcount/app.py", str(inbox), out2, state, "--once")
    assert r.returncode == 0, r.stderr[-1500:]
    import csv

    events = [
        (rec["word"], int(rec["count"]), int(rec["diff"]))
        for rec in csv.DictReader(open(out2))
    ]
    assert sorted(events) == [("x", 2, -1), ("x", 3, 1)], events


def test_linear_regression_example(tmp_path):
    inbox = tmp_path / "inbox"
    inbox.mkdir()
    import random

    rng = random.Random(7)
    with open(inbox / "pts.jsonl", "w") as f:
        for _ in range(400):
            x = rng.uniform(0, 10)
            f.write(json.dumps({"x": x, "y": 2 * x - 1 + rng.gauss(0, 0.05)}) + "\n")
    out = str(tmp_path / "reg.csv")
    r = _run("linear_regression/app.py", str(inbox), out, "--once")
    assert r.returncode == 0, r.stderr[-1500:]
    import csv

    rows = [rec for rec in csv.DictReader(open(out)) if int(rec["diff"]) == 1]
    a, b = float(rows[-1]["a"]), float(rows[-1]["b"])
    assert abs(a - (-1.0)) < 0.1 and abs(b - 2.0) < 0.05, (a, b)


def test_adaptive_rag_example(tmp_path):
    corpus = tmp_path / "corpus"
    corpus.mkdir()
    (corpus / "refunds.txt").write_text(
        "Refund policy: purchases can be refunded within 30 days."
    )
    (corpus / "shipping.txt").write_text(
        "Shipping: orders ship within 2 business days."
    )
    port = free_port_base()
    # never PIPE a long-running server without draining: a filled pipe
    # buffer would block its writes and stall serving
    errlog = open(tmp_path / "server.err", "w+")
    proc = subprocess.Popen(
        [
            sys.executable,
            os.path.join(REPO, "examples", "adaptive_rag", "app.py"),
            str(corpus), "--mock", "--port", str(port),
        ],
        env=ENV, stdout=subprocess.DEVNULL, stderr=errlog, text=True,
    )
    try:
        answer = None
        deadline = time.time() + 60
        while time.time() < deadline:
            time.sleep(0.5)
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/pw_ai_answer",
                    data=json.dumps({"prompt": "What is the refund policy?"}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=10) as resp:
                    answer = json.loads(resp.read().decode())
                break
            except Exception:
                if proc.poll() is not None:
                    errlog.seek(0)
                    raise AssertionError(errlog.read()[-2000:])
        assert answer is not None, "server never came up"
        assert "response" in (answer or {}), answer
    finally:
        proc.kill()
        proc.wait(timeout=10)
        errlog.close()
