"""Tests of what makes a run's set-up the same in every run, at the tiny
preset of bench/rehearsal on the CPU. Run by hand, beside test_correct.py;
``pytest tests/`` does not collect them:

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests/test_setup.py -q

* the ingest's encode calls are the same list of rows for two seeds, and
  with a sleep planted between the source's rows (a ``put`` reaches the
  engine whole, whatever the timing);
* ``wait_until_indexed`` does not return while the index holds fewer
  documents than were put (an embedder that blocks until released), and
  raises at its deadline;
* ``compiled_in_setup`` lists the encoder's program after a run with an
  empty cache directory and is empty after the next run in the same
  directory (two processes);
* a prefill cut by the trace's edge does not move ``prefill_ms``: the
  recorded trace with its first prefill cut by hand, and with that prefill
  taken out, read what the trace reads as it is.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", str(ROOT / ".pathway-cache" / "xla-rehearsal")
)
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT))

from pwbench import harness, spec, trace_reduce, traffic  # noqa: E402
from pwbench.server import Rag, wait_until_indexed  # noqa: E402

PRESET = BENCH / "rehearsal" / "BENCHMARK.json"


def served(seed: int):
    """The tiny preset's server, started, with its corpus: not ingested."""
    cell = spec.Cell(PRESET, "tiny.backlog")
    corpus = traffic.Corpus(seed, cell.config["corpus"])
    rag = Rag(cell.config, seed)
    rag.watch_encodes(True)
    rag.start()
    return rag, corpus


@pytest.mark.parametrize("seed, row_sleep_s", [(5, 0.0), (3000000019, 0.0), (5, 0.0005)])
def test_the_ingest_waves_are_the_same_list(seed, row_sleep_s):
    rag, corpus = served(seed)
    try:
        subject = rag.source.subject
        plain_next = subject.next

        def slow_next(**kw):  # the engine's pump looks every 2 ms
            time.sleep(row_sleep_s)
            plain_next(**kw)

        if row_sleep_s:
            subject.next = slow_next
        waves = harness._ingest(rag, corpus, wave_rows=128)
    finally:
        rag.stop()
    assert waves == [128, 128, 128, 128]
    assert rag.indexed() == 512


def test_the_wait_is_for_the_index_not_the_parser():
    rag, corpus = served(9)
    release = threading.Event()
    encode = rag.embedder._batcher.flush_fn

    def blocked(texts):
        release.wait(60)
        return encode(texts)

    rag.embedder._batcher.flush_fn = blocked
    try:
        docs = sorted(corpus.texts.items())
        rag.source.put(docs)
        # parsed long before: the store's own count says so, the index's not
        with pytest.raises(TimeoutError, match="never 512"):
            wait_until_indexed(rag, len(docs), time.monotonic() + 3.0)
        assert rag.indexed() == 0
        release.set()
        wait_until_indexed(rag, len(docs), time.monotonic() + 60)
        assert rag.indexed() == len(docs)
    finally:
        release.set()
        rag.stop()


ENCODE_ONCE = """
import json, sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
from pwbench import compiles
log = compiles.CompileLog().install()
import time
t = time.monotonic()
from pathway_tpu.engine.device_plane import get_device_plane
get_device_plane()  # sets the compile cache first, as server.Rag does
from pathway_tpu.models import embedder_config
from pathway_tpu.xpacks.llm.embedders import JaxEmbedder
emb = JaxEmbedder(config=embedder_config(
    vocab_size=512, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=32,
    embed_dim=32))
a = time.monotonic()
emb.encode_many(["one two three"] * 8)
end = time.monotonic()
import threading
calls = [(8, a, end, threading.get_ident()), (9, a, end, -1)]  # another thread's call
print(json.dumps(log.between(t, end + 1, {{"all": end - t}}, calls)))
"""


def test_compiled_in_setup_is_the_cold_run_only(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    script = ENCODE_ONCE.format(bench=str(BENCH), root=str(ROOT))

    def run() -> dict:
        out = subprocess.run(
            [sys.executable, "-c", script], env=env, capture_output=True,
            text=True, timeout=300, check=True,
        )
        return json.loads(out.stdout.splitlines()[-1])

    cold, warm = run(), run()
    encoders = [c for c in cold["compiled"] if c["program"] == "encode"]
    assert len(encoders) == 1 and encoders[0]["rows"] == 8, cold
    assert encoders[0]["seconds"] > 0 and encoders[0]["phase"] == "all"
    assert warm["compiled"] == [], warm
    assert warm["loaded"]["programs"] >= len(cold["compiled"]) > 0


def prefill_ms(planes: list) -> float:
    programs = trace_reduce.reduce_planes(planes)["programs"]
    p = programs["small_prefill"]
    return 1e3 * p["total_s"] / p["count"]


def test_a_cut_prefill_does_not_move_prefill_ms():
    planes = trace_reduce.read_planes(str(BENCH / "testdata" / "tiny.xplane.pb"))
    device = next(i for i, p in enumerate(planes) if p["name"].startswith("/device:TPU:"))
    modules = next(
        i for i, ln in enumerate(planes[device]["lines"])
        if ln["name"] == trace_reduce.MODULE_LINE
    )
    events = planes[device]["lines"][modules]["events"]
    first = min(range(len(events)), key=lambda i: events[i][1])
    name, start, dur = events[first]
    whole = prefill_ms(planes)
    assert abs(whole - 4.040e-3) < 1e-5  # the two prefills inside: 4,038 and 4,042 ns

    cut = copy.deepcopy(planes)  # the trace began a quarter into it
    cut[device]["lines"][modules]["events"][first] = (name, start, dur / 4)
    assert prefill_ms(cut) == whole

    without = copy.deepcopy(planes)
    for ln in without[device]["lines"]:
        ln["events"] = [e for e in ln["events"] if not start <= e[1] < start + dur]
    assert prefill_ms(without) == whole
    # the reduction says what it left out
    assert trace_reduce.reduce_planes(cut)["programs"]["small_prefill"]["cut"] == 1
