"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process builds the live RAG service the way
``examples/adaptive_rag/app.py`` does — documents -> ``DocumentStore`` ->
``JaxEmbedder`` -> ``BruteForceKnnFactory`` -> ``BaseRAGQuestionAnswerer``
-> ``run_server(threaded=True)`` — at the full width of the largest encoder
and decoder the repository has, answers HTTP requests on localhost, and
then checks that the chip, not a fallback, did the work: an empty
quarantine, zero host fallbacks on every device program, an empty error
log, both native libraries loaded, the Mosaic kernel inside the encode
program, and two numerical checks against references.

    python chip_smoke.py          # on a machine with a TPU

It exits non-zero, naming what ``jax.devices()`` returned, when there is
no TPU, and it sets no ``JAX_PLATFORMS`` itself. Weights are random, made
from a seed; the corpus is generated here (no network, no files read).
The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<checkout>/.pathway-cache/xla``; a second run adds nothing to it.

With more than one device visible it also runs the sharded legs of
``__graft_entry__.py`` and a mesh-spanning decode on the real devices.

The seconds it prints are wall-clock bring-up facts (how long a cold and
a warm start take), not a benchmark: no rate is printed.

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
"""

from __future__ import annotations

import dataclasses
import json
import os
import socket
import sys
import threading
import time
import urllib.request
from typing import Any

import numpy as np


@dataclasses.dataclass(frozen=True)
class Widths:
    """Model widths and traffic of one smoke run."""

    encoder: dict[str, int]
    decoder: dict[str, int]
    max_new_tokens: int
    n_docs: int
    # the kernel check sweeps every (sequence bucket, row bucket) the
    # DevicePlane's BucketPolicy can hand the encoder
    kernel_seqs: tuple[int, ...]
    kernel_rows: tuple[int, ...]


# The largest widths the repository had when this check was written
# (PR 21); no width and no depth is cut.
FULL = Widths(
    encoder=dict(
        vocab_size=32768, d_model=384, n_heads=6, n_layers=6, d_ff=1536,
        max_len=128, embed_dim=384,
    ),
    decoder=dict(
        vocab_size=256_128, d_model=2048, n_heads=8, n_layers=18,
        d_ff=16384, max_len=1024,
    ),
    max_new_tokens=16,
    n_docs=320,
    kernel_seqs=(16, 32, 64, 128),
    kernel_rows=(8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096),
)

SEARCH_TOPK = 4
SEED = 20260926


class Checks:
    """Named pass/fail checks; any failure makes the run exit non-zero."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def check(self, name: str, ok: bool, detail: Any = None) -> bool:
        if not ok:
            self.failed.append(
                name if detail is None else f"{name}: {str(detail)[:600]}"
            )
        return bool(ok)


# ------------------------------------------------------------------ corpus


def make_corpus(n_docs: int, max_len: int) -> list[str]:
    """Seeded short documents over a large made-up vocabulary, so two
    documents share few words. Lengths run from a handful of words to
    past the encoder's ``max_len``: the ingest wave reaches the widest
    sequence bucket, and questions of three lengths reach the others."""
    rng = np.random.default_rng(SEED)
    docs = []
    for i in range(n_docs):
        n_words = max_len if i % 40 == 7 else int(rng.integers(6, 22))
        words = [f"w{int(w)}" for w in rng.integers(0, 20000, n_words)]
        docs.append(f"doc{i} " + " ".join(words))
    return docs


def make_questions(docs: list[str]) -> list[str]:
    """Sixteen questions built from document words. Three lengths, so the
    query encoder runs at sequence buckets 16, 32 and 64 (the tokenizer
    adds one token to each)."""
    rng = np.random.default_rng(SEED + 1)
    out = []
    for i, n_words in enumerate([6] * 10 + [24] * 3 + [48] * 3):
        words = docs[int(rng.integers(0, len(docs)))].split()
        picked = [words[int(j)] for j in rng.integers(0, len(words), n_words)]
        out.append(f"question{i} what about " + " ".join(picked))
    return out


# --------------------------------------------------------- numerical checks


def check_kernel(widths: Widths, on_tpu: bool, checks: Checks) -> dict:
    """`fused_qkv_attention` against `reference_attention` at the
    encoder's head shape, for every sequence bucket and row bucket.

    Tolerance: both sides accumulate in float32 and round the
    probabilities and the context to bfloat16, but in a different order
    (the kernel normalises with an explicit divide, the reference with
    `jax.nn.softmax`), so results may differ by a unit or two in the
    last place of bfloat16 — 8 significand bits, 2**-8 relative. The
    bound is 2**-6 times the largest reference magnitude (at least 1).

    Off the TPU (tests) the same code runs through the Pallas
    interpreter."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops.attention import (
        fused_qkv_attention,
        reference_attention,
    )

    d, h = widths.encoder["d_model"], widths.encoder["n_heads"]

    @jax.jit
    def compare(qkv, mask):
        got = fused_qkv_attention(qkv, mask, h, interpret=not on_tpu)
        got = got.astype(jnp.float32)
        want = reference_attention(qkv, mask, h).astype(jnp.float32)
        return (
            jnp.max(jnp.abs(got - want)),
            jnp.max(jnp.abs(want)),
            jnp.all(jnp.isfinite(got)),
        )

    rng = np.random.default_rng(SEED + 2)
    key = jax.random.PRNGKey(SEED + 2)
    worst = 0.0
    for s in widths.kernel_seqs:
        for b in widths.kernel_rows:
            key, sub = jax.random.split(key)
            qkv = jax.random.normal(sub, (b, s, 3 * d), jnp.bfloat16)
            lens = rng.integers(1, s + 1, (b, 1))
            mask = jnp.asarray(np.arange(s)[None, :] < lens, jnp.int32)
            err, top, finite = (float(x) for x in compare(qkv, mask))
            bound = 2.0 ** -6 * max(1.0, top)
            worst = max(worst, err / bound)
            checks.check(
                f"kernel s={s} b={b}",
                bool(finite) and err <= bound,
                f"max abs err {err:.4g} > {bound:.4g}",
            )
    return {
        "head_dim": d // h,
        "shapes_checked": len(widths.kernel_seqs) * len(widths.kernel_rows),
        "worst_err_over_bound": round(worst, 3),
    }


def check_encode(embedder: Any, texts: list[str], checks: Checks) -> dict:
    """`JaxEmbedder` on the default device (bf16, the Pallas kernel on a
    TPU) against the float32 einsum path on the host CPU backend of this
    process: cosine >= 0.99 for every row."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import encoder

    got = np.stack(embedder.encode_many(texts)).astype(np.float32)
    cpu = jax.devices("cpu")[0]
    cfg = dataclasses.replace(
        embedder.config, dtype=jnp.float32, fused_attention=False
    )
    ids, mask = embedder.tokenizer.batch(texts)
    with jax.default_device(cpu):
        params = jax.tree.map(
            lambda x: jax.device_put(np.asarray(x, np.float32), cpu),
            embedder.params,
        )
        want = np.asarray(
            encoder.encode(params, jnp.asarray(ids), jnp.asarray(mask), cfg)
        )
    cos = np.sum(got * want, axis=1) / (
        np.linalg.norm(got, axis=1) * np.linalg.norm(want, axis=1)
    )
    checks.check(
        "encode vs float32 reference on the host CPU",
        got.shape == want.shape
        and np.isfinite(got).all()
        and float(cos.min()) >= 0.99,
        f"cosine per row {np.round(cos, 4).tolist()}",
    )
    return {"rows": len(texts), "min_cosine": round(float(cos.min()), 5)}


# ------------------------------------------------------------------ service


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(port: int, route: str, payload: dict, timeout: float) -> Any:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        if r.status != 200:
            raise RuntimeError(f"{route}: HTTP {r.status}")
        return json.loads(r.read().decode())


def _wait_until_indexed(port: int, n_docs: int, deadline: float) -> None:
    """Poll /v1/statistics until the store reports every document."""
    last: Any = None
    while time.monotonic() < deadline:
        try:
            last = _post(port, "/v1/statistics", {}, timeout=30)
            if last.get("file_count") == n_docs:
                return
        except OSError as e:  # server still starting
            last = e
        time.sleep(0.5)
    raise TimeoutError(f"index never reported {n_docs} documents: {last!r}")


def _device_ids(x: Any) -> list[int]:
    return sorted(d.id for d in x.devices())


def serve_and_ask(widths: Widths, checks: Checks, report: dict) -> Any:
    """Build the RAG service, serve it from a thread, send the traffic,
    stop it, and check what the device plane did. Returns the chat, whose
    decoder the several-chip leg decodes with again."""
    import jax
    import jax.numpy as jnp

    import pathway_tpu as pw
    from pathway_tpu.engine.core import ExternalIndexNode
    from pathway_tpu.engine.device_plane import get_device_plane
    from pathway_tpu.internals import run as run_mod
    from pathway_tpu.models import embedder_config, lm_config, transformer
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import JaxEmbedder
    from pathway_tpu.xpacks.llm.llms import JaxLMChat
    from pathway_tpu.xpacks.llm.question_answering import (
        BaseRAGQuestionAnswerer,
    )

    plane = get_device_plane()
    # what the process-wide plane held before this run (nothing, when
    # run as a script): only what this run adds is judged
    before = {
        "fallbacks": {n: p.host_fallbacks for n, p in plane.programs.items()},
        "quarantined": set(plane.quarantined()),
    }
    on_tpu = jax.default_backend() == "tpu"
    docs = make_corpus(widths.n_docs, widths.encoder["max_len"])
    questions = make_questions(docs)

    embedder = JaxEmbedder(config=embedder_config(**widths.encoder))
    report["encode_check"] = check_encode(embedder, docs[:12], checks)
    ids, mask = embedder.tokenizer.batch(docs[:8], pad_to=16)
    lowered = embedder._encode.lowered_text(
        embedder.params, jnp.asarray(ids), jnp.asarray(mask)
    )
    report["mosaic_call_in_encode"] = "tpu_custom_call" in lowered
    if on_tpu:
        checks.check(
            "encode program calls the Mosaic kernel",
            report["mosaic_call_in_encode"],
        )

    # bf16 leaf by leaf: JaxLMChat would initialise float32 parameters,
    # 8.2 GB of the chip's 16 at this width
    lm_cfg = lm_config(**widths.decoder)
    chat = JaxLMChat(
        config=lm_cfg,
        params=transformer.init_params(
            jax.random.PRNGKey(1), lm_cfg, jnp.bfloat16
        ),
        max_new_tokens=widths.max_new_tokens,
    )

    table = pw.debug.table_from_rows(
        pw.schema_from_types(data=bytes, _metadata=object),
        [(d.encode(), {"path": f"doc{i}.txt"}) for i, d in enumerate(docs)],
    )
    store = DocumentStore(
        table,
        retriever_factory=BruteForceKnnFactory(
            dimensions=embedder.get_embedding_dimension(), embedder=embedder
        ),
    )
    qa = BaseRAGQuestionAnswerer(chat, store, search_topk=SEARCH_TOPK)
    port = _free_port()
    t_start = time.monotonic()
    thread = qa.run_server(
        host="127.0.0.1", port=port, threaded=True, with_cache=False,
        terminate_on_error=True,
    )
    answers: dict[int, str] = {}
    errors: list[str] = []

    def ask(i: int) -> None:
        try:
            reply = _post(
                port, "/v2/answer", {"prompt": questions[i]}, timeout=600
            )
            answers[i] = reply["response"]
        except Exception as e:  # noqa: BLE001 — reported as a failed check
            errors.append(f"question {i}: {type(e).__name__}: {e}")

    try:
        _wait_until_indexed(port, len(docs), time.monotonic() + 600)
        ask(0)
        report["seconds_to_first_answer"] = round(
            time.monotonic() - t_start, 1
        )
        t_rest = time.monotonic()
        first = answers.get(0)
        ask(0)  # the same prompt again must give the same answer
        checks.check(
            "same prompt twice gives the same answer",
            first is not None and answers.get(0) == first,
        )
        # eight at once: requests join a running decode at a step boundary
        burst = [threading.Thread(target=ask, args=(i,)) for i in range(1, 9)]
        for t in burst:
            t.start()
        for t in burst:
            t.join(timeout=900)
        for i in range(9, len(questions)):
            ask(i)
        target = docs[13]
        hits = _post(
            port, "/v1/retrieve", {"query": target, "k": 3}, timeout=600
        )
        checks.check(
            "retrieve of a document's exact text returns it first",
            bool(hits) and hits[0]["text"] == target,
            [h["text"][:40] for h in hits],
        )
        report["seconds_for_the_rest"] = round(time.monotonic() - t_rest, 1)
        session = run_mod.current_session()
        indexes = [
            n.host_index
            for n in session.graph.nodes
            if isinstance(n, ExternalIndexNode)
        ]
    finally:
        run_mod.stop_current_run()
        qa.server.webserver.stop()
        thread.join(timeout=120)
    checks.check("server thread stopped", not thread.is_alive())
    chat._cb.drain()

    sent = len(questions) + 1
    checks.check("every request answered", not errors, errors)
    checks.check(
        "every question has an answer",
        sorted(answers) == list(range(len(questions))),
        sorted(answers),
    )
    lengths = {len(a.split()) for a in answers.values()}
    checks.check(
        f"every answer is {widths.max_new_tokens} tokens long",
        lengths == {widths.max_new_tokens},
        lengths,
    )
    report["requests"] = {
        "answer_sent": sent, "answer_ok": sent - len(errors),
        "retrieve_sent": 1,
    }
    check_device_plane(plane, before, indexes, checks, report)

    # where the default single-mesh path put its arrays
    cache = plane.lease(chat._cb._cache_key, lambda: None)
    report["placement"] = {
        "encoder_params": _device_ids(embedder.params["tok_embed"]),
        "decoder_params": _device_ids(chat.params["tok_embed"]),
        "kv_cache": _device_ids(cache["k"]) if cache else None,
        "knn_slab": sorted(
            {d for i in indexes if i._device_docs is not None
             for d in _device_ids(i._device_docs)}
        ),
    }
    if cache is not None:
        plane.restore(chat._cb._cache_key, cache)
    return chat


def check_device_plane(
    plane: Any, before: dict, indexes: list, checks: Checks, report: dict
) -> None:
    """The device plane did the work, and nothing degraded quietly."""
    import pathway_tpu as pw

    pools = plane.slot_pools()
    report["slot_pools"] = pools
    checks.check(
        "a request joined a running decode",
        any(p["joined_inflight"] > 0 for p in pools.values()),
        pools,
    )
    report["compile_counts"] = {
        f"{n} {b}": c for (n, b), c in sorted(
            plane.compile_counts().items(), key=str
        )
    }
    report["compile_seconds"] = {
        f"{n} {b}": round(s, 2) for (n, b), s in sorted(
            plane.compile_seconds().items(), key=str
        )
    }
    quarantined = {
        k: q for k, q in plane.quarantined().items()
        if k not in before["quarantined"]
    }
    checks.check("no quarantined program", not quarantined, quarantined)
    fallbacks = {
        n: p.host_fallbacks for n, p in plane.programs.items()
        if p.host_fallbacks > before["fallbacks"].get(n, 0)
    }
    checks.check("no host fallback on any program", not fallbacks, fallbacks)
    ran = {n.split("#")[0] for n, _b in plane.compile_counts()}
    want = {"embed_encode", "cb", "knn_slab_search"}
    checks.check("every program of the path ran", want <= ran, ran)
    checks.check(
        "every index searched on the device",
        bool(indexes)
        and all(i.use_device and i._device_failures == 0 for i in indexes),
        [(i.use_device, i._device_failures) for i in indexes],
    )
    log = pw.global_error_log().entries
    checks.check("error log empty", not log, log[:5])


# ------------------------------------------------------------ several chips


def check_sharded_legs(
    n_devices: int, chat: Any, checks: Checks, report: dict
) -> None:
    """On a multi-chip host: the sharded legs of ``__graft_entry__`` and a
    mesh-spanning decode, on the real devices, in this process. Each leg
    reports the devices that hold what its program produced; fewer than
    all of them (at least four) is a failure.

    The decode is the served decoder itself — the chat's configuration
    and parameters, no width or depth cut — in a slot pool spread over
    the mesh, against the same prompts through a single-device pool. The
    parameters are copied to every chip first: the batcher leaves them
    where its caller put them."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import __graft_entry__ as graft
    from pathway_tpu.engine.device_plane import get_device_plane
    from pathway_tpu.parallel import device_exchange as dx
    from pathway_tpu.parallel.mesh import default_mesh
    from pathway_tpu.serving.continuous_batching import ContinuousBatcher

    legs = graft.sharded_legs(n_devices)
    # the AUTO gate of the device wire (a multi-device TPU mesh and a
    # payload past AUTO_MIN_ELEMS), which no real mesh had met
    if jax.default_backend() == "tpu":
        legs["engine_groupby_exchange_a2a_auto"] = (
            graft._leg_engine_device_exchange(
                n_devices, forced=False,
                rows=2 * dx.AUTO_MIN_ELEMS // 256, width=256,
            )
        )

    plane = get_device_plane()
    everywhere = NamedSharding(default_mesh(("data",)), P())
    prompts = [f"prompt {i} " + "word " * (3 * i) for i in range(4)]
    slots_per_chip, tokens = 2, {}
    for span in (False, True):
        cb = ContinuousBatcher(
            params=jax.device_put(chat.params, everywhere) if span
            else chat.params,
            cfg=chat.config, tokenizer=chat.tokenizer, n_steps=3,
            n_slots=slots_per_chip, mesh_span=span,
        )
        try:
            futs = [cb.submit(p) for p in prompts]
            tokens[span] = [f.result(timeout=600) for f in futs]
            cb.drain()
            if span:
                # the cache as the last decode step handed it back
                cache = plane.lease(cb._cache_key, lambda: None)
                legs["continuous_batcher_mesh_span"] = _device_ids(cache["k"])
                local = {
                    sh.data.shape[1] for sh in cache["k"].addressable_shards
                }
                checks.check(
                    f"each chip holds {slots_per_chip} decode slots",
                    local == {slots_per_chip}, local,
                )
                plane.restore(cb._cache_key, cache)
        finally:
            cb.close()
    checks.check(
        "mesh-spanning decode matches the single-device pool",
        tokens[True] == tokens[False], tokens,
    )
    report["mesh_span_decode"] = {
        "d_model": chat.config.d_model, "n_layers": chat.config.n_layers,
        "vocab_size": chat.config.vocab_size,
        "slots": slots_per_chip * n_devices, "requests": len(prompts),
    }
    report["sharded_legs"] = legs
    need = max(4, n_devices)
    for leg, ids in legs.items():
        checks.check(
            f"{leg} spans {need} devices", len(ids) >= need, ids
        )


# --------------------------------------------------------------------- main


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for f in os.listdir(path) if f.endswith("-cache"))
    except FileNotFoundError:
        return 0


def run_smoke(widths: Widths = FULL, *, require_tpu: bool = True) -> int:
    """The whole smoke; returns the process exit code. ``require_tpu`` is
    lifted only by the tier-1 test, which drives the same code at tiny
    widths on the CPU."""
    t0 = time.monotonic()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; jax.devices() returned {devices}",
            file=sys.stderr,
        )
        return 2

    import jaxlib

    import pathway_tpu as pw
    from pathway_tpu.engine import native
    from pathway_tpu.engine.device_plane import (
        compile_cache_dir,
        get_device_plane,
    )
    from pathway_tpu.engine.native import dataplane

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None

    get_device_plane()  # configures the compile cache before any compile
    cache_dir = compile_cache_dir()
    cache_before = _cache_entries(cache_dir)
    checks = Checks()
    phases: dict[str, float] = {"start_up": round(time.monotonic() - t0, 1)}

    def timed(name: str, fn: Any, *args: Any) -> Any:
        t = time.monotonic()
        out = fn(*args)
        phases[name] = round(time.monotonic() - t, 1)
        return out

    report: dict[str, Any] = {
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
        },
        "versions": {
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version,
        },
        "widths": {
            "encoder": widths.encoder, "decoder": widths.decoder,
            "max_new_tokens": widths.max_new_tokens, "n_docs": widths.n_docs,
        },
    }
    report["native"] = timed(
        "native_build_or_load",
        lambda: {
            "zset": native.available(), "dataplane": dataplane.available(),
        },
    )
    checks.check(
        "both native libraries built and loaded",
        all(report["native"].values()), report["native"],
    )
    pw.global_error_log().entries.clear()
    report["kernel_check"] = timed(
        "kernel_check", check_kernel, widths, dev.platform == "tpu", checks
    )
    chat = timed("build_serve_ask_stop", serve_and_ask, widths, checks, report)
    if len(devices) > 1:
        timed(
            "sharded_legs", check_sharded_legs, len(devices), chat, checks,
            report,
        )
    report["phase_seconds"] = phases
    report["compile_cache"] = {
        "dir": cache_dir,
        "entries_before": cache_before,
        "entries_added": _cache_entries(cache_dir) - cache_before,
    }
    report["wall_seconds"] = round(time.monotonic() - t0, 1)
    report["failed_checks"] = checks.failed
    report["ok"] = not checks.failed
    report["claim"] = None
    print(json.dumps(report), flush=True)
    if checks.failed:
        for f in checks.failed:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(run_smoke())
