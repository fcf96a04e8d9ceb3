"""One run of one cell: build, ingest, warm up, measure, compare, print."""

from __future__ import annotations

import contextlib
import json
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Any, Callable

import numpy as np

from . import check, compiles, loadgen, spec, traffic, trace_reduce
from .server import Client, Rag, wait_until_indexed

# the traced part of a --trace 1 run: this long, from a quarter into the
# window (tens of MB of trace a run; the counters cover the whole window)
TRACE_WINDOW_S = 5.0
# questions made for a closed loop, a second: more than any server finishes
CLOSED_LOOP_QUESTIONS_PER_S = 60


class RunFailed(Exception):
    """The run cannot give a result; the message names why."""


def _device_check(chips: int, require_tpu: bool) -> Any:
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise RunFailed(
            f"needs {chips} TPU chip(s); jax.devices() returned {devices}"
        )
    return devices


def _buckets(lo: int, hi: int, floor: int, cap: int) -> list[int]:
    """The power-of-two buckets (as ``BucketPolicy`` rounds rows and the
    encoder's sequences, capped) that lengths from lo to hi can land in.
    Not for prompts: their widths are the batcher's to say
    (``_warm_prefill``)."""
    def bucket(n: int) -> int:
        b = floor
        while b < n:
            b *= 2
        return min(b, cap)

    out, n = [], lo
    while True:
        b = bucket(n)
        out.append(b)
        if b >= bucket(hi):
            return out
        n = b + 1


def _warm_prefill(widths_of: Callable[[list[int]], list[int]], lo: int,
                  hi: int) -> list[int]:
    """Loads every prompt width that lengths from lo to hi tokens can run
    at, by asking the program and not a copy of its rule:
    ``widths_of(lengths)`` runs prompts of that many tokens at once and
    returns every width the program has run a prompt at so far. The
    shortest and the longest prompt go first, together; where they ran at
    two widths, the first length past the last width found is tried until
    it runs at a width already met (the longest's), which finds every
    width between so long as a longer prompt never runs narrower."""
    found = widths_of(sorted({lo, hi}))
    n = min(found) + 1
    while n < hi:
        more = widths_of([n])
        if more == found:
            break
        n = max(min(set(more) - set(found)), n) + 1
        found = more
    return found


def _warm_up(rag: Rag, cell: spec.Cell, corpus: traffic.Corpus) -> dict:
    """Every shape the window can use, through the program's own entries:
    the batcher's ``submit`` for the prompt widths and the step, the
    embedder for the query buckets, and concurrent bursts over HTTP for
    the search buckets."""
    cfg, mix = cell.config, cell.mix
    srv = cfg["server"]
    seconds: dict[str, float] = {}
    t_mark = [time.monotonic()]

    def mark(name: str) -> None:
        now = time.monotonic()
        seconds[name] = now - t_mark[0]
        t_mark[0] = now

    in_flight = int(mix.get("clients") or mix.get("max_in_flight"))
    row_buckets = _buckets(1, in_flight, 8, 4096)
    q_lo, q_hi = mix["question_words"]
    seq_buckets = _buckets(q_lo + 1, q_hi + 1, 16, cfg["encoder"]["max_position_embeddings"])
    for rows in row_buckets:
        for seq in seq_buckets:
            rag.embedder.encode_many(
                [" ".join(f"warm{j}" for j in range(seq - 1))] * rows
            )
    warmed: dict[str, Any] = {"encode": [row_buckets, seq_buckets], "seconds": seconds}
    mark("encode")
    k = srv["search_topk"]
    if mix["route"] == "/v2/answer":
        lo, hi = corpus.prompt_token_range(k, mix["question_words"])
        warmed["prefill"] = _warm_prefill(rag.prompt_widths, lo, hi)
        mark("prefill_and_step")
    if float(mix.get("upserts_per_s", 0)) > 0:
        # the slab grows past its power of two with the first new
        # document, and its update program has a bucket per batch of
        # changed rows: both happen here, not in the window
        # up to 256 rows at once: a stalled engine takes that many upserts
        # in one wave, and a wave's size picks the encode and update buckets
        bursts = [1, 8, 16, 32, 64, 128, 256]
        for burst in bursts:
            docs = []
            for j in range(burst):
                if j % 8 == 0:
                    docs.append(corpus.append())
                else:
                    docs.append((j, corpus.replace(j)))
            rag.source.put(docs)
            # the burst's last document comes back first: it is embedded,
            # and the search that found it applied the pending rows to the
            # device slab
            wait_until_indexed(
                rag, len(corpus.texts), time.monotonic() + 120,
                probe=docs[-1][1], k=k,
            )
        warmed["upsert_bursts"] = bursts
        mark("upserts")
    # the search program's buckets follow how many queries share an engine
    # wave: bursts of every size up to the most in flight
    questions = traffic.make_questions(0, corpus, mix, 2 * in_flight)
    route = "/v1/retrieve"
    for rows in row_buckets:
        for _ in range(2):
            _burst(rag.port, route, questions[:min(rows, in_flight)], k)
    mark("search_bursts")
    if mix["route"] == "/v2/answer":
        # the served route once, end to end
        _burst(rag.port, mix["route"], questions[:1], k)
        mark("one_answer")
    return warmed


def _burst(port: int, route: str, questions: list[dict], k: int) -> None:
    """The questions at once, one thread each; any failure ends the run."""
    def one(q: dict, rec: loadgen.Record) -> None:
        client = Client(port)
        try:
            loadgen._send(client, route, rec, k)
        finally:
            client.close()

    recs = [loadgen.Record(0, q, 0.0) for q in questions]
    threads = [threading.Thread(target=one, args=qr) for qr in zip(questions, recs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for rec in recs:
        if rec.status != 200:
            raise RunFailed(f"warm-up {route} failed: {rec.reply!r}")


def _ingest(rag: Rag, corpus: traffic.Corpus,
            wave_rows: int | None = None) -> list[int]:
    """Feeds the corpus in waves of one fixed size, the encoder's largest
    row bucket, each put whole (``CorpusSource``) and the next only when
    the index holds the last: every encode call of every run is then the
    same program, the last wave as the first, where one ``put`` of the
    whole corpus was cut by timing into waves whose last had 77 to 2,833
    rows and a row bucket, and so an encoder program, of its own (PERF.md,
    Open question 14). Returns the rows of each encode call it caused."""
    initial = sorted(corpus.texts.items())
    wave = wave_rows or rag.wave_rows
    seen = len(rag.encode_calls)
    for i in range(0, len(initial), wave):
        rag.source.put(initial[i:i + wave])
        wait_until_indexed(
            rag, min(i + wave, len(initial)), time.monotonic() + 900
        )
    return [call[0] for call in rag.encode_calls[seen:]]


def _grown(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}


class Upserter(threading.Thread):
    """Sends the mix's upserts at their due times and keeps the log the
    comparison reads: (time pushed, doc id, old text or None, new text)."""

    def __init__(self, rag: Rag, corpus: traffic.Corpus, plan: list, t0: float,
                 seed: int):
        super().__init__(name="bench-upserter", daemon=True)
        self.rag, self.corpus, self.plan, self.t0 = rag, corpus, plan, t0
        self.log: list[tuple[float, int, str | None, str]] = []
        self._rng = np.random.default_rng([int(seed), 43])
        self._halt = threading.Event()

    def run(self) -> None:
        initial = int(self.corpus.cfg["passages"])
        for due, kind in self.plan:
            wait = self.t0 + due - time.monotonic()
            if wait > 0 and self._halt.wait(wait):
                return
            if kind == "replace":
                doc_id = int(self._rng.integers(0, initial))
                old = self.corpus.texts[doc_id]
                new = self.corpus.replace(doc_id)
            else:
                doc_id, new = self.corpus.append()
                old = None
            self.rag.source.put([(doc_id, new)])
            self.log.append((time.monotonic(), doc_id, old, new))

    def halt(self) -> None:
        self._halt.set()


def _sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)


def run_cell(
    bench_file: Path, workload: str, seed: int, seconds: float, trace: bool,
    *, t_start: float, require_tpu: bool = True, out_dir: Path | None = None,
    fault: Any = None, control: bool = False,
) -> dict:
    """Returns the result line as a dict. ``fault`` (a ``faults.Fault``)
    breaks the timed path underneath; ``control`` puts the fp8 reference's
    tokens and passages in the served ones' place before the comparison:
    both are for bench/tests and bench/control.py, and each has to come
    out with ``correct`` false."""
    cell = spec.Cell(bench_file, workload)
    compile_log = compiles.CompileLog().install()  # before anything compiles
    devices = _device_check(cell.chips, require_tpu)
    dev = devices[0]
    peaks = spec.peaks(dev.device_kind) if dev.platform == "tpu" else None
    cfg, mix = cell.config, cell.mix
    srv = cfg["server"]
    k = int(srv["search_topk"])
    closed = mix["loop"] == "closed"
    phases: dict[str, float] = {"imports": time.monotonic() - t_start}

    def phase(name: str, t: float) -> float:
        now = time.monotonic()
        phases[name] = now - t
        return now

    # ------------------------------------------------------------ set-up
    t = time.monotonic()
    corpus = traffic.Corpus(seed, cfg["corpus"])
    if closed:
        # the clients start one after another over ramp_s, before the
        # window opens: the ramp is set-up, the window sees a full queue
        ramp_s = float(mix["ramp_s"])
        due: list[float] = []
        n_questions = int((seconds + ramp_s) * CLOSED_LOOP_QUESTIONS_PER_S) + 64
    else:
        ramp_s = 0.0
        due = list(traffic.arrival_times(seed, float(mix["rate_per_s"]), seconds))
        n_questions = len(due)
    questions = traffic.make_questions(seed, corpus, mix, n_questions)
    t = phase("traffic", t)
    with fault.program(cfg) if fault is not None else contextlib.nullcontext():
        rag = Rag(cfg, seed)
    t = phase("build", t)
    rag.watch_encodes(True)
    rag.start()
    try:
        encode_waves = _ingest(rag, corpus)
        t = phase("ingest", t)
        warmed = _warm_up(rag, cell, corpus)
        t = phase("warm_up", t)
        rag.watch_encodes(False)  # the window runs the program's own flush
        if fault is not None:
            fault.served(rag)
        trace_dir = None
        if trace:
            trace_dir = (out_dir or spec.ROOT / ".bench-out") / "trace"
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True, exist_ok=True)

        # ---------------------------------------------- ramp, then window
        t_begin = time.monotonic()
        t0 = t_begin + ramp_s
        t_close = t0 + seconds
        setup_s = t0 - t_start
        loaded: list[list] = []

        def load() -> None:
            if closed:
                loaded.append(loadgen.closed_loop(
                    rag.port, mix["route"], questions, int(mix["clients"]), k,
                    t_begin, ramp_s, t_close,
                ))
            else:
                loaded.append(loadgen.open_loop(
                    rag.port, mix["route"], questions, due, k, t0,
                    int(mix["max_in_flight"]),
                ))

        loader = threading.Thread(target=load, name="bench-load", daemon=True)
        loader.start()
        _sleep_until(t0)
        before = rag.counters()
        upserter = Upserter(
            rag, corpus, traffic.upsert_plan(seed, mix, seconds), t0, seed
        )
        upserter.start()
        tracer = None
        if trace_dir is not None:
            tracer = trace_reduce.TraceWindow(
                trace_dir, t0 + seconds / 4.0, min(TRACE_WINDOW_S, seconds / 2.0)
            )
            tracer.start()
        _sleep_until(t_close)
        at_close = rag.counters()
        loader.join()
        if not loaded:
            raise RunFailed("the load generator died; see its traceback above")
        records = loaded[0]
        t_drained = time.monotonic()
        upserter.halt()
        upserter.join()
        traced = tracer.finish() if tracer is not None else None
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)  # tens of MB a run
        after = rag.counters()
        memory_peak = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices
        )
        phases["ramp"] = ramp_s
        phases["window"] = t_drained - t0

        # ------------------------------------- guarantees, server still up
        guarantees = _check_guarantees(rag, corpus, upserter.log, seed, k)
    finally:
        rag.stop()
    log_entries = _error_log()
    slot_of = dict(rag.slot_of)
    n_slots, n_steps = rag.batcher.n_slots, rag.batcher.n_steps
    rag.free()

    # ---------------------------------------------------------- compare
    t = time.monotonic()
    items, malformed = _items(cell, records, slot_of)
    numbers, program_numbers = _compare(
        cell, seed, corpus, items, malformed, upserter.log, guarantees,
        n_slots, control,
    )
    phases["reference"] = time.monotonic() - t

    # a program that compiled, fell back or was quarantined in the window
    # (or while it drained)
    window_faults = {
        "compiled_in_window": _grown(before["compiles"], after["compiles"]),
        "host_fallbacks_in_window": _grown(
            before["host_fallbacks"], after["host_fallbacks"]
        ),
        "quarantined": after["quarantined"],
        "error_log": log_entries[:5],
    }
    for name, what in window_faults.items():
        if what:
            numbers[name] = {"value": len(what), "limit": 0, "what": what}

    in_window = [r for r in records if t0 <= r.due < t_close]
    done = [r for r in records if r.status == 200 and t0 <= r.done <= t_close]
    failed = [r for r in in_window if r.status != 200]
    done_ids = {id(r) for r in done}
    prompt_tokens = [
        it["prompt_tokens"] for it in items
        if id(it["record"]) in done_ids and "prompt_tokens" in it
    ]
    counters = {
        # what the batcher counted from the window's first instant to its
        # last: requests in flight at either end count where they finished
        "batcher": _grown(before["batcher"], at_close["batcher"]),
        "n_slots": n_slots,
        "n_steps": n_steps,
        "attempted": len(in_window),
        "completed_in_window": len(done),
        "failed": len(failed),
        "upserts": len(upserter.log),
        "drain_s": t_drained - t_close,
    }
    if prompt_tokens:
        counters["prompt_tokens_mean"] = sum(prompt_tokens) / len(prompt_tokens)
        counters["prompt_tokens_min_max"] = [min(prompt_tokens), max(prompt_tokens)]
    values = _end_to_end(mix, in_window, done, seconds)
    values["setup_s"] = setup_s
    ctx = {
        "config": cfg, "mix": mix, "peaks": peaks, "window_s": seconds,
        "counters": counters, "records": in_window, "trace": traced,
        "dec_sizes": rag.dec_sizes, "enc_sizes": rag.enc_sizes,
        "prompt_tokens": prompt_tokens,
    }
    enc_len = cfg["encoder"]["max_position_embeddings"]
    ctx["encoder_rows"] = [
        min(enc_len, 1 + len(r.question["text"].split())) for r in in_window
    ] + [min(enc_len, 1 + len(new.split())) for _t, _d, _o, new in upserter.log]
    ctx["encoder_tokens"] = sum(ctx["encoder_rows"])
    metrics: dict[str, dict] = {}
    if trace:
        for m in cell.per_layer:
            got = cell.reader(m["name"])(ctx)
            if got is not None:
                metrics[m["name"]] = {"value": float(got), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RunFailed(f"no value for end-to-end metric {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result: dict[str, Any] = {
        "correct": _correct(numbers), "attempted": len(in_window),
        "failed": len(failed), "metrics": metrics, "device": device,
    }
    if traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = {
            "device_ops": traced["device_ops"][:10],
            "idle_gaps": traced["idle_gaps"][:10],
        }
    result["phases_s"] = phases
    result["counters"] = counters
    result["warmed"] = warmed
    # the rows of each encode call of the ingest: the same list in every run
    result["encode_waves"] = encode_waves
    result["encode_wave_s"] = [  # the ingest's calls are the embedder's first
        end - start for _rows, start, end, _thread in rag.encode_calls[:len(encode_waves)]
    ]
    # what XLA compiled, and did not load from the checkout's cache, between
    # process start and the window: everything in a checkout's first run,
    # nothing after it. It fails no run; it tells a draw from a regression
    in_setup = compile_log.between(t_start, t0, phases, rag.encode_calls)
    result["compiled_in_setup"] = in_setup["compiled"]
    result["loaded_in_setup"] = in_setup["loaded"]
    if trace:
        result["end_to_end_in_traced_run"] = values
        # executions the trace's edges cut, [program, seconds left of it]:
        # in no program's or operation's sum (trace_reduce)
        result["trace_cut"] = (traced or {}).get("cut_modules", [])
    if program_numbers is not None:
        # control mode: ``compared`` and ``correct`` are the control's;
        # what the program itself served, judged the same way, is here
        result["program"] = {
            "correct": _correct(program_numbers), "compared": program_numbers,
        }
    result["compared"] = numbers  # comes last: each number and its limit
    return result


def _correct(numbers: dict) -> bool:
    return all(_within(n["value"], n["limit"]) for n in numbers.values())


def _within(value: float, limit: float) -> bool:
    return value == value and value <= limit  # NaN fails


def _error_log() -> list[str]:
    import pathway_tpu as pw

    return [str(e) for e in pw.global_error_log().entries]


def _end_to_end(mix: dict, in_window: list, done_in_window: list,
                seconds: float) -> dict:
    out: dict[str, float] = {}
    if mix["loop"] == "closed":
        out["answers_per_s"] = len(done_in_window) / seconds
        return out
    # a failed or refused request counts as the worst
    lat = [
        (r.done - r.due) if r.status == 200 else float("inf")
        for r in in_window
    ]
    worst = max((x for x in lat if x != float("inf")), default=0.0)
    lat = [x if x != float("inf") else max(worst, 60.0) for x in lat]
    name = "retrieve_p95_ms" if mix["route"] == "/v1/retrieve" else "answer_p95_ms"
    out[name] = 1e3 * loadgen.percentile(lat, 95)
    return out


def _check_guarantees(rag: Rag, corpus: traffic.Corpus, log: list, seed: int,
                      k: int) -> dict:
    """After the window, with the source quiet: every replaced or new
    document's own text comes back first, and no retracted version comes
    back for its own text."""
    out = {"not_found": 0, "zombies": 0, "checked": 0}
    if log:
        wait_until_indexed(rag, len(corpus.texts), time.monotonic() + 60)
    rng = np.random.default_rng([int(seed), 61])
    live_ids = sorted({d for _t, d, _o, _n in log})
    picks = [live_ids[int(i)] for i in rng.permutation(len(live_ids))[:16]]
    picks += [int(i) for i in rng.integers(0, int(corpus.cfg["passages"]), 4)]
    retracted = [old for _t, _d, old, _n in log if old is not None]
    zombies = [retracted[int(i)] for i in rng.permutation(len(retracted))[:16]]
    client = Client(rag.port)
    try:
        deadline = time.monotonic() + 30
        for doc_id in picks:
            text = corpus.texts[doc_id]
            while True:
                status, hits = client.post("/v1/retrieve", {"query": text, "k": k})
                ok = status == 200 and hits and hits[0]["text"] == text
                # the last upserts may still be on their way to the index
                if ok or time.monotonic() > deadline:
                    break
                time.sleep(0.25)
            out["checked"] += 1
            out["not_found"] += 0 if ok else 1
        for text in zombies:
            status, hits = client.post("/v1/retrieve", {"query": text, "k": k})
            out["checked"] += 1
            if status != 200 or any(h["text"] == text for h in hits):
                out["zombies"] += 1
    finally:
        client.close()
    return out


def _items(cell: spec.Cell, records: list, slot_of: dict) -> tuple[list, int]:
    """What each finished request of the run said, and how many replies
    were not what the route promises."""
    cfg, mix = cell.config, cell.mix
    k = int(cfg["server"]["search_topk"])
    n_new = int(cfg["server"]["max_new_tokens"])
    dec = spec.family_of(cfg).sizes(cfg)
    budget = dec["positions"] - n_new
    items, malformed = [], 0
    for r in (r for r in records if r.status == 200):
        query = r.question["text"]
        if mix["route"] != "/v2/answer":
            texts = [h["text"] for h in (r.reply or [])]
            if len(texts) != k:
                malformed += 1
                continue
            items.append({"record": r, "query": query, "texts": texts})
            continue
        docs = (r.reply or {}).get("context_docs") or []
        toks = check.served_tokens((r.reply or {}).get("response", ""))
        texts = [d["text"] for d in docs]
        if len(toks) != n_new or len(texts) != k or any(
            not 0 <= t < dec["vocab"] for t in toks
        ):
            malformed += 1
            continue
        prompt = traffic.tokenize(
            traffic.build_prompt(texts, query), dec["vocab"], dec["positions"]
        )[-budget:]
        items.append({
            "record": r, "query": query, "texts": texts,
            # the decoder's side: what it was given, and what is judged
            "prompt": prompt, "given": toks, "tokens": toks,
            "prompt_tokens": len(prompt),
            # the slot the batcher admitted this very prompt into
            "slot": slot_of.get(tuple(prompt)),
        })
    return items, malformed


def _compare(cell: spec.Cell, seed: int, corpus: traffic.Corpus,
             items: list, malformed: int, log: list, guarantees: dict,
             n_slots: int, control: bool) -> tuple[dict, dict | None]:
    """The numbers compared, each beside its limit. With ``control`` the
    first dict is of the control (the fp8 reference's tokens and passages
    in the served ones' place, judged by the very same code) and the second
    of what the program served; without, the second is None."""
    cfg, mix = cell.config, cell.mix
    limits = _limits(cell)
    answers = mix["route"] == "/v2/answer"
    if answers:
        sample = check.pick_per_slot(seed, items, n_slots)
    else:
        sample = check.pick_sample(seed, items, int(mix["sample_requests"]))
    if not sample:
        return {"sampled": {
            "value": 1, "limit": 0, "what": "no finished request to compare",
        }}, None
    # the corpus as it stood: versions that changed while a sampled
    # request was open are in flux
    spans = [(s["record"].sent, s["record"].done) for s in sample]
    lo, hi = min(a for a, _ in spans), max(b for _, b in spans)
    settle = 2.0  # seconds an upsert may take to reach the index
    in_flux = {d for t, d, _o, _n in log if lo - settle <= t <= hi + settle}
    live = dict(corpus.texts)
    for t, d, old, _new in reversed(log):
        if t > hi + settle:  # pushed after the sample: roll back
            if old is None:
                live.pop(d, None)
            else:
                live[d] = old

    def judge(sample: list) -> dict:
        numbers: dict[str, dict] = {}
        rg = check.rank_gaps(seed, cfg, live, in_flux, sample, corpus.history)
        numbers["rank_gap"] = {
            "value": rg["gap"], "limit": limits["rank_gap"],
            "requests": rg["requests"],
        }
        if answers:
            lg = check.logit_gaps(seed, cfg, sample)
            numbers["logit_gap"] = {
                "value": lg["gap"], "limit": limits["logit_gap"],
                "tokens": lg["tokens"], "off_best": lg["off_best"],
                "slots": sorted({s["slot"] for s in sample if s["slot"] is not None}),
            }
        numbers["malformed"] = {"value": malformed + rg["unknown_texts"], "limit": 0}
        numbers["not_found"] = {"value": guarantees["not_found"], "limit": 0}
        numbers["zombies"] = {"value": guarantees["zombies"], "limit": 0}
        return numbers

    served = judge(sample)
    if not control:
        return served, None
    return judge(check.control_sample(seed, cfg, live, in_flux, sample)), served


def _limits(cell: spec.Cell) -> dict:
    """The limits of the numbers compared, kept beside the configuration:
    ``bench/configs/<configuration>.limits.json``."""
    path = Path(cell.config["_file"]).with_suffix(".limits.json")
    with open(path) as f:
        return json.load(f)["limits"]


def print_result(result: dict) -> None:
    compared = result.get("compared", {})
    for name, n in compared.items():
        print(
            f"compared {name}: {n['value']!r} limit {n['limit']!r}"
            + (f" ({n['what']})" if "what" in n else ""),
            file=sys.stderr,
        )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
