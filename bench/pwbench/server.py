"""Builds the system under test: the live RAG server of
``chip_smoke.serve_and_ask`` (documents -> ``DocumentStore`` ->
``JaxEmbedder`` -> ``BruteForceKnnFactory`` -> ``BaseRAGQuestionAnswerer``
-> ``run_server(threaded=True)``), its sizes read from a configuration
file, its corpus fed through a ``ConnectorSubject`` that goes on upserting
while the server answers. Both configurations share every line here."""

from __future__ import annotations

import json
import queue
import socket
import threading
import time
from typing import Any

from . import spec, weights


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Rag:
    """The running server and the handles the harness reads counters
    from."""

    def __init__(self, config: dict, seed: int):
        import jax.numpy as jnp

        import pathway_tpu as pw
        from pathway_tpu.engine.device_plane import get_device_plane
        from pathway_tpu.models import embedder_config
        from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
        from pathway_tpu.xpacks.llm.document_store import DocumentStore
        from pathway_tpu.xpacks.llm.embedders import JaxEmbedder
        from pathway_tpu.xpacks.llm.llms import JaxLMChat
        from pathway_tpu.xpacks.llm.question_answering import (
            BaseRAGQuestionAnswerer,
        )

        self.config = config
        self.plane = get_device_plane()  # sets the compile cache first
        enc, srv = config["encoder"], config["server"]
        family = spec.family_of(config)  # the decoder's block is its file's
        self.enc_sizes = weights.encoder_sizes(enc)
        self.dec_sizes = family.sizes(config)
        dtype = {"bfloat16": jnp.bfloat16}[config["dtype"]]
        enc_cfg = embedder_config(
            vocab_size=enc["vocab_size"], d_model=enc["hidden_size"],
            n_heads=enc["num_attention_heads"],
            n_layers=enc["num_hidden_layers"], d_ff=enc["intermediate_size"],
            max_len=enc["max_position_embeddings"],
            embed_dim=enc["embedding_size"], dtype=dtype,
        )
        self.embedder = JaxEmbedder(
            config=enc_cfg, params=weights.make_params(seed, self.enc_sizes)
        )
        self.chat = JaxLMChat(
            config=family.program_config(config, dtype),
            params=family.make_params(seed, self.dec_sizes),
            max_new_tokens=srv["max_new_tokens"],
            decode_slots=srv["decode_slots"],
        )
        self.batcher = self.chat._cb
        self.slot_of = _log_slots(self.batcher)
        self.source = CorpusSource()

        class DocSchema(pw.Schema):
            doc_id: int = pw.column_definition(primary_key=True)
            data: bytes
            _metadata: dict

        table = pw.io.python.read(
            self.source.subject, schema=DocSchema, name="bench-corpus"
        )
        self.indexes: list[Any] = []  # the store's index, once the run builds it
        store = DocumentStore(
            table,
            retriever_factory=_watched(BruteForceKnnFactory, self.indexes)(
                dimensions=enc["embedding_size"], embedder=self.embedder
            ),
        )
        self.qa = BaseRAGQuestionAnswerer(
            self.chat, store, search_topk=srv["search_topk"]
        )
        self.port = free_port()
        self.thread: threading.Thread | None = None
        # the encoder's largest row bucket: a wave of the ingest
        self.wave_rows = int(self.embedder._batcher.max_batch)
        # (rows, start, end, thread) of every encode call while set-up watches
        self.encode_calls: list[tuple[int, float, float, int]] = []

    def start(self) -> None:
        self.thread = self.qa.run_server(
            host="127.0.0.1", port=self.port, threaded=True,
            with_cache=False, terminate_on_error=True,
        )

    def stop(self) -> None:
        from pathway_tpu.internals import run as run_mod

        self.source.stop()
        run_mod.stop_current_run()
        if self.qa.server is not None:
            self.qa.server.webserver.stop()
        if self.thread is not None:
            self.thread.join(timeout=120)
            if self.thread.is_alive():
                raise RuntimeError("the server thread did not stop")
        self.batcher.drain(timeout=120)

    def free(self) -> None:
        """Drop the decoder, the cache and the encoder from the device, so
        that the reference has the chip's memory."""
        self.chat._finalizer()
        self.embedder._finalizer()
        self.batcher.params = None
        self.chat.params = None
        self.embedder.params = None

    def indexed(self) -> int:
        """Documents the store's index holds now: embedded, added, and
        seen by the next search (``/v1/statistics`` counts the parsed)."""
        return len(self.indexes[-1]) if self.indexes else 0

    def watch_encodes(self, on: bool) -> None:
        """While on, every call of the embedder's flush (the engine's
        waves and the queries alike) is noted in ``encode_calls``. Set-up
        watches; the window runs the program's own function."""
        batcher = self.embedder._batcher
        if not on:
            batcher.flush_fn = self.embedder._encode_batch
            return

        def noted(texts: list[str]) -> list:
            t = time.monotonic()
            out = self.embedder._encode_batch(texts)
            self.encode_calls.append(
                (len(texts), t, time.monotonic(), threading.get_ident())
            )
            return out

        batcher.flush_fn = noted

    def prompt_widths(self, lengths: list[int]) -> list[int]:
        """Runs prompts of that many tokens (the leading class token and
        made-up words) through the batcher's ``submit``, all at once, and
        returns every width the program says it has run a prompt at: the
        sequence lengths of the prefill program's buckets in the plane's
        compile counts."""
        futures = [
            self.batcher.submit(" ".join(f"warm{j}" for j in range(n - 1)))
            for n in lengths
        ]
        for f in futures:
            f.result(timeout=1200)
        return sorted(
            int(bucket[-1]) for (name, bucket) in self.plane.compile_counts()
            if name == f"{self.batcher.name}/prefill"
        )

    # ---------------------------------------------------------- counters

    def counters(self) -> dict:
        """What the program counts, as it stands now."""
        plane = self.plane
        return {
            "batcher": dict(self.batcher.stats),
            "compiles": {
                f"{n} {b}": c for (n, b), c in plane.compile_counts().items()
            },
            "compile_seconds": {
                f"{n} {b}": s for (n, b), s in plane.compile_seconds().items()
            },
            "host_fallbacks": {
                n: p.host_fallbacks for n, p in plane.programs.items()
            },
            "quarantined": [f"{n} {b}" for (n, b) in plane.quarantined()],
            "slot_pools": plane.slot_pools(),
        }


def _watched(factory_cls: Any, found: list) -> Any:
    """The index factory, with the index it builds noted in ``found``:
    the engine makes the index when the run starts and shows it nowhere,
    and the harness waits for its count. The index is the program's own,
    built by the program's own code."""

    class WatchedFactory(factory_cls):
        def build_inner_index(self, *args: Any, **kwargs: Any) -> Any:
            inner = super().build_inner_index(*args, **kwargs)
            make = inner._host_index_factory

            def noting() -> Any:
                build = make()

                def built() -> Any:
                    found.append(build())
                    return found[-1]

                return built

            # a frozen dataclass: set as it sets its own cached column
            object.__setattr__(inner, "_host_index_factory", noting)
            return inner

    return WatchedFactory


def _log_slots(batcher: Any) -> dict[tuple, int]:
    """Prompt (its token ids) -> the slot the batcher admitted it into:
    the program keeps the slot on its request and shows it nowhere, so the
    harness notes it where the program assigns it, for a sample of
    ``correct`` that holds an answer of every slot. A batcher without
    ``_admit(req, slot, cache)`` gives an empty log, and the sample is
    then drawn without the slots (the result's ``slots`` is empty)."""
    slot_of: dict[tuple, int] = {}
    admit = getattr(batcher, "_admit", None)
    if admit is None:
        return slot_of

    def logged(req: Any, slot: int, cache: Any) -> Any:
        slot_of[tuple(int(t) for t in req.row)] = int(slot)
        return admit(req, slot, cache)

    batcher._admit = logged
    return slot_of


class CorpusSource:
    """Feeds documents to the engine; built before ``pw.io.python.read``
    wraps it. ``put`` may be called from any thread at any time, and what
    one ``put`` holds reaches the engine whole: the engine's pump takes
    whatever the session has staged each time it looks, and the program's
    ``commit`` is a yield, so rows staged one by one were cut into waves
    by timing (PERF.md, Open question 14). The pump's ``drain`` of this
    session therefore waits while a ``put`` is being staged; the rows
    still go in through the program's own ``next``."""

    def __init__(self) -> None:
        import pathway_tpu as pw

        outer = self
        self._queue: queue.Queue = queue.Queue()
        self._stop = threading.Event()

        class Subject(pw.io.python.ConnectorSubject):
            def run(self) -> None:
                session = self._session
                whole = threading.Lock()
                drain = session.drain

                def drain_whole_puts() -> list:
                    with whole:
                        return drain()

                session.drain = drain_whole_puts
                while not outer._stop.is_set():
                    try:
                        batch = outer._queue.get(timeout=0.05)
                    except queue.Empty:
                        continue
                    with whole:
                        for doc_id, text in batch:
                            self.next(
                                doc_id=doc_id, data=text.encode(),
                                _metadata={"path": f"p{doc_id}"},
                            )
                    self.commit()

        self.subject = Subject()

    def put(self, docs: list[tuple[int, str]]) -> None:
        self._queue.put(docs)

    def stop(self) -> None:
        self._stop.set()


class Client:
    """One keep-alive HTTP connection; one per thread."""

    def __init__(self, port: int, timeout: float = 120.0):
        import http.client

        self._http = http.client
        self.port = port
        self.timeout = timeout
        self.conn: Any = None

    def post(self, route: str, payload: dict) -> tuple[int, Any]:
        body = json.dumps(payload).encode()
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = self._http.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout
                )
            try:
                self.conn.request(
                    "POST", route, body, {"Content-Type": "application/json"}
                )
                r = self.conn.getresponse()
                data = r.read()
                break
            except (self._http.RemoteDisconnected, ConnectionResetError,
                    BrokenPipeError):
                # a kept-alive connection the server closed: once more
                self.close()
                if attempt:
                    raise
        if r.status != 200:
            return r.status, None
        return 200, json.loads(data.decode())

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def wait_until_indexed(rag: Rag, n_docs: int, deadline: float,
                       probe: str | None = None, k: int = 1) -> None:
    """Returns when the store's index holds ``n_docs`` documents: each
    embedded and added, so that the next search sees it. (``/v1/statistics``
    counts parsed documents and says so seconds before the first is
    embedded.) The count does not move when a live document is replaced:
    ``probe``, the text of the last document put, is then retrieved until
    it comes back first. Raises ``TimeoutError`` at the deadline."""
    while rag.indexed() != n_docs:
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"the index holds {rag.indexed()} documents, never {n_docs}"
            )
        time.sleep(0.01)
    if probe is None:
        return
    client = Client(rag.port, timeout=60)
    last: Any = None
    try:
        while time.monotonic() < deadline:
            try:
                status, last = client.post("/v1/retrieve", {"query": probe, "k": k})
                if status == 200 and last and last[0]["text"] == probe:
                    return
            except OSError as e:  # the server is still starting
                last = e
                client.close()
            time.sleep(0.05)
    finally:
        client.close()
    raise TimeoutError(f"the last document put never came back first: {last!r}")
