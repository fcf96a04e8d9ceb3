"""VectorStoreServer: plain-callable components, LangChain/LlamaIndex
adapter classmethods (duck-typed, no heavy deps needed for the embedding
path), the slides variant's metadata redaction, and client validation.
Reference: xpacks/llm/vector_store.py:38,92,136,566,629."""

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.xpacks.llm.mocks import fake_embeddings_model
from pathway_tpu.xpacks.llm.vector_store import (
    SlidesVectorStoreServer,
    VectorStoreClient,
    VectorStoreServer,
)

DIM = 12


def _docs():
    return pw.debug.table_from_rows(
        pw.schema_from_types(data=bytes, _metadata=object),
        [
            (b"quick brown fox", {"path": "a.txt", "b64_image": "XXXX"}),
            (b"stream processing engine", {"path": "b.txt", "b64_image": "YYYY"}),
        ],
    )


def _retrieve(server, query="quick brown fox", k=1):
    queries = pw.debug.table_from_rows(
        VectorStoreServer.RetrieveQuerySchema, [(query, k, None, None)]
    )
    df = pw.debug.table_to_pandas(
        server.retrieve_query(queries), include_id=False
    )
    (res,) = [
        r.result.value if hasattr(r.result, "value") else r.result
        for r in df.itertuples()
    ]
    return res


def test_plain_sync_callable_embedder():
    server = VectorStoreServer(
        _docs(), embedder=lambda x: fake_embeddings_model(x, DIM)
    )
    top = _retrieve(server)
    assert top[0]["text"] == "quick brown fox"


def test_plain_async_callable_embedder():
    async def embed(x: str):
        return fake_embeddings_model(x, DIM)

    server = VectorStoreServer(_docs(), embedder=embed)
    top = _retrieve(server, "stream processing engine")
    assert top[0]["text"] == "stream processing engine"


class _FakeLangchainEmbedder:
    """Duck-typed langchain Embeddings: aembed_documents(list) -> list."""

    async def aembed_documents(self, texts):
        return [fake_embeddings_model(t, DIM).tolist() for t in texts]


def test_from_langchain_components_embedding_only():
    server = VectorStoreServer.from_langchain_components(
        _docs(), embedder=_FakeLangchainEmbedder()
    )
    top = _retrieve(server)
    assert top[0]["text"] == "quick brown fox"


class _FakeLlamaEmbedding:
    async def aget_text_embedding(self, text):
        return fake_embeddings_model(text, DIM).tolist()


def test_from_llamaindex_components_embedding_only():
    server = VectorStoreServer.from_llamaindex_components(
        _docs(), transformations=[_FakeLlamaEmbedding()]
    )
    top = _retrieve(server, "stream processing engine")
    assert top[0]["text"] == "stream processing engine"


def test_from_llamaindex_rejects_non_embedder_tail():
    with pytest.raises(ValueError, match="embedding"):
        VectorStoreServer.from_llamaindex_components(
            _docs(), transformations=[object()]
        )
    with pytest.raises(ValueError, match="empty"):
        VectorStoreServer.from_llamaindex_components(_docs(), transformations=[])


def test_slides_server_redacts_metadata():
    server = SlidesVectorStoreServer(
        _docs(), embedder=lambda x: fake_embeddings_model(x, DIM)
    )
    queries = pw.debug.table_from_rows(
        VectorStoreServer.InputsQuerySchema, [(None, None)]
    )
    df = pw.debug.table_to_pandas(
        server.inputs_query(queries), include_id=False
    )
    (res,) = [
        r.result.value if hasattr(r.result, "value") else r.result
        for r in df.itertuples()
    ]
    assert {m["path"] for m in res} == {"a.txt", "b.txt"}
    assert all("b64_image" not in m for m in res)
    # parsed_documents_query mirrors the same listing
    df2 = pw.debug.table_to_pandas(
        server.parsed_documents_query(
            pw.debug.table_from_rows(
                VectorStoreServer.InputsQuerySchema, [(None, None)]
            )
        ),
        include_id=False,
    )
    assert len(df2) == 1


def test_slides_redaction_does_not_mutate_store():
    """Redaction must copy: the listed dicts are the store's live
    metadata objects."""
    server = SlidesVectorStoreServer(
        _docs(), embedder=lambda x: fake_embeddings_model(x, DIM)
    )
    queries = pw.debug.table_from_rows(
        VectorStoreServer.InputsQuerySchema, [(None, None)]
    )
    pw.debug.table_to_pandas(server.inputs_query(queries), include_id=False)
    # list again through the UNREDACTED base listing: images must survive
    df = pw.debug.table_to_pandas(
        server.document_store.inputs_query(
            pw.debug.table_from_rows(
                VectorStoreServer.InputsQuerySchema, [(None, None)]
            )
        ),
        include_id=False,
    )
    (res,) = [
        r.result.value if hasattr(r.result, "value") else r.result
        for r in df.itertuples()
    ]
    assert all("b64_image" in m for m in res)


def test_slides_redaction_served_over_rest():
    """run_server must register the SUBCLASS endpoints — the redacted
    inputs listing is what REST clients get."""
    import threading
    import time

    from conftest import free_port_base

    port = free_port_base()
    server = SlidesVectorStoreServer(
        _docs(), embedder=lambda x: fake_embeddings_model(x, DIM)
    )
    threading.Thread(
        target=lambda: server.run_server(
            host="127.0.0.1", port=port, with_cache=False
        ),
        daemon=True,
    ).start()
    client = VectorStoreClient(host="127.0.0.1", port=port, timeout=5)
    files = None
    for _ in range(60):
        time.sleep(0.25)
        try:
            files = client.get_input_files()
            break
        except Exception:
            continue
    assert files is not None, "server did not come up"
    assert {m["path"] for m in files} == {"a.txt", "b.txt"}
    assert all("b64_image" not in m for m in files)


def test_async_splitter_rejected_early():
    async def split(text):
        return [(text, {})]

    with pytest.raises(ValueError, match="synchronous"):
        VectorStoreServer(
            _docs(),
            embedder=lambda x: fake_embeddings_model(x, DIM),
            splitter=split,
        )


def test_embedding_dimension_probe():
    server = VectorStoreServer(
        _docs(), embedder=lambda x: np.zeros(7, np.float32)
    )
    assert server.embedder.get_embedding_dimension() == 7


def test_client_arg_validation():
    with pytest.raises(ValueError):
        VectorStoreClient(host="h", port=1, url="http://x")
    with pytest.raises(ValueError):
        VectorStoreClient()
    c = VectorStoreClient(url="http://example:123", additional_headers={"X-K": "v"})
    assert c.url == "http://example:123"
    assert c.additional_headers == {"X-K": "v"}
    # default port matches run_server's 8000
    assert VectorStoreClient(host="h").url == "http://h:8000"
