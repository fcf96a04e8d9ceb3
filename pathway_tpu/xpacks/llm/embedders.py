"""Embedders — text -> vector UDFs.

Reference parity: xpacks/llm/embedders.py — `BaseEmbedder` (:64),
`OpenAIEmbedder` (:85), `LiteLLMEmbedder` (:180),
`SentenceTransformerEmbedder` (:270, row-wise torch — the bottleneck the
north-star targets), `GeminiEmbedder` (:330).

TPU flagship: `JaxEmbedder` — the framework's own transformer encoder with a
microbatching async front: every concurrently in-flight call in a wave lands
in one device batch, so the engine's async-apply operator (which gathers a
wave's rows into one asyncio.gather) drives the TPU at full batch size
instead of row-at-a-time.
"""

from __future__ import annotations

import asyncio
import weakref
from typing import Any

import numpy as np

import pathway_tpu as pw
from pathway_tpu.internals import observability as _obs
from pathway_tpu.internals import udfs
from pathway_tpu.internals.expression import ColumnExpression
from pathway_tpu.xpacks.llm._utils import _coerce_sync


class BaseEmbedder(pw.UDF):
    def get_embedding_dimension(self, **kwargs: Any) -> int:
        return len(_coerce_sync(self.__wrapped__)(".", **kwargs))

    def __call__(self, input: ColumnExpression, *args: Any, **kwargs: Any) -> ColumnExpression:
        return super().__call__(input, *args, **kwargs)


class OpenAIEmbedder(BaseEmbedder):
    """OpenAI embeddings API (reference: embedders.py:85)."""

    def __init__(
        self,
        *,
        capacity: int | None = None,
        retry_strategy: udfs.AsyncRetryStrategy | None = None,
        cache_strategy: udfs.CacheStrategy | None = None,
        model: str | None = "text-embedding-3-small",
        **openai_kwargs: Any,
    ):
        executor = udfs.async_executor(capacity=capacity, retry_strategy=retry_strategy)
        super().__init__(executor=executor, cache_strategy=cache_strategy)
        try:
            import openai
        except ImportError as e:
            raise ImportError(
                "OpenAIEmbedder requires `openai`; use JaxEmbedder for the "
                "on-TPU path"
            ) from e
        self.kwargs = {"model": model, **openai_kwargs}
        # one client for all rows — connection pooling matters on the
        # hottest path of the pipeline
        self.client = openai.AsyncOpenAI()

    async def __wrapped__(self, input: str, **kwargs: Any) -> np.ndarray:
        merged = {**self.kwargs, **kwargs}
        ret = await self.client.embeddings.create(input=[input or "."], **merged)
        return np.array(ret.data[0].embedding)


class LiteLLMEmbedder(BaseEmbedder):
    """LiteLLM embeddings (reference: embedders.py:180)."""

    def __init__(
        self,
        *,
        capacity: int | None = None,
        retry_strategy: udfs.AsyncRetryStrategy | None = None,
        cache_strategy: udfs.CacheStrategy | None = None,
        model: str | None = None,
        **kwargs: Any,
    ):
        executor = udfs.async_executor(capacity=capacity, retry_strategy=retry_strategy)
        super().__init__(executor=executor, cache_strategy=cache_strategy)
        try:
            import litellm  # noqa: F401
        except ImportError as e:
            raise ImportError("LiteLLMEmbedder requires `litellm`") from e
        self.kwargs = {"model": model, **kwargs}

    async def __wrapped__(self, input: str, **kwargs: Any) -> np.ndarray:
        import litellm

        merged = {**self.kwargs, **kwargs}
        ret = await litellm.aembedding(input=[input or "."], **merged)
        return np.array(ret.data[0]["embedding"])


class GeminiEmbedder(BaseEmbedder):
    """Google Gemini embeddings (reference: embedders.py:330)."""

    def __init__(
        self,
        *,
        capacity: int | None = None,
        retry_strategy: udfs.AsyncRetryStrategy | None = None,
        cache_strategy: udfs.CacheStrategy | None = None,
        model: str | None = "models/embedding-001",
        **kwargs: Any,
    ):
        executor = udfs.async_executor(capacity=capacity, retry_strategy=retry_strategy)
        super().__init__(executor=executor, cache_strategy=cache_strategy)
        try:
            import google.generativeai as genai  # noqa: F401
        except ImportError as e:
            raise ImportError("GeminiEmbedder requires `google-generativeai`") from e
        self.kwargs = {"model": model, **kwargs}

    def __wrapped__(self, input: str, **kwargs: Any) -> np.ndarray:
        import google.generativeai as genai

        merged = {**self.kwargs, **kwargs}
        ret = genai.embed_content(content=input or ".", **merged)
        return np.array(ret["embedding"])


class SentenceTransformerEmbedder(BaseEmbedder):
    """Local sentence-transformers torch model, row-wise
    (reference: embedders.py:270). Kept for drop-in compatibility; the TPU
    path is JaxEmbedder."""

    def __init__(
        self,
        model: str,
        call_kwargs: dict = {},
        device: str = "cpu",
        **init_kwargs: Any,
    ):
        super().__init__()
        try:
            from sentence_transformers import SentenceTransformer
        except ImportError as e:
            raise ImportError(
                "SentenceTransformerEmbedder requires `sentence_transformers`; "
                "use JaxEmbedder for the on-TPU path"
            ) from e
        self.model = SentenceTransformer(model, device=device, **init_kwargs)
        self.kwargs = dict(call_kwargs)

    def __wrapped__(self, text: str, **kwargs: Any) -> np.ndarray:
        merged = {**self.kwargs, **kwargs}
        return self.model.encode(text or ".", **merged)


# The wave batcher moved into the device plane: coalescing is a serving
# concern shared by every XLA-backed stage (embed, generate, batched
# UDFs). Kept under its historical name — callers (and the bench's phase
# probes) patch `<udf>._batcher.flush_fn`. The padding rules live beside
# the ladder they pad to (BucketPolicy) and are re-exported here.
from pathway_tpu.engine.device_plane import (  # noqa: E402
    WaveCoalescer as _MicroBatcher,
    bucket_len,
    get_device_plane,
    pad_left_rows,
)


class JaxEmbedder(BaseEmbedder):
    """The TPU-native embedder: hash tokenizer + the flagship JAX encoder.

    Replaces the reference's per-row torch SentenceTransformer call
    (embedders.py:270) with wave-batched XLA encoding. Pass trained `params`
    for a real model; defaults give a deterministic random-weight encoder
    (useful for pipelines and tests — similarity structure still follows
    token overlap thanks to mean pooling).
    """

    def __init__(
        self,
        config: Any = None,
        params: Any = None,
        tokenizer: Any = None,
        *,
        max_batch: int = 4096,
        cache_strategy: udfs.CacheStrategy | None = None,
    ):
        super().__init__(
            executor=udfs.async_executor(), cache_strategy=cache_strategy
        )
        import functools

        import jax

        from pathway_tpu.models import embedder_config, encoder, transformer
        from pathway_tpu.models.tokenizer import HashTokenizer

        self.config = config or embedder_config(
            vocab_size=32768, d_model=256, n_heads=8, n_layers=4, d_ff=1024,
            max_len=128, embed_dim=256,
        )
        if params is None:
            params = transformer.init_params(jax.random.PRNGKey(0), self.config)
        # serving keeps bf16-resident params (half the HBM weight reads;
        # no per-matmul casts inside the jitted program)
        self.params = jax.device_put(
            transformer.cast_params(params, self.config.dtype)
        )
        self.tokenizer = tokenizer or HashTokenizer(
            vocab_size=self.config.vocab_size, max_len=self.config.max_len
        )
        # the device plane owns the dispatch: bucketed shapes, compile
        # ledger, off-loop flushes (a slow generate elsewhere never
        # blocks this embedder's coalescer)
        self._plane = get_device_plane()
        self._encode = self._plane.program(
            self._plane.unique_name("embed_encode"),
            functools.partial(encoder.encode, cfg=self.config),
        )
        self._batcher = self._plane.coalescer(
            self._encode_batch, max_batch=max_batch
        )
        # release the per-instance program when this embedder dies — the
        # plane is process-global and would otherwise pin it forever
        self._finalizer = weakref.finalize(
            self, self._plane.drop_program, self._encode.name
        )

    def _encode_batch(self, texts: list[str]) -> list[np.ndarray]:
        import jax.numpy as jnp

        with _obs.span(_obs.SPAN_EMBED_ENCODE_BATCH, rows=len(texts)):
            ids, mask = self.tokenizer.batch([t or "." for t in texts])
            # pad rows + seq up to the plane's buckets: ragged live
            # waves hit a bounded set of XLA programs
            (ids, mask), rows = self._plane.pad_rows(
                [ids, mask], ids.shape[0]
            )
            seq = ids.shape[1]
            bucket = bucket_len(seq, self.config.max_len)
            if bucket != seq:
                ids = np.pad(ids, ((0, 0), (0, bucket - seq)))
                mask = np.pad(mask, ((0, 0), (0, bucket - seq)))
            out = np.asarray(
                self._encode(
                    self.params, jnp.asarray(ids), jnp.asarray(mask),
                    bucket=(rows, bucket),
                )
            )
        return [out[i] for i in range(len(texts))]

    async def __wrapped__(self, input: str, **kwargs: Any) -> np.ndarray:
        out = await self._batcher.submit(input)
        # a REST request's query: its clock's `embed` ends here
        _obs.stamp_current(_obs.STAGE_EMBED)
        return out

    def encode_many(self, texts: list[str]) -> list[np.ndarray]:
        """Synchronous bulk encode (used by rerankers and tests)."""
        return self._encode_batch(texts)
