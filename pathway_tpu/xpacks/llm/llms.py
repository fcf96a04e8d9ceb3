"""Chat wrappers — LLMs as UDFs on tables.

Reference parity: xpacks/llm/llms.py — `BaseChat` (:27), `OpenAIChat` (:84),
`LiteLLMChat` (:313), `HFPipelineChat` (:441), `CohereChat` (:544). Each is a
`pw.UDF` whose async `__wrapped__` calls the provider; capacity/retry/cache
come from the UDF executor machinery.

TPU addition: `JaxLMChat` runs generation on-TPU with the framework's own
causal transformer (`pathway_tpu.models.transformer`) — the local-model path
the reference delegates to HF torch pipelines.
"""

from __future__ import annotations

import weakref
from typing import Any

import pathway_tpu as pw
from pathway_tpu.internals import udfs
from pathway_tpu.internals.expression import ColumnExpression
from pathway_tpu.internals.json import Json


def _prep_message_log(messages: Any, verbose: bool) -> str:
    if verbose:
        return repr(messages)
    return repr(messages)[:500]


def prompt_chat_single_qa(question: str) -> Json:
    """Wrap a plain question into the single-turn chat message format."""
    return Json([{"role": "user", "content": question}])


class BaseChat(pw.UDF):
    """Common chat surface: __wrapped__(messages, **kwargs) -> str."""

    kwargs: dict[str, Any]

    def __init__(
        self,
        *,
        capacity: int | None = None,
        retry_strategy: udfs.AsyncRetryStrategy | None = None,
        cache_strategy: udfs.CacheStrategy | None = None,
        **chat_kwargs: Any,
    ):
        executor = udfs.async_executor(
            capacity=capacity, retry_strategy=retry_strategy
        )
        super().__init__(executor=executor, cache_strategy=cache_strategy)
        self.kwargs = dict(chat_kwargs)

    def _accepts_call_arg(self, arg_name: str) -> bool:
        return True

    def __call__(self, messages: ColumnExpression, **kwargs: Any) -> ColumnExpression:
        return super().__call__(messages, **kwargs)


class OpenAIChat(BaseChat):
    """OpenAI chat-completions (reference: llms.py:84). Requires the
    `openai` package and network access; construction fails fast otherwise."""

    def __init__(self, model: str | None = "gpt-4o-mini", **kwargs: Any):
        super().__init__(**kwargs)
        self.kwargs["model"] = model
        try:
            import openai
        except ImportError as e:
            raise ImportError(
                "OpenAIChat requires the `openai` package; use JaxLMChat for "
                "on-TPU generation or mocks.FakeChatModel in tests"
            ) from e
        self.client = openai.AsyncOpenAI()  # shared pool across rows

    async def __wrapped__(self, messages: Any, **kwargs: Any) -> str | None:
        msgs = messages.value if isinstance(messages, Json) else messages
        merged = {**self.kwargs, **kwargs}
        ret = await self.client.chat.completions.create(messages=msgs, **merged)
        return ret.choices[0].message.content


class LiteLLMChat(BaseChat):
    """LiteLLM multi-provider chat (reference: llms.py:313)."""

    def __init__(self, model: str | None = None, **kwargs: Any):
        super().__init__(**kwargs)
        self.kwargs["model"] = model
        try:
            import litellm  # noqa: F401
        except ImportError as e:
            raise ImportError("LiteLLMChat requires the `litellm` package") from e

    async def __wrapped__(self, messages: Any, **kwargs: Any) -> str | None:
        import litellm

        msgs = messages.value if isinstance(messages, Json) else messages
        merged = {**self.kwargs, **kwargs}
        ret = await litellm.acompletion(messages=msgs, **merged)
        return ret.choices[0].message.content


class CohereChat(BaseChat):
    """Cohere chat with citations (reference: llms.py:544)."""

    def __init__(self, model: str | None = "command", **kwargs: Any):
        super().__init__(**kwargs)
        self.kwargs["model"] = model
        try:
            import cohere
        except ImportError as e:
            raise ImportError("CohereChat requires the `cohere` package") from e
        self.client = cohere.AsyncClient()  # shared pool across rows

    async def __wrapped__(
        self, messages: Any, documents: Any = None, **kwargs: Any
    ) -> tuple:
        msgs = messages.value if isinstance(messages, Json) else messages
        client = self.client
        merged = {**self.kwargs, **kwargs}
        docs = (
            [d.value if isinstance(d, Json) else d for d in documents]
            if documents
            else None
        )
        message = msgs[-1]["content"]
        chat_history = msgs[:-1]
        ret = await client.chat(
            message=message, chat_history=chat_history, documents=docs, **merged
        )
        cited = [
            {"text": c.text, "start": c.start, "end": c.end}
            for c in (ret.citations or [])
        ]
        return ret.text, cited


class HFPipelineChat(BaseChat):
    """Local HuggingFace text-generation pipeline (reference: llms.py:441).

    Runs on CPU torch in this image; prefer JaxLMChat for the TPU path.
    """

    def __init__(
        self,
        model: str | None = "gpt2",
        call_kwargs: dict | None = None,
        device: str = "cpu",
        **kwargs: Any,
    ):
        super().__init__(**kwargs)
        try:
            from transformers import pipeline
        except ImportError as e:
            raise ImportError("HFPipelineChat requires `transformers`") from e
        self.pipeline = pipeline("text-generation", model=model, device=device)
        self.tokenizer = self.pipeline.tokenizer
        self.call_kwargs = call_kwargs or {}

    def crop_to_max_length(self, input_string: str, max_prompt_length: int = 500) -> str:
        tokens = self.tokenizer.tokenize(input_string)
        if len(tokens) > max_prompt_length:
            tokens = tokens[-max_prompt_length:]
        return self.tokenizer.convert_tokens_to_string(tokens)

    def __wrapped__(self, messages: Any, **kwargs: Any) -> str | None:
        msgs = messages.value if isinstance(messages, Json) else messages
        if isinstance(msgs, list):
            prompt = "\n".join(m["content"] for m in msgs)
        else:
            prompt = str(msgs)
        merged = {**self.call_kwargs, **kwargs}
        merged.setdefault("max_new_tokens", 64)
        merged.setdefault("return_full_text", False)
        out = self.pipeline(prompt, **merged)
        return out[0]["generated_text"]


class JaxLMChat(BaseChat):
    """On-TPU generation with the framework's causal transformer.

    The reference has no analog — its local path is a torch HF pipeline
    (llms.py:441). Here the model is a JAX program: batched prefill + scanned
    decode with a KV cache (models/transformer.py), jit-compiled once.
    Pass trained `params`, or leave None for random weights (testing).

    Dispatch model, chosen by ``temperature`` alone: at 0, **continuous
    batching** — requests join an in-flight decode batch at step
    boundaries through the slot scheduler
    (serving/continuous_batching.py), so a request arriving
    mid-generation never waits for the whole wave to drain; above 0, the
    wave-aligned coalescer: one left-padded generate dispatch per wave
    (the batcher does not sample). Only the chosen scheduler is built.
    ``_generate_batch`` is callable on any chat and makes its program on
    first use: at temperature 0 its output is byte-identical to the
    batcher's, which is what the tests hold the batcher to.
    """

    def __init__(
        self,
        config: Any = None,
        params: Any = None,
        tokenizer: Any = None,
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        max_batch: int = 64,
        decode_slots: int = 8,
        **kwargs: Any,
    ):
        super().__init__(**kwargs)
        import jax

        from pathway_tpu.engine.device_plane import get_device_plane
        from pathway_tpu.models import lm_config, transformer
        from pathway_tpu.models.tokenizer import HashTokenizer
        from pathway_tpu.serving.continuous_batching import ContinuousBatcher

        self.config = config or lm_config(
            vocab_size=32768, d_model=256, n_heads=8, n_layers=4, d_ff=1024,
            max_len=512,
        )
        if params is None:
            params = transformer.init_params(jax.random.PRNGKey(0), self.config)
        self.params = params
        self.tokenizer = tokenizer or HashTokenizer(
            vocab_size=self.config.vocab_size, max_len=self.config.max_len
        )
        if max_new_tokens >= self.config.max_len:
            raise ValueError(
                f"max_new_tokens ({max_new_tokens}) must be smaller than the "
                f"model context length ({self.config.max_len})"
            )
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.max_batch = max_batch
        self._plane = get_device_plane()
        # the wave-aligned program: its name is reserved now, for the
        # finalizer, and the first `_generate_batch` registers it
        self._gen_name = self._plane.unique_name("lm_generate")
        self._gen: Any = None
        self._cb: ContinuousBatcher | None = None
        self._batcher: Any = None
        if self.temperature == 0.0:
            # slot-scheduled decode: requests join at step boundaries
            self._cb = ContinuousBatcher(
                params=self.params,
                cfg=self.config,
                tokenizer=self.tokenizer,
                n_steps=self.max_new_tokens,
                n_slots=decode_slots,
                plane=self._plane,
            )
        else:
            # a wave of concurrent chat calls left-pads into ONE generate
            # dispatch (prompt_mask keeps per-row outputs equal to
            # unpadded runs); per-question dispatch would serialize on
            # host->device submission latency
            self._batcher = self._plane.coalescer(
                self._generate_batch, max_batch=max_batch
            )
        # the plane is process-global: without this, every dead chat
        # instance would pin its compiled program + KV-cache pools forever
        self._finalizer = weakref.finalize(
            self, _release_chat_programs, self._plane, self._gen_name,
            self._cb.name if self._cb is not None else None,
        )

    def _generate_program(self) -> Any:
        """The wave-aligned `generate_serving` program, registered by the
        first `_generate_batch` (register-or-get: racing first calls are
        handed one program). Its KV cache is a PERSISTENT donated buffer
        per row bucket (device_plane lease): XLA reuses the allocation
        across dispatches instead of re-allocating the cache every call."""
        import functools

        from pathway_tpu.models import transformer

        if self._gen is None:
            self._gen = self._plane.program(
                self._gen_name,
                functools.partial(
                    transformer.generate_serving,
                    n_steps=self.max_new_tokens,
                    cfg=self.config,
                    temperature=self.temperature,
                ),
                donate_argnums=(2,),  # the KV cache rides the lease cycle
            )
        return self._gen

    def _generate_batch(self, prompts: list[str]) -> list[str]:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from pathway_tpu.engine.device_plane import pad_left_rows
        from pathway_tpu.models import transformer

        gen = self._generate_program()
        budget = self.config.max_len - self.max_new_tokens
        rows = [self.tokenizer.tokenize(p)[-budget:] for p in prompts]
        n = min(self._plane.buckets.rows_bucket(len(rows)), self.max_batch)
        n = max(n, len(rows))
        ids, mask = pad_left_rows(rows, budget, n_rows=n)
        bucket = ids.shape[1]
        kwargs = {}
        if self.temperature > 0.0:
            kwargs["rng"] = jax.random.PRNGKey(abs(hash(tuple(prompts))) % (1 << 31))
        cache_key = ("lm_kv_cache", gen.name, n)
        cache = self._plane.lease(
            cache_key, lambda: transformer.init_kv_cache(self.config, n)
        )
        out, cache = gen(
            self.params, jnp.asarray(ids), cache,
            prompt_mask=jnp.asarray(mask),
            bucket=(n, bucket), **kwargs,
        )
        self._plane.restore(cache_key, cache)
        out = np.asarray(out)
        return [
            " ".join(f"<{int(t)}>" for t in out[i, bucket:])
            for i in range(len(rows))
        ]

    async def __wrapped__(self, messages: Any, **kwargs: Any) -> str:
        import asyncio

        msgs = messages.value if isinstance(messages, Json) else messages
        if isinstance(msgs, list):
            prompt = "\n".join(m["content"] for m in msgs)
        else:
            prompt = str(msgs)
        if self._cb is not None:
            return await asyncio.wrap_future(self._cb.submit(prompt))
        return await self._batcher.submit(prompt)


def _release_chat_programs(plane: Any, gen_name: str, cb_name: str | None) -> None:
    """Finalizer body for JaxLMChat: module-level so the weakref holds no
    bound method back-reference to the instance."""
    plane.drop_program(gen_name)
    if cb_name is not None:
        plane.drop_namespace(cb_name)
