"""On-TPU model zoo backing the LLM xpack.

The reference calls external APIs or local torch pipelines for embeddings and
chat (`/root/reference/python/pathway/xpacks/llm/embedders.py:270`,
`llms.py:441`); model execution is never distributed. Here models are
first-class JAX programs: pytree params with `PartitionSpec` sharding rules,
jit-compiled forward/train steps over a `jax.sharding.Mesh` (dp x tp), and a
decode path with a KV cache for on-TPU generation.
"""

from pathway_tpu.models import transformer  # noqa: F401  (the served decoder)
from pathway_tpu.models.config import (
    LatentSpec,
    LayerSpec,
    TransformerConfig,
    embedder_config,
    lm_config,
)
from pathway_tpu.models.encoder import TransformerLM
from pathway_tpu.models.transformer import count_params

__all__ = [
    "LatentSpec",
    "LayerSpec",
    "TransformerConfig",
    "TransformerLM",
    "count_params",
    "embedder_config",
    "lm_config",
]
