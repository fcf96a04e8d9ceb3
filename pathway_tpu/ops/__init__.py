"""TPU numeric plane — jit-compiled XLA kernels used across the framework.

This is the layer the reference implements with per-row ndarray math
(`/root/reference/src/mat_mul.rs:5`, `stdlib/ml/classifiers/_knn_lsh.py:50-57`)
and external C index libraries (`src/external_integration/`). Here the numeric
hot paths are batched XLA programs designed for the MXU: large bf16 matmuls,
fused distance + top-k, segment reductions, and sharded variants that ride the
ICI via `shard_map` collectives.
"""

from pathway_tpu.ops.distances import (
    cosine_distances,
    dot_products,
    l2_distances,
    normalize,
)
from pathway_tpu.ops.ivf import (
    IvfPqArrays,
    build_ivf_pq,
    ivf_pq_search,
)
from pathway_tpu.ops.topk import (
    TopKResult,
    knn_search,
    knn_search_sharded,
    make_knn_searcher,
)
from pathway_tpu.ops.segment import segment_reduce

__all__ = [
    "cosine_distances",
    "dot_products",
    "l2_distances",
    "normalize",
    "IvfPqArrays",
    "build_ivf_pq",
    "ivf_pq_search",
    "TopKResult",
    "knn_search",
    "knn_search_sharded",
    "make_knn_searcher",
    "segment_reduce",
]
