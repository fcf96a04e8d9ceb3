"""pathway_tpu.indexing — device-native approximate-nearest-neighbor
indexes maintained incrementally under the zset contract.

The stdlib index layer (`pathway_tpu/stdlib/indexing/`) owns the
dataflow-facing retriever API; this package owns the mutable index
*structures* that scale past the brute-force slab: today the IVF-PQ
index (`ann.py`), built on the kernels in `pathway_tpu/ops/ivf.py`.

Kill switch: ``PATHWAY_ANN=0`` forces every ANN-configured retriever
back to the exact slab search (byte-identical ranking semantics —
same (score, key) tie-break), the same discipline as
``PATHWAY_ITERATE_NATIVE``. ``PATHWAY_ANN=1`` additionally flips
opt-in call sites (``make_knn_searcher``) whose default is exact.
"""

from __future__ import annotations

import os

# Re-export the whole stdlib index layer: `pw.indexing` is bound to
# pathway_tpu.stdlib.indexing in the package root, but importing THIS
# subpackage rebinds the attribute to this module (python sets the
# submodule attribute on its parent). With the re-export the rebind is
# harmless — pw.indexing stays the full index surface either way.
from pathway_tpu.stdlib.indexing import *  # noqa: F401,F403
from pathway_tpu.stdlib.indexing import __all__ as _stdlib_all
from pathway_tpu.stdlib.indexing import (  # noqa: F401 — engine-layer names
    _INDEX_REPLY,
    _INDEX_REPLY_ID,
    _INDEX_REPLY_SCORE,
    _MATCHED_ID,
    _SCORE,
)

from pathway_tpu.indexing.ann import IvfPqIndex
from pathway_tpu.indexing.tiers import (  # noqa: F401
    TIER_COLD,
    TIER_HOT,
    TIER_NAMES,
    TIER_WARM,
    TierState,
    tiered_enabled,
    verify_tier_state,
)

__all__ = [
    "IvfPqIndex",
    "ann_enabled",
    "tiered_enabled",
    "TierState",
    "TIER_HOT",
    "TIER_WARM",
    "TIER_COLD",
    "TIER_NAMES",
    "verify_tier_state",
    *_stdlib_all,
]


def ann_enabled(default: bool = True) -> bool:
    """The PATHWAY_ANN kill switch. `default` is what the call site
    wants when the env var is unset: an explicitly ANN-configured
    retriever passes True (env can only veto), an exact-by-default path
    like `make_knn_searcher` passes False (env can opt in)."""
    v = os.environ.get("PATHWAY_ANN")
    if v is None:
        return default
    return v.strip().lower() not in ("0", "false", "")
