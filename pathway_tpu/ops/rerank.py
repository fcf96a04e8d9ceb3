"""Batched on-device second-stage reranking.

The reference framework reranks retrieval hits ROW-WISE through torch
cross-encoders (`xpacks/llm/rerankers.py` keeps those adapters,
torch-gated). This module is the device-native seat for that stage:
score every (query, candidate) pair of a wave in ONE bucketed XLA
dispatch — [B, C, d] candidate rows against [B, d] queries — through
the DevicePlane's program/bucket compile ledger, exactly the
discipline LLM decode uses (docs/serving.md), so steady-state serving
never recompiles and the ledger stays flat.

The default scorer is the EXACT f32 metric (cos/dot/l2sq) over the
candidates' full-precision rows. That is deliberately honest: against
an IVF-PQ first stage the quality loss is dominated by probe misses
and ADC quantization, and an exact rescore over a WIDER candidate set
(fetched via the adaptive expansion in
`stdlib/indexing/reranking.py`) is what recovers recall — not a
fancier pair function. A custom jax `scorer(q[B,d], cands[B,C,d]) ->
[B,C]` (e.g. a learned cross-encoder head) drops in through the same
bucketed dispatch.

Degradation: three consecutive failures switch to the numpy mirror
(`rerank_scores_host`) for good and say so in the error log — the same
ladder as every other device op in the repo.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

__all__ = [
    "BatchedReranker",
    "rerank_scores_host",
]


def _rerank_scores_fn(q, cands, valid, *, metric: str = "cos"):
    """[B, d] queries x [B, C, d] candidate rows -> [B, C] f32 scores
    (larger is better; invalid slots pinned to -inf)."""
    import jax.numpy as jnp

    q = q.astype(jnp.float32)
    c = cands.astype(jnp.float32)
    if metric in ("cos", "cosine"):
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        c = c / jnp.maximum(jnp.linalg.norm(c, axis=-1, keepdims=True), 1e-12)
        s = jnp.einsum("bd,bcd->bc", q, c, preferred_element_type=jnp.float32)
    elif metric == "l2sq":
        diff = q[:, None, :] - c
        s = -jnp.sum(diff * diff, axis=-1)
    elif metric == "dot":
        s = jnp.einsum("bd,bcd->bc", q, c, preferred_element_type=jnp.float32)
    else:
        raise NotImplementedError(f"rerank metric {metric!r}")
    return jnp.where(valid, s, -jnp.inf)


def rerank_scores_host(
    q: np.ndarray, cands: np.ndarray, valid: np.ndarray, metric: str = "cos"
) -> np.ndarray:
    """Numpy mirror of `_rerank_scores_fn` (degradation path)."""
    q = np.asarray(q, np.float32)
    c = np.asarray(cands, np.float32)
    if metric in ("cos", "cosine"):
        q = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        c = c / np.maximum(np.linalg.norm(c, axis=-1, keepdims=True), 1e-12)
        s = np.einsum("bd,bcd->bc", q, c)
    elif metric == "l2sq":
        diff = q[:, None, :] - c
        s = -np.sum(diff * diff, axis=-1)
    elif metric == "dot":
        s = np.einsum("bd,bcd->bc", q, c)
    else:
        raise NotImplementedError(f"rerank metric {metric!r}")
    return np.where(np.asarray(valid, bool), s, -np.inf).astype(np.float32)


class BatchedReranker:
    """Second-stage pair scorer with bucketed device dispatch.

    `scores(q, cands, valid)` pads B to the plane's row bucket and C to
    the pow2 cap bucket, so distinct wave shapes collapse onto a small
    ladder of compiled programs (one ledger entry per bucket, verified
    flat by the serving tests)."""

    def __init__(
        self,
        metric: str = "cos",
        *,
        device: bool = True,
        scorer: Callable | None = None,
        name: str = "rerank_scores",
    ):
        self.metric = metric if metric != "cosine" else "cos"
        self.name = name
        self._scorer = scorer
        self._use_device = device
        self._failures = 0

    # --------------------------------------------------------------- API

    def scores(
        self, q: np.ndarray, cands: np.ndarray, valid: np.ndarray
    ) -> np.ndarray:
        """[B, d], [B, C, d], [B, C] -> [B, C] f32; -inf on invalid."""
        if self._use_device:
            try:
                out = self._scores_device(q, cands, valid)
                self._failures = 0
                return out
            except Exception as e:  # noqa: BLE001 — transient (OOM…)
                self._failures += 1
                if self._failures >= 3:
                    self._use_device = False
                self._log(e, disabled=not self._use_device)
        if self._scorer is not None:
            raise RuntimeError(
                "custom rerank scorer has no host mirror and the device "
                "path is unavailable"
            )
        return rerank_scores_host(q, cands, valid, self.metric)

    # ------------------------------------------------------------ device

    def _scores_device(self, q, cands, valid) -> np.ndarray:
        import jax.numpy as jnp

        from pathway_tpu.engine.device_plane import get_device_plane

        plane = get_device_plane()
        B, C = valid.shape
        d = q.shape[1]
        if B > plane.buckets.max_rows:
            Bb = B
        else:
            Bb = plane.buckets.rows_bucket(B)
        Cb = plane.buckets.cap_bucket(max(C, 1))
        qp = np.zeros((Bb, d), np.float32)
        qp[:B] = q
        cp = np.zeros((Bb, Cb, d), np.float32)
        cp[:B, :C] = cands
        vp = np.zeros((Bb, Cb), bool)
        vp[:B, :C] = valid
        prog = plane.program(
            self.name,
            self._scorer or _rerank_scores_fn,
            static_argnames=() if self._scorer else ("metric",),
        )
        kwargs = {} if self._scorer else {"metric": self.metric}
        s = prog(
            jnp.asarray(qp),
            jnp.asarray(cp),
            jnp.asarray(vp),
            bucket=(Bb, Cb, d, self.metric),
            **kwargs,
        )
        return np.asarray(s)[:B, :C]

    def _log(self, e: Exception, *, disabled: bool) -> None:
        from pathway_tpu.internals.errors import global_error_log

        global_error_log().log(
            f"device rerank failed ({type(e).__name__}: {e}); "
            + (
                f"device program {self.name!r} DISABLED for this reranker: "
                "the numpy mirror scores every later wave"
                if disabled
                else "served by the numpy mirror, will retry"
            )
        )
