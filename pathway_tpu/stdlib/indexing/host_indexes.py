"""Mutable index structures answering top-k queries — the TPU replacements
for the reference's external index libraries.

Reference parity: `ExternalIndex` trait (add/remove/search) in
src/external_integration/mod.rs:40 with implementations USearchKNNIndex
(HNSW, usearch_integration.rs:20), BruteForceKNNIndex
(brute_force_knn_integration.rs:22) and TantivyIndex BM25
(tantivy_integration.rs:16), wrapped by the JMESPath-filtering
DerivedFilteredSearchIndex (mod.rs:373).

TPU-first redesign: vector search keeps ONE growable row-slab of vectors.
The hot copy lives in HBM as a pre-normalized bf16 matrix with a validity
mask; queries are batched into a single fused matmul + top-k XLA program
(`pathway_tpu.ops.knn_search_masked`). Deletions tombstone the mask (no HNSW
graph surgery); growth doubles capacity and re-device-puts — O(n) but
amortized, and 1M x 256 bf16 is only 512 MB of HBM. The "approximate" mode
maps to `lax.approx_max_k` rather than an HNSW graph: on the MXU the exact
scan is already faster than pointer chasing, approx only trims the top-k
phase. Metadata-filtered queries fall back to a host numpy scan over the
filtered candidate set (filters select small subsets in practice).
"""

from __future__ import annotations

import math
from collections import defaultdict
from typing import Any, Callable

import numpy as np

from pathway_tpu.internals import observability as _obs
from pathway_tpu.internals.keys import Key
from pathway_tpu.stdlib.indexing.filters import compile_filter

Matches = list[tuple[Key, float]]


class HostIndex:
    """Protocol: add/remove/search. `search` returns [(key, score)]."""

    def add(self, key: Key, data: Any, metadata: Any = None) -> None:
        raise NotImplementedError

    def remove(self, key: Key) -> None:
        raise NotImplementedError

    def search(self, query: Any, k: int, metadata_filter: str | None = None) -> Matches:
        raise NotImplementedError


def _as_vector(data: Any) -> np.ndarray:
    if isinstance(data, np.ndarray):
        return data.astype(np.float32).ravel()
    return np.asarray(data, dtype=np.float32).ravel()


class _FilterCache:
    def __init__(self) -> None:
        self._cache: dict[str, Callable[[Any], bool]] = {}

    def __reduce__(self):
        # compiled predicates are closures; rebuild lazily after unpickle
        # (operator-snapshot persistence pickles whole host indexes)
        return (_FilterCache, ())

    def get(self, expression: str) -> Callable[[Any], bool]:
        fn = self._cache.get(expression)
        if fn is None:
            fn = self._cache[expression] = compile_filter(expression)
        return fn


class VectorSlabIndex(HostIndex):
    """Growable vector slab with an HBM-resident bf16 mirror.

    Both the brute-force and "usearch-equivalent" KNN indexes are this class;
    `approx` selects `lax.approx_max_k` for the top-k phase.
    """

    # consecutive failed device searches; a class default so that indexes
    # unpickled from older operator snapshots have it too
    _device_failures = 0

    def __init__(
        self,
        dimensions: int | None = None,
        reserved_space: int = 1024,
        metric: str = "cos",
        approx: bool = False,
        device: bool = True,
    ):
        self.dim = dimensions
        self.metric = metric
        self.approx = approx
        self.use_device = device
        self.capacity = max(64, reserved_space)
        self.vectors: np.ndarray | None = None  # [capacity, dim] f32
        self.valid = np.zeros(self.capacity, dtype=bool)
        self.slot_of: dict[Key, int] = {}
        self.key_of: dict[int, Key] = {}
        self.metadata: dict[Key, Any] = {}
        self.free: list[int] = []
        self.n_slots = 0  # high-water mark
        self._device_dirty = True
        self._device_docs = None
        self._device_valid = None
        # slots whose vector/validity changed since the last mirror sync:
        # small deltas scatter into the PERSISTENT device slab via a
        # donated update program instead of re-uploading the whole mirror
        self._dirty_slots: set[int] = set()
        self._filters = _FilterCache()

    def __getstate__(self):
        # device mirrors are rebuilt lazily on first search after unpickle
        st = dict(self.__dict__)
        st["_device_docs"] = None
        st["_device_valid"] = None
        st["_device_dirty"] = True
        st["_dirty_slots"] = set()  # no mirror to patch: full rebuild
        return st

    # ------------------------------------------------------------- mutation

    def _ensure_storage(self, dim: int) -> None:
        if self.vectors is None:
            self.dim = self.dim or dim
            if dim != self.dim:
                raise ValueError(f"vector dim {dim} != index dim {self.dim}")
            self.vectors = np.zeros((self.capacity, self.dim), np.float32)

    def _grow(self) -> None:
        self.capacity *= 2
        new = np.zeros((self.capacity, self.dim), np.float32)
        new[: self.vectors.shape[0]] = self.vectors
        self.vectors = new
        nv = np.zeros(self.capacity, dtype=bool)
        nv[: self.valid.shape[0]] = self.valid
        self.valid = nv

    def add(self, key: Key, data: Any, metadata: Any = None) -> None:
        vec = _as_vector(data)
        self._ensure_storage(vec.shape[0])
        if vec.shape[0] != self.dim:
            raise ValueError(f"vector dim {vec.shape[0]} != index dim {self.dim}")
        if self.metric in ("cos", "cosine"):
            norm = float(np.linalg.norm(vec))
            if norm > 0:
                vec = vec / norm
        old_slot = self.slot_of.get(key)
        if old_slot is not None:
            self.vectors[old_slot] = vec
        else:
            if self.free:
                slot = self.free.pop()
            else:
                if self.n_slots >= self.capacity:
                    self._grow()
                slot = self.n_slots
                self.n_slots += 1
            self.vectors[slot] = vec
            self.valid[slot] = True
            self.slot_of[key] = slot
            self.key_of[slot] = key
            old_slot = slot
        self.metadata[key] = metadata
        self._device_dirty = True
        self._dirty_slots.add(old_slot)

    def remove(self, key: Key) -> None:
        slot = self.slot_of.pop(key, None)
        if slot is None:
            return
        self.valid[slot] = False
        del self.key_of[slot]
        self.metadata.pop(key, None)
        self.free.append(slot)
        self._device_dirty = True
        self._dirty_slots.add(slot)

    def __len__(self) -> int:
        return len(self.slot_of)

    # -------------------------------------------------------------- search

    def _refresh_device(self) -> None:
        """Sync the persistent device mirror with host state.

        Small deltas (the streaming steady state: a few upserts per wave)
        scatter into the EXISTING slab through a donated device program —
        the [n, d] allocation is reused in place, and the host->device
        payload is just the changed rows. The mirror is rebuilt wholesale
        only when the padded slot bucket grew or most rows changed.
        """
        import jax
        import jax.numpy as jnp

        from pathway_tpu.engine.device_plane import get_device_plane

        plane = get_device_plane()
        padded = self._padded_slots()
        incremental = (
            self._device_docs is not None
            and int(self._device_docs.shape[0]) == padded
            and self._dirty_slots
            and len(self._dirty_slots) <= padded // 2
        )
        if incremental:
            prog = plane.program(
                "knn_slab_update",
                lambda docs, valid, idx, rows, vbits: (
                    docs.at[idx].set(rows), valid.at[idx].set(vbits)
                ),
                donate_argnums=(0, 1),  # patch the slab in place
            )
            idx = np.fromiter(self._dirty_slots, np.int32)
            # pad the update batch to a power-of-two bucket by REPEATING
            # the first entry: duplicate scatter indices write the same
            # value, so padding is idempotent and the jit cache sees a
            # bounded set of update shapes
            ub = plane.buckets.rows_bucket(min(len(idx), plane.buckets.max_rows))
            if len(idx) > ub:  # huge delta past the cap: rebuild instead
                incremental = False
            else:
                idx = np.concatenate([idx, np.full(ub - len(idx), idx[0], np.int32)])
                rows = self.vectors[idx]
                vbits = self.valid[idx]
                try:
                    self._device_docs, self._device_valid = prog(
                        self._device_docs,
                        self._device_valid,
                        jnp.asarray(idx),
                        jnp.asarray(rows, jnp.bfloat16),
                        jnp.asarray(vbits),
                        # dim in the key: the program is shared plane-wide,
                        # and indexes of different dims compile separately
                        bucket=(padded, ub, self.dim),
                    )
                except Exception:
                    # donation already consumed the old slab — drop the
                    # mirror so the next refresh rebuilds from host state
                    # instead of touching a deleted buffer
                    self._device_docs = self._device_valid = None
                    raise
        if not incremental:
            docs = self.vectors[:padded]
            self._device_docs = jax.device_put(jnp.asarray(docs, jnp.bfloat16))
            self._device_valid = jax.device_put(jnp.asarray(self.valid[:padded]))
        self._dirty_slots.clear()
        self._device_dirty = False

    def _padded_slots(self) -> int:
        # pad the live row count to a power of two so the jit cache sees a
        # handful of shapes as the index grows, not one shape per size
        n = max(self.n_slots, 64)
        return min(self.capacity, 1 << math.ceil(math.log2(n)))

    def search(self, query: Any, k: int, metadata_filter: str | None = None) -> Matches:
        return self.search_batch([(query, k, metadata_filter)])[0]

    def search_batch(self, items: list[tuple[Any, int, str | None]]) -> list[Matches]:
        if not self.slot_of:
            return [[] for _ in items]
        plain = [(i, q, k) for i, (q, k, f) in enumerate(items) if not f]
        filtered = [(i, q, k, f) for i, (q, k, f) in enumerate(items) if f]
        results: list[Matches] = [[] for _ in items]
        if plain:
            kmax = max(k for _i, _q, k in plain)
            qmat = np.stack([_as_vector(q) for _i, q, _k in plain])
            # candidates are re-ranked by (score, key) below so equal-score
            # results never depend on index insertion order (worker-count
            # invariance). The host path returns all k-th-boundary ties
            # (exact); the device path over-fetches a headroom instead —
            # sufficient unless >8 keys tie at the boundary, which for
            # real-valued embedding scores is a measure-zero event.
            top = self._topk(qmat, min(kmax + 8, len(self.slot_of)))
            for (i, _q, k), (idxs, dists) in zip(plain, top):
                matches = [
                    (self.key_of[slot], float(d))
                    for slot, d in zip(idxs, dists)
                    if slot in self.key_of
                ]
                matches.sort(key=lambda m: (m[1], m[0].value))
                results[i] = matches[:k]
        for i, q, k, f in filtered:
            results[i] = self._search_filtered(_as_vector(q), k, f)
        return results

    def _topk(self, qmat: np.ndarray, k: int):
        if self.use_device:
            try:
                result = self._topk_device(qmat, k)
                self._device_failures = 0
                return result
            except Exception as e:  # noqa: BLE001 — possibly transient (OOM…)
                self._device_failures += 1
                if self._device_failures >= 3:
                    self.use_device = False  # three strikes: stop retrying
                self._log_device_error(
                    e, "knn_slab_search", disabled=not self.use_device
                )
        return self._topk_host(qmat, k)

    def _log_device_error(
        self, e: Exception, path: str, *, disabled: bool
    ) -> None:
        """One error-log line per failed device search. `path` names the
        device path that failed; a `disabled` line is the last this
        index writes about it — every later search is a host scan."""
        from pathway_tpu.internals.errors import global_error_log

        state = (
            f"DISABLED for this {type(self).__name__}: every later search "
            "is a host scan"
            if disabled
            else "will retry"
        )
        global_error_log().log(
            f"KNN device search failed ({type(e).__name__}: {e}); served "
            f"by the host scan, device path {path!r} {state}"
        )

    def _topk_device(self, qmat: np.ndarray, k: int):
        import jax.numpy as jnp

        from pathway_tpu.engine.device_plane import get_device_plane
        from pathway_tpu.ops.topk import knn_search_masked

        if self._device_dirty:
            with _obs.span(_obs.SPAN_KNN_REFRESH):
                self._refresh_device()
        plane = get_device_plane()
        # query batches are as ragged as the waves that carry them: pad
        # to the row bucket so (slab, qbucket, k) bounds the jit cache.
        # Batches past the bucket cap (bulk backfills) dispatch at their
        # exact size — one-off shapes, not a streaming recompile loop.
        n_q = qmat.shape[0]
        if n_q > plane.buckets.max_rows:
            qpad, qbucket = qmat.astype(np.float32), n_q
        else:
            (qpad,), qbucket = plane.pad_rows([qmat.astype(np.float32)], n_q)
        prog = plane.program(
            "knn_slab_search", knn_search_masked,
            static_argnames=("k", "metric"),
        )
        with _obs.span(_obs.SPAN_KNN_SEARCH, rows=n_q):  # launch + readback
            res = prog(
                jnp.asarray(qpad),
                self._device_docs,
                self._device_valid,
                k=min(k, int(self._device_docs.shape[0])),
                metric=self.metric if self.metric != "cosine" else "cos",
                bucket=(int(self._device_docs.shape[0]), qbucket, k, self.dim),
            )
            idxs = np.asarray(res.indices)[:n_q]
            dists = np.asarray(res.distances)[:n_q]
        out = []
        for r in range(idxs.shape[0]):
            keep = np.isfinite(dists[r])
            out.append((idxs[r][keep], dists[r][keep]))
        return out

    def _topk_host(self, qmat: np.ndarray, k: int):
        docs = self.vectors[: self.n_slots]
        dists = self._host_distances(qmat, docs)
        dists[:, ~self.valid[: self.n_slots]] = np.inf
        k = min(k, dists.shape[1])
        part = np.argpartition(dists, k - 1, axis=1)[:, :k]
        out = []
        for r in range(qmat.shape[0]):
            # include EVERY candidate tied with the k-th distance so the
            # caller's (score, key) re-rank is exact however many ties —
            # results never depend on slot/insertion order
            kth = np.max(dists[r][part[r]])
            if not np.isfinite(kth):
                finite = np.isfinite(dists[r])
                cand = np.flatnonzero(finite)
            else:
                cand = np.flatnonzero(dists[r] <= kth)
            out.append((cand, dists[r][cand]))
        return out

    def _host_distances(self, qmat: np.ndarray, docs: np.ndarray) -> np.ndarray:
        if self.metric in ("cos", "cosine"):
            qn = qmat / np.maximum(np.linalg.norm(qmat, axis=1, keepdims=True), 1e-12)
            return 1.0 - qn @ docs.T  # docs already unit-norm
        if self.metric == "dot":
            return -(qmat @ docs.T)
        qq = (qmat * qmat).sum(1, keepdims=True)
        dd = (docs * docs).sum(1)
        return np.maximum(qq - 2.0 * qmat @ docs.T + dd[None, :], 0.0)

    def _search_filtered(self, vec: np.ndarray, k: int, flt: str) -> Matches:
        pred = self._filters.get(flt)
        slots = [s for s, key in self.key_of.items() if pred(self.metadata.get(key))]
        if not slots:
            return []
        docs = self.vectors[slots]
        dists = self._host_distances(vec[None, :], docs)[0]
        matches = [(self.key_of[s], float(d)) for s, d in zip(slots, dists)]
        matches.sort(key=lambda m: (m[1], m[0].value))
        return matches[:k]


class LshIndex(HostIndex):
    """Locality-sensitive hashing over random projections.

    Reference parity: stdlib/ml/classifiers/_lsh.py (random projections,
    bucket assignment) + _knn_lsh.py (bucketed candidate scan). OR-AND
    scheme: `n_or` tables each of `n_and` concatenated hyperplane bits.
    """

    def __init__(
        self,
        dimensions: int | None = None,
        n_or: int = 4,
        n_and: int = 8,
        bucket_length: float = 2.0,
        metric: str = "l2",
        seed: int = 0,
        projection: Any = None,
        distance: Any = None,
    ):
        """`projection` (vec -> sequence of per-table bucket ids) and
        `distance` ((query, doc) -> float) plug user callables into the
        bucket assignment and the candidate rescore — the generic-LSH
        contract of the reference's knn_lsh_generic_classifier_train
        (ml/classifiers/_knn_lsh.py:135). Defaults draw OR-AND hyperplane
        projections and use the named metric."""
        self.dim = dimensions
        self.n_or = n_or
        self.n_and = n_and
        self.bucket_length = bucket_length
        self.metric = metric
        self.seed = seed
        self.custom_projection = projection
        self.custom_distance = distance
        self.projections: list[np.ndarray] | None = None
        self.offsets: list[np.ndarray] | None = None
        self.buckets: list[dict[tuple, set[Key]]] = [defaultdict(set) for _ in range(n_or)]
        self.vectors: dict[Key, np.ndarray] = {}
        self.metadata: dict[Key, Any] = {}
        self._filters = _FilterCache()

    def _ensure(self, dim: int) -> None:
        if self.custom_projection is not None:
            return
        if self.projections is None:
            self.dim = self.dim or dim
            rng = np.random.default_rng(self.seed)
            self.projections = [
                rng.normal(size=(self.dim, self.n_and)).astype(np.float32)
                for _ in range(self.n_or)
            ]
            self.offsets = [
                rng.uniform(0, self.bucket_length, size=self.n_and).astype(np.float32)
                for _ in range(self.n_or)
            ]

    def _bucket_ids(self, vec: np.ndarray) -> list:
        if self.custom_projection is not None:
            from pathway_tpu.engine.core import freeze_value

            ids = [freeze_value(b) for b in self.custom_projection(vec)]
            if len(ids) > len(self.buckets):  # grow to the callable's L
                self.buckets.extend(
                    defaultdict(set) for _ in range(len(ids) - len(self.buckets))
                )
            return ids
        return [
            tuple(np.floor((vec @ proj + off) / self.bucket_length).astype(np.int64))
            for proj, off in zip(self.projections, self.offsets)
        ]

    def add(self, key: Key, data: Any, metadata: Any = None) -> None:
        vec = _as_vector(data)
        self._ensure(vec.shape[0])
        self.remove(key)
        self.vectors[key] = vec
        self.metadata[key] = metadata
        for table, bid in zip(self.buckets, self._bucket_ids(vec)):
            table[bid].add(key)

    def remove(self, key: Key) -> None:
        vec = self.vectors.pop(key, None)
        if vec is None:
            return
        self.metadata.pop(key, None)
        for table, bid in zip(self.buckets, self._bucket_ids(vec)):
            table[bid].discard(key)

    def search(self, query: Any, k: int, metadata_filter: str | None = None) -> Matches:
        if not self.vectors:
            return []
        vec = _as_vector(query)
        self._ensure(vec.shape[0])
        candidates: set[Key] = set()
        for table, bid in zip(self.buckets, self._bucket_ids(vec)):
            candidates |= table.get(bid, set())
        if metadata_filter:
            pred = self._filters.get(metadata_filter)
            candidates = {c for c in candidates if pred(self.metadata.get(c))}
        if not candidates:
            return []
        keys = list(candidates)
        if self.custom_distance is not None:
            dists = [
                float(self.custom_distance(vec, self.vectors[c])) for c in keys
            ]
        else:
            docs = np.stack([self.vectors[c] for c in keys])
            if self.metric in ("cos", "cosine"):
                qn = vec / max(np.linalg.norm(vec), 1e-12)
                dn = docs / np.maximum(
                    np.linalg.norm(docs, axis=1, keepdims=True), 1e-12
                )
                dists = 1.0 - dn @ qn
            else:
                dists = np.linalg.norm(docs - vec[None, :], axis=1) ** 2
        matches = [(key, float(d)) for key, d in zip(keys, dists)]
        matches.sort(key=lambda m: (m[1], m[0].value))
        return matches[:k]


_TOKEN_SPLIT = None


def _bm25_tokenize(text: str) -> list[str]:
    import re

    global _TOKEN_SPLIT
    if _TOKEN_SPLIT is None:
        _TOKEN_SPLIT = re.compile(r"[a-z0-9]+")
    return _TOKEN_SPLIT.findall(text.lower())


class Bm25Index(HostIndex):
    """In-memory BM25 inverted index (Okapi BM25, k1/b standard constants).

    Reference parity: TantivyIndex (src/external_integration/
    tantivy_integration.rs:16). Scores are returned NEGATED so that the
    uniform 'smaller = closer' distance convention of the index layer holds.
    """

    K1 = 1.2
    B = 0.75

    def __init__(self) -> None:
        self.postings: dict[str, dict[Key, int]] = defaultdict(dict)
        self.doc_len: dict[Key, int] = {}
        self.metadata: dict[Key, Any] = {}
        self._filters = _FilterCache()

    def add(self, key: Key, data: Any, metadata: Any = None) -> None:
        self.remove(key)
        terms = _bm25_tokenize(str(data))
        self.doc_len[key] = len(terms)
        self.metadata[key] = metadata
        for t in terms:
            self.postings[t][key] = self.postings[t].get(key, 0) + 1

    def remove(self, key: Key) -> None:
        if key not in self.doc_len:
            return
        del self.doc_len[key]
        self.metadata.pop(key, None)
        for t in list(self.postings):
            self.postings[t].pop(key, None)
            if not self.postings[t]:
                del self.postings[t]

    def search(self, query: Any, k: int, metadata_filter: str | None = None) -> Matches:
        n = len(self.doc_len)
        if n == 0:
            return []
        avg_len = sum(self.doc_len.values()) / n
        scores: dict[Key, float] = defaultdict(float)
        for t in _bm25_tokenize(str(query)):
            plist = self.postings.get(t)
            if not plist:
                continue
            idf = math.log(1.0 + (n - len(plist) + 0.5) / (len(plist) + 0.5))
            for key, tf in plist.items():
                dl = self.doc_len[key]
                scores[key] += idf * (
                    tf * (self.K1 + 1.0)
                    / (tf + self.K1 * (1.0 - self.B + self.B * dl / avg_len))
                )
        if metadata_filter:
            pred = self._filters.get(metadata_filter)
            scores = {key: s for key, s in scores.items() if pred(self.metadata.get(key))}
        # key tie-break: scores must not depend on dict/insertion order
        ranked = sorted(scores.items(), key=lambda kv: (-kv[1], kv[0].value))[:k]
        return [(key, -s) for key, s in ranked]
