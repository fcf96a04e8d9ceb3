"""Device-dispatch plane: bucketed batch coalescing, double-buffered
host->device staging, and persistent donated buffers for the serving path.

Every XLA-backed serving operator (the JaxEmbedder encoder, the JaxLMChat
decoder, the KNN slab mirror, batched ``@pw.udf`` functions) routes its
dispatches through one process-wide :class:`DevicePlane`. The plane owns
four concerns the operators used to improvise separately:

* **Shape-bucketed coalescing** — live-data waves are ragged; padding
  every batch up to a bucket (rows to a power of two; sequence lengths
  to a power of two up to 512 and to four rungs an octave above it)
  means the jit cache sees a bounded set of shapes however the stream
  arrives. :class:`BucketPolicy` is the single rounding rule, and every
  :class:`DeviceProgram` records compilations per bucket so tests can
  assert "N ragged waves inside one bucket = exactly one compile".

* **Double-buffered staging** — dispatches run on a small pool of
  dispatch threads, so the host-side prep of wave *t+1* (tokenization,
  padding, ``device_put``) overlaps the device compute of wave *t*:
  while one thread blocks on the device result, another is already
  staging the next wave. ``stage()`` exposes the staging executor for
  callers that want the prep/compute split explicit (bench loops).

* **Frontier-driven stage coalescing** — :class:`WaveCoalescer` gathers
  every concurrently in-flight request (the engine's async-apply
  operator admits whole waves at once; under stage overlap, several
  waves) and flushes them as one padded dispatch, off the event loop,
  so a long generate never blocks the embed of a later wave.

* **Donated persistent buffers** — ``lease()``/``restore()`` keep
  big per-shape device buffers (the decoder's KV cache, the KNN doc
  slab) alive across dispatches; programs registered with
  ``donate_argnums`` hand the buffer back to XLA so the allocation is
  reused in place instead of re-created per call.

Everything here is backend-agnostic: on CPU the same code runs (donation
is a no-op), which is what lets the compile-count regression guard run
in tier-1 without TPU hardware.
"""

from __future__ import annotations

import os
import re as _re
import threading
import time as _time
from concurrent.futures import Future, ThreadPoolExecutor
from pathlib import Path
from typing import Any, Callable

from pathway_tpu.engine import faults
from pathway_tpu.internals import observability as _obs
from pathway_tpu.analysis import lockgraph as _lockgraph

__all__ = [
    "BucketPolicy",
    "DeviceProgram",
    "DevicePlane",
    "SlotPool",
    "WaveCoalescer",
    "bucket_len",
    "compile_cache_dir",
    "get_device_plane",
    "pad_left_rows",
    "reset_quarantines",
]


class BucketPolicy:
    """The single shape-rounding rule of the serving path.

    Rows round up to a power of two between ``min_rows`` and
    ``max_rows``. Sequence lengths round up to the next rung of one
    ladder between ``min_seq`` and the caller's cap (the model context):
    powers of two up to ``SEQ_OCTAVE_ABOVE``, four rungs an octave above
    it (:meth:`seq_bucket`). Distinct live batch sizes therefore hit at
    most ``log2(max/min)`` jit entries per program instead of one per
    size, and a long prompt is padded by a quarter at most, not doubled.
    """

    # Sequence rungs are powers of two up to here and a quarter octave
    # apart above: a program's time grows at least linearly with its
    # width, so doubling a long prompt's width doubles what it costs,
    # while under a few hundred tokens the dispatch costs more than the
    # padding and fewer shapes are worth more than tighter ones.
    SEQ_OCTAVE_ABOVE = 512

    def __init__(self, min_rows: int = 8, max_rows: int = 4096, min_seq: int = 16):
        if min_rows < 1 or max_rows < min_rows:
            raise ValueError(f"bad row bucket range [{min_rows}, {max_rows}]")
        self.min_rows = min_rows
        self.max_rows = max_rows
        self.min_seq = min_seq

    @staticmethod
    def _round_up(n: int, lo: int, hi: int) -> int:
        b = lo
        while b < n:
            b *= 2
        return min(b, hi)

    def rows_bucket(self, n: int) -> int:
        """Padded row count for a batch of n rows (n may exceed
        max_rows; the caller splits such batches before padding)."""
        if n > self.max_rows:
            raise ValueError(
                f"batch of {n} rows exceeds the {self.max_rows}-row bucket "
                "cap; split before padding"
            )
        return self._round_up(max(n, 1), self.min_rows, self.max_rows)

    def cap_bucket(self, n: int, lo: int = 8) -> int:
        """Padded capacity for a RESIDENT slab dimension (doc slots, ANN
        list capacity): power-of-two round-up with no upper clamp —
        unlike dispatch-batch rows, a persistent buffer legitimately
        grows past max_rows, and the pow2 ladder still bounds the jit
        cache to log2(capacity) shapes over the slab's lifetime."""
        b = max(1, lo)
        while b < n:
            b *= 2
        return b

    def seq_bucket(self, longest: int, cap: int) -> int:
        """Padded sequence length for rows whose longest is `longest`,
        bounded by the model cap: a function of the length and the cap
        only. Powers of two from ``min_seq`` up to 512; above 512 four
        rungs an octave, each a multiple of 128 (640, 768, 896, 1024,
        1280, 1536, 1792, 2048, 2560, ...), so the padding above 512
        stays under a quarter of the length."""
        n = max(longest, 1)
        b = self._round_up(n, self.min_seq, self.SEQ_OCTAVE_ABOVE)
        if b < n:
            step = (1 << ((n - 1).bit_length() - 1)) // 4  # octave below n
            b = -(-n // step) * step
        return min(b, cap)


def bucket_len(longest: int, cap: int) -> int:
    """Sequence bucket (>=16: a power of two up to 512, four rungs an
    octave above it, never over `cap`) so the jit cache sees few distinct
    shapes as lengths vary — shared by the embedder's right-pad and the
    chat's left-pad batching (the device plane's BucketPolicy)."""
    return get_device_plane().buckets.seq_bucket(longest, cap)


def pad_left_rows(
    rows: list, cap: int, pad_rows_to: int | None = None,
    n_rows: int | None = None,
):
    """Left-pad variable-length token rows into (ids, mask) int32 arrays
    at a bucketed width (`bucket_len` of the longest row; generation
    convention — real tokens end at the last column, so last-position
    logits are every row's next token).
    The batch dimension pads with all-masked rows so arbitrary wave
    sizes hit few jit shapes: to exactly `n_rows` (callers pass the
    device plane's row bucket), to a multiple of `pad_rows_to`, or to
    the plane's power-of-two bucket by default."""
    import numpy as np

    bucket = bucket_len(max((len(r) for r in rows), default=1) or 1, cap)
    if n_rows is not None:
        n = n_rows
    elif pad_rows_to is not None:
        n = ((len(rows) + pad_rows_to - 1) // pad_rows_to) * pad_rows_to
    else:
        n = get_device_plane().buckets.rows_bucket(len(rows))
    ids = np.zeros((n, bucket), np.int32)
    mask = np.zeros((n, bucket), np.int32)
    for i, r in enumerate(rows):
        r = r[-bucket:]
        ids[i, bucket - len(r):] = r
        mask[i, bucket - len(r):] = 1
    return ids, mask


def _named_for_trace(fn: Callable, plane_name: str) -> Callable:
    """`fn` under the name its XLA module should carry. jit names a
    module ``jit_<fn.__name__>``, and a ``functools.partial`` has no
    ``__name__`` (every program was ``jit__unknown`` in a device trace):
    a partial takes the name of the function it wraps, a lambda the
    plane's name for the program. A function with a name of its own is
    returned as it is."""
    import functools

    inner = fn
    while isinstance(inner, functools.partial):
        inner = inner.func
    name = getattr(inner, "__name__", None)
    if name is None or name == "<lambda>":
        name = _re.sub(r"\W", "_", plane_name)
    if getattr(fn, "__name__", None) == name:
        return fn

    @functools.wraps(fn)  # keeps the signature jit resolves argnames from
    def named(*args: Any, **kwargs: Any) -> Any:
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = name
    return named


class DeviceProgram:
    """One jitted program plus its per-bucket compile ledger and
    quarantine state.

    Wraps ``jax.jit(fn, ...)``; each call passes the bucket key it
    padded to, and the ledger records how many XLA compilations that
    (program, bucket) pair has cost — read straight off the jit cache
    (``_cache_size``), with a shape-signature fallback on runtimes that
    hide it. The invariant the tier-1 guard pins: streaming ragged
    batches inside one bucket never grows the ledger past 1.

    **Graceful degradation**: a dispatch that fails on a signature that
    has run before (device loss, out of memory) or on an injected
    ``device.dispatch.{name}`` fault *quarantines* the (program, bucket)
    entry and the wave falls back to the HOST path — the un-jitted
    function, op-by-op, slower but correct. While quarantined, calls for
    that bucket go straight to the host path; after an exponentially
    growing cooldown (``PROBE_BASE_S`` doubling up to ``PROBE_CAP_S``)
    one call is admitted as a re-probe, and a successful probe lifts the
    quarantine.

    **A first compile that fails raises.** An exception on a fresh
    signature — the call that traces and compiles — other than an
    injected fault is a bug in the program (a shape the compiler
    refuses, a kernel that does not fit), not a device fault: a program
    that has never run must not turn into a slow success on the host.
    The call re-raises and nothing is quarantined: the next call for
    that bucket is a first compile again, and raises again.

    The host re-run is also refused when the failed call already
    consumed a donated argument (``cb/step`` donates the KV cache): the
    buffer is gone, so the original exception propagates to the caller,
    which owns the recovery of that buffer.

    Every quarantine and every failed first compile is written to the
    global error log with the program, the bucket and the error.
    """

    # re-probe backoff for quarantined buckets (class-level so tests and
    # drills can compress the clock)
    PROBE_BASE_S = 0.5
    PROBE_CAP_S = 30.0

    def __init__(
        self,
        name: str,
        fn: Callable,
        *,
        donate_argnums: tuple[int, ...] = (),
        static_argnames: tuple[str, ...] = (),
    ):
        import jax

        self.name = name
        self._fn = fn  # the host-path fallback: same math, no XLA program
        self.donate_argnums = tuple(donate_argnums)
        kw: dict[str, Any] = {}
        if donate_argnums:
            kw["donate_argnums"] = self.donate_argnums
        if static_argnames:
            kw["static_argnames"] = tuple(static_argnames)
        self._jit = jax.jit(_named_for_trace(fn, name), **kw)
        self._lock = _lockgraph.register_lock(
            "device_plane.program", threading.Lock()
        )
        # bucket key -> compilations charged to it
        self.compile_counts: dict[Any, int] = {}
        # bucket key -> host seconds its fresh-signature calls spent in
        # trace + compile (or the persistent cache's load) + enqueue
        self.compile_seconds: dict[Any, float] = {}
        self._seen_sigs: set[Any] = set()
        # bucket key -> {"failures": n, "reopen_at": t, "last_error": str}
        self.quarantine: dict[Any, dict[str, Any]] = {}
        self.host_fallbacks = 0  # dispatches served by the host path

    def jit_cache_size(self) -> int | None:
        """Entries in the underlying jit cache — XLA's own ledger. Tests
        cross-check it against `total_compiles` (our per-bucket ledger);
        None on runtimes that hide the private accessor."""
        try:
            return int(self._jit._cache_size())
        except Exception:  # noqa: BLE001 — private accessor
            return None

    def lowered_text(self, *args: Any, **kwargs: Any) -> str:
        """The program lowered for these arguments, as StableHLO text —
        what a check reads to see which kernels the program calls."""
        return self._jit.lower(*args, **kwargs).as_text()

    @staticmethod
    def _signature(args: tuple, kwargs: dict) -> Any:
        def leaf(x: Any) -> Any:
            shape = getattr(x, "shape", None)
            if shape is not None:
                return (tuple(shape), str(getattr(x, "dtype", "?")))
            return x

        import jax

        flat, treedef = jax.tree_util.tree_flatten((args, kwargs))
        return (treedef, tuple(leaf(x) for x in flat))

    def __call__(self, *args: Any, bucket: Any = None, **kwargs: Any) -> Any:
        if self.quarantine and not self._admit_probe(bucket):
            # quarantined bucket, cooldown still running: host path
            return self._host_path(args, kwargs)
        # bookkeeping only under the lock; the dispatch itself runs
        # outside it so overlapping stages never serialize here
        sig = self._signature(args, kwargs)
        with self._lock:
            fresh_sig = sig not in self._seen_sigs
            if fresh_sig:
                self._seen_sigs.add(sig)
                self.compile_counts[bucket] = (
                    self.compile_counts.get(bucket, 0) + 1
                )
        t0 = _time.perf_counter()
        try:
            faults.check(f"device.dispatch.{self.name}")
            out = self._jit(*args, **kwargs)
        except Exception as e:  # noqa: BLE001 — quarantined, logged; see below
            from pathway_tpu.internals.errors import global_error_log

            error = f"{type(e).__name__}: {e}"
            first_compile = fresh_sig and not isinstance(
                e, faults.FaultInjected
            )
            with self._lock:
                if fresh_sig:
                    # the compile never happened; let a successful
                    # later call charge the ledger instead
                    self._seen_sigs.discard(sig)
                    n = self.compile_counts.get(bucket, 0) - 1
                    if n > 0:
                        self.compile_counts[bucket] = n
                    else:
                        self.compile_counts.pop(bucket, None)
                if not first_compile:
                    q = self.quarantine.setdefault(
                        bucket,
                        {"failures": 0, "reopen_at": 0.0, "last_error": ""},
                    )
                    q["failures"] += 1
                    q["last_error"] = error
                    q["reopen_at"] = _time.monotonic() + self._cooldown(
                        q["failures"]
                    )
                    failures = q["failures"]
            if first_compile:
                global_error_log().log(
                    f"device program {self.name!r} bucket {bucket!r}: "
                    f"first compile failed, not served from the host: "
                    f"{error[:600]}"
                )
                raise
            global_error_log().log(
                f"device program {self.name!r} bucket {bucket!r} "
                f"quarantined after {failures} failure(s): {error[:600]}"
            )
            if _obs.PLANE is not None:
                _obs.PLANE.record(
                    "device.quarantine", program=self.name,
                    bucket=repr(bucket), failures=failures,
                    error=error[:300],
                )
                _obs.PLANE.metrics.counter(
                    "pathway_device_dispatch_failures_total",
                    {"program": self.name},
                    help="device dispatches that failed and quarantined "
                    "their bucket",
                )
            if self._donation_consumed(args):
                raise
            return self._host_path(args, kwargs)
        with self._lock:
            lifted = self.quarantine.pop(bucket, None) is not None
            if fresh_sig:
                self.compile_seconds[bucket] = self.compile_seconds.get(
                    bucket, 0.0
                ) + (_time.perf_counter() - t0)
        if _obs.PLANE is not None:
            if lifted:
                _obs.PLANE.record(
                    "device.quarantine_lift", program=self.name,
                    bucket=repr(bucket),
                )
            if fresh_sig:
                _obs.PLANE.record(
                    "device.compile", program=self.name, bucket=repr(bucket),
                )
                _obs.PLANE.metrics.counter(
                    "pathway_device_compiles_total",
                    {"program": self.name},
                    help="XLA compilations charged to the program",
                )
            _obs.PLANE.metrics.counter(
                "pathway_device_dispatches_total", {"program": self.name},
                help="device dispatches through the plane",
            )
        return out

    def _cooldown(self, failures: int) -> float:
        """Doubling re-probe cooldown, saturating at PROBE_CAP_S. The
        exponent is clamped: a bucket failing for hours reaches failure
        counts where an unclamped ``2 ** failures`` overflows — crashing
        the wave the host fallback exists to save."""
        return min(
            self.PROBE_BASE_S * 2 ** min(failures - 1, 32), self.PROBE_CAP_S
        )

    def reset_quarantine(self) -> int:
        """Drop every per-bucket quarantine record (generation boundary:
        a supervisor restart or mesh rebalance starts the new generation
        with a clean slate — stale cooldowns belong to the device state
        of a process that no longer exists). Returns entries dropped."""
        with self._lock:
            n = len(self.quarantine)
            self.quarantine.clear()
        return n

    def _admit_probe(self, bucket: Any) -> bool:
        """True when the bucket is healthy, or quarantined but due for a
        re-probe (which is then claimed: the cooldown moves forward so
        concurrent callers don't stampede the device)."""
        with self._lock:
            q = self.quarantine.get(bucket)
            if q is None:
                return True
            now = _time.monotonic()
            if now < q["reopen_at"]:
                return False
            q["reopen_at"] = now + self._cooldown(q["failures"])
            return True

    def _host_path(self, args: tuple, kwargs: dict) -> Any:
        """Serve one dispatch from the un-jitted function."""
        with self._lock:
            self.host_fallbacks += 1
        if _obs.PLANE is not None:
            _obs.PLANE.metrics.counter(
                "pathway_device_host_fallbacks_total",
                {"program": self.name},
                help="dispatches served by the host path",
            )
        return self._fn(*args, **kwargs)

    def _donation_consumed(self, args: tuple) -> bool:
        """True when a failed call already deleted a donated argument —
        the host path has nothing left to run on."""
        import jax

        return any(
            leaf.is_deleted()
            for i in self.donate_argnums
            if i < len(args)
            for leaf in jax.tree_util.tree_leaves(args[i])
            if isinstance(leaf, jax.Array)
        )

    @property
    def total_compiles(self) -> int:
        return sum(self.compile_counts.values())


class WaveCoalescer:
    """Coalesces concurrently in-flight requests into one padded dispatch.

    The engine's async-apply operator starts every row coroutine of a
    wave before awaiting any (``asyncio.gather``), so each ``submit``
    lands here and the flush scheduled behind them sees the whole wave —
    and, under frontier stage overlap, rows of *several* admitted waves
    at once. The flush itself runs on the plane's dispatch pool (never
    on the event loop): a slow generate flush cannot stall the embed
    coalescer of a later wave, which is what lets causally-independent
    stages pipeline through the scheduler.

    ``flush_fn(items) -> list[results]`` must return exactly
    ``len(items)`` results in order.
    """

    def __init__(
        self,
        flush_fn: Callable[[list], list],
        max_batch: int = 4096,
        pool: ThreadPoolExecutor | None = None,
    ):
        self.flush_fn = flush_fn
        self.max_batch = max_batch
        self._pool = pool
        self.pending: list[tuple[Any, Any]] = []  # (item, asyncio.Future)
        self._scheduled = False
        self.flushes = 0  # dispatch count (tests: coalescing actually happened)

    async def submit(self, item: Any) -> Any:
        import asyncio

        loop = asyncio.get_running_loop()
        fut: Any = loop.create_future()
        self.pending.append((item, fut))
        if not self._scheduled:
            self._scheduled = True
            loop.call_soon(self._flush_cb, loop)
        return await fut

    # Called on the event loop. Splits pending into max_batch chunks and
    # hands each to the dispatch pool; results resolve the row futures
    # back on the loop. Without a pool (tests, teardown) the flush runs
    # inline — same results, no overlap.
    def _flush_cb(self, loop: Any) -> None:
        self._scheduled = False
        while self.pending:
            batch, self.pending = (
                self.pending[: self.max_batch],
                self.pending[self.max_batch:],
            )
            items = [it for it, _f in batch]
            futs = [f for _it, f in batch]
            self.flushes += 1
            if self._pool is None:
                self._resolve(futs, *self._run(items))
            else:
                task = self._pool.submit(self._run, items)
                task.add_done_callback(
                    lambda t, futs=futs: loop.call_soon_threadsafe(
                        self._resolve, futs, *t.result()
                    )
                )

    def _run(self, items: list) -> tuple[list | None, Exception | None]:
        try:
            return self.flush_fn(items), None
        except Exception as e:  # noqa: BLE001 — delivered per-row below
            return None, e

    @staticmethod
    def _resolve(futs: list, values: list | None, err: Exception | None) -> None:
        if err is None and (values is None or len(values) != len(futs)):
            err = RuntimeError(
                f"coalesced flush returned {0 if values is None else len(values)}"
                f" results for {len(futs)} items"
            )
        for i, f in enumerate(futs):
            if f.done():
                continue
            if err is not None:
                f.set_exception(err)
            else:
                f.set_result(values[i])


class SlotPool:
    """Fixed pool of decode slots over one persistent multi-row buffer —
    the bookkeeping half of continuous batching (serving/
    continuous_batching.py). Each slot is one row of a leased KV cache; a
    request acquires a slot at admission, holds it across its whole
    generation, and releases it at the step boundary where it finishes —
    at which point the *same decode batch* re-fills the row with the next
    queued request instead of waiting for the wave to drain.

    Counters are the observable the acceptance tests pin: ``refills``
    (acquisitions after the pool has been non-empty at least once — i.e.
    a freed row handed to a new request), ``joined_inflight``
    (acquisitions while at least one other slot was mid-generation), and
    the active/high-water gauges. They export through the metrics
    registry as ``pathway_serving_slot_*`` when the observability plane
    is armed, and are always readable off the pool itself.
    """

    def __init__(self, name: str, n_slots: int):
        if n_slots < 1:
            raise ValueError(f"slot pool needs >= 1 slot, got {n_slots}")
        self.name = name
        self.n_slots = n_slots
        self._lock = _lockgraph.register_lock(
            "device_plane.slot_pool", threading.Lock()
        )
        # LIFO keeps hot cache rows hot; slot 0 first for determinism
        self._free = list(range(n_slots))[::-1]
        self.acquired_total = 0
        self.refills = 0  # acquisitions of a previously-used slot
        self.joined_inflight = 0  # acquired while others were mid-flight
        self.high_water = 0
        self._ever_used: set[int] = set()
        # the scheduler that drives this pool hangs its own counters here
        # (ContinuousBatcher.stats), so /statistics and /metrics find them
        # beside the pool's and they go when the pool is dropped
        self.scheduler_stats: dict[str, float] | None = None

    @property
    def active(self) -> int:
        with self._lock:
            return self.n_slots - len(self._free)

    def acquire(self) -> int | None:
        """Take a free slot (None when the pool is exhausted — the caller
        leaves the request queued for the next step boundary)."""
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop()
            self.acquired_total += 1
            others_in_flight = self.n_slots - len(self._free) - 1
            joined = others_in_flight > 0
            refill = slot in self._ever_used
            if joined:
                self.joined_inflight += 1
            if refill:
                self.refills += 1
            self._ever_used.add(slot)
            self.high_water = max(self.high_water, others_in_flight + 1)
            active = others_in_flight + 1
        if _obs.PLANE is not None:
            m = _obs.PLANE.metrics
            m.counter(
                "pathway_serving_slot_acquires_total", {"pool": self.name},
                help="decode slots handed to requests",
            )
            m.gauge(
                "pathway_serving_slots_active", active, {"pool": self.name},
                help="decode slots currently mid-generation",
            )
            if refill:
                m.counter(
                    "pathway_serving_slot_refills_total", {"pool": self.name},
                    help="freed decode slots re-filled with a new request",
                )
            if joined:
                m.counter(
                    "pathway_serving_joined_inflight_total",
                    {"pool": self.name},
                    help="requests that joined an in-flight decode batch",
                )
        return slot

    def release(self, slot: int) -> None:
        with self._lock:
            if slot in self._free:
                raise ValueError(f"slot {slot} released twice")
            self._free.append(slot)
            active = self.n_slots - len(self._free)
        if _obs.PLANE is not None:
            _obs.PLANE.metrics.gauge(
                "pathway_serving_slots_active", active, {"pool": self.name},
                help="decode slots currently mid-generation",
            )

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return {
                "n_slots": self.n_slots,
                "active": self.n_slots - len(self._free),
                "acquired_total": self.acquired_total,
                "refills": self.refills,
                "joined_inflight": self.joined_inflight,
                "high_water": self.high_water,
            }


class DevicePlane:
    """Process-wide device-dispatch plane (see module docstring)."""

    def __init__(self, bucket_policy: BucketPolicy | None = None):
        self.buckets = bucket_policy or BucketPolicy()
        self.programs: dict[str, DeviceProgram] = {}
        self._leases: dict[Any, list] = {}  # key -> pooled buffers
        self._slot_pools: dict[str, SlotPool] = {}
        self._name_seq = 0
        # REENTRANT on purpose: drop_program/drop_namespace run from
        # weakref finalizers, and gc can fire a finalizer on any
        # allocation — including one made while THIS thread already
        # holds the plane lock. A plain Lock deadlocks that thread
        # against itself (observed: jax.jit construction inside
        # program() triggering a dead chat's finalizer).
        self._lock = _lockgraph.register_lock(
            "device_plane.plane", threading.RLock(), reentrant=True
        )
        self._dispatch_pool: ThreadPoolExecutor | None = None
        self._staging_pool: ThreadPoolExecutor | None = None

    # ----------------------------------------------------------- executors

    @property
    def dispatch_pool(self) -> ThreadPoolExecutor:
        """Pool the coalescers flush on. More than one worker on purpose:
        stage overlap needs a generate dispatch blocked on the device to
        coexist with an embed dispatch staging its inputs."""
        with self._lock:
            if self._dispatch_pool is None:
                self._dispatch_pool = ThreadPoolExecutor(
                    max_workers=4, thread_name_prefix="pw-device-dispatch"
                )
            return self._dispatch_pool

    @property
    def staging_pool(self) -> ThreadPoolExecutor:
        """Single staging thread: host-side prep (tokenize/pad/device_put)
        runs here IN ORDER while the caller's current dispatch computes —
        the classic two-slot host->device double buffer."""
        with self._lock:
            if self._staging_pool is None:
                self._staging_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="pw-device-staging"
                )
            return self._staging_pool

    def stage(self, prep_fn: Callable, *args: Any) -> Future:
        """Run host-side prep on the staging thread; returns a Future.
        Submit wave t+1's prep before blocking on wave t's result and the
        two overlap."""
        return self.staging_pool.submit(prep_fn, *args)

    # ------------------------------------------------------------ programs

    def program(
        self,
        name: str,
        fn: Callable | None = None,
        *,
        donate_argnums: tuple[int, ...] = (),
        static_argnames: tuple[str, ...] = (),
    ) -> DeviceProgram:
        """Register-or-get the named program. The first caller supplies
        `fn`; later callers may omit it."""
        with self._lock:
            prog = self.programs.get(name)
        if prog is not None:
            return prog
        if fn is None:
            raise KeyError(f"no device program named {name!r}")
        # build the jit OUTSIDE the lock: jit construction allocates
        # heavily, and a gc-triggered finalizer re-entering the plane
        # must never find this thread mid-critical-section
        fresh = DeviceProgram(
            name,
            fn,
            donate_argnums=donate_argnums,
            static_argnames=static_argnames,
        )
        with self._lock:
            prog = self.programs.setdefault(name, fresh)
        return prog

    def _ledger(
        self, attr: str, snap: Callable[[Any], Any] = lambda v: v
    ) -> dict[tuple[str, Any], Any]:
        """{(program_name, bucket): snap(value)} of one per-bucket ledger
        across the plane. Snapshotted under each program's lock:
        dispatch-pool threads mutate the ledgers (incl. pops on failed
        dispatches)."""
        out: dict[tuple[str, Any], Any] = {}
        with self._lock:
            progs = list(self.programs.items())
        for name, prog in progs:
            with prog._lock:
                items = [
                    (b, snap(v)) for b, v in getattr(prog, attr).items()
                ]
            for bucket, value in items:
                out[(name, bucket)] = value
        return out

    def compile_counts(self) -> dict[tuple[str, Any], int]:
        """{(program_name, bucket): compilations} across the plane — the
        observable the no-recompile regression guard asserts on."""
        return self._ledger("compile_counts")

    def compile_seconds(self) -> dict[tuple[str, Any], float]:
        """{(program_name, bucket): seconds} beside :meth:`compile_counts`
        (see DeviceProgram.compile_seconds)."""
        return self._ledger("compile_seconds")

    def reset_quarantines(self) -> int:
        """Clear quarantine state across every registered program (the
        new-generation slate wipe; see DeviceProgram.reset_quarantine).
        Returns the number of (program, bucket) entries dropped."""
        with self._lock:
            progs = list(self.programs.values())
        dropped = sum(p.reset_quarantine() for p in progs)
        if dropped:
            from pathway_tpu.internals import observability as _obs

            if _obs.PLANE is not None:
                _obs.PLANE.record(
                    "device.quarantine_reset", dropped=dropped
                )
        return dropped

    def quarantined(self) -> dict[tuple[str, Any], dict[str, Any]]:
        """{(program_name, bucket): quarantine record} for every entry
        currently kept off the device (see DeviceProgram)."""
        return self._ledger("quarantine", dict)

    def coalescer(
        self, flush_fn: Callable[[list], list], max_batch: int = 4096,
        *, inline: bool = False,
    ) -> WaveCoalescer:
        return WaveCoalescer(
            flush_fn, max_batch=max_batch,
            pool=None if inline else self.dispatch_pool,
        )

    def slot_pool(self, name: str, n_slots: int) -> SlotPool:
        """Register-or-get the named decode slot pool (continuous
        batching). Like :meth:`program`, pools are plane-owned so their
        counters survive the batcher that uses them and export through
        /metrics; `drop_program` releases pools keyed to the program."""
        with self._lock:
            pool = self._slot_pools.get(name)
            if pool is None:
                pool = self._slot_pools[name] = SlotPool(name, n_slots)
            elif pool.n_slots != n_slots:
                raise ValueError(
                    f"slot pool {name!r} already registered with "
                    f"{pool.n_slots} slots (asked for {n_slots})"
                )
            return pool

    def slot_pools(self) -> dict[str, dict[str, int]]:
        """{pool_name: counters} across the plane — the /statistics and
        metrics view of continuous-batching occupancy."""
        with self._lock:
            pools = list(self._slot_pools.items())
        return {name: pool.snapshot() for name, pool in pools}

    def scheduler_stats(self) -> dict[str, dict[str, float]]:
        """{pool_name: the driving scheduler's counters} for the pools
        whose scheduler published them (`SlotPool.scheduler_stats`)."""
        with self._lock:
            pools = list(self._slot_pools.items())
        return {
            name: dict(pool.scheduler_stats) for name, pool in pools
            if pool.scheduler_stats is not None
        }

    def unique_name(self, prefix: str) -> str:
        """Collision-proof program name for per-instance registrations
        (id()-based names would be recycled by the allocator and hand a
        new instance a dead instance's compiled program)."""
        with self._lock:
            self._name_seq += 1
            return f"{prefix}#{self._name_seq}"

    # -------------------------------------------------- persistent buffers
    #
    # Each key holds a POOL of buffers, not a single slot: concurrent
    # flush chunks of one stage may overlap, and a single slot would make
    # the loser allocate fresh every dispatch and silently drop one
    # restored buffer. The pool depth is bounded by the stage's maximum
    # dispatch concurrency.

    def lease(self, key: Any, make: Callable[[], Any]) -> Any:
        """Take a persistent buffer for `key`, creating one on first use
        (or when every pooled buffer is currently leased). The caller
        passes it to a donating program and MUST hand the program's
        returned buffer back via :meth:`restore` — a leased buffer is
        consumed by XLA."""
        with self._lock:
            pool = self._leases.get(key)
            buf = pool.pop() if pool else None
        if buf is None:
            buf = make()
        return buf

    def restore(self, key: Any, buf: Any) -> None:
        with self._lock:
            self._leases.setdefault(key, []).append(buf)

    def drop_lease(self, key: Any) -> None:
        with self._lock:
            self._leases.pop(key, None)

    def drop_program(self, name: str) -> None:
        """Release a per-instance program and every lease pool keyed to it
        (lease keys embed the program name). Instances registered through
        :meth:`unique_name` call this from a finalizer — without it the
        process-global plane would pin dead instances' compiled executables
        and device buffers for the life of the process."""
        with self._lock:
            self.programs.pop(name, None)
            for key in [
                k for k in self._leases
                if isinstance(k, tuple) and name in k
            ]:
                del self._leases[key]

    def drop_namespace(self, prefix: str) -> None:
        """Release every program, lease pool and slot pool in a
        per-instance namespace: names equal to `prefix` or starting with
        ``prefix + "/"`` (a continuous batcher registers
        ``{prefix}/prefill``, ``{prefix}/step``, ``{prefix}/slots`` and a
        cache lease keyed on `prefix`). Prefix matching is
        delimiter-aware so ``cb#1`` never swallows ``cb#10``."""

        def hit(s: Any) -> bool:
            return isinstance(s, str) and (
                s == prefix or s.startswith(prefix + "/")
            )

        with self._lock:
            for pname in [p for p in self.programs if hit(p)]:
                del self.programs[pname]
            for key in [
                k for k in self._leases
                if isinstance(k, tuple) and any(hit(e) for e in k)
            ]:
                del self._leases[key]
            for pname in [p for p in self._slot_pools if hit(p)]:
                del self._slot_pools[pname]

    # -------------------------------------------------------- batch padding

    def pad_rows(self, mats: list, n_rows: int) -> tuple[list, int]:
        """Pad each 2-d numpy array in `mats` with zero rows up to the
        row bucket for `n_rows`; returns (padded, bucket)."""
        import numpy as np

        bucket = self.buckets.rows_bucket(n_rows)
        if bucket == n_rows:
            return list(mats), bucket
        out = [np.pad(m, ((0, bucket - n_rows), (0, 0))) for m in mats]
        return out, bucket


_plane: DevicePlane | None = None
_plane_lock = _lockgraph.register_lock(
    "device_plane.registry", threading.Lock()
)


def compile_cache_dir() -> str:
    """Where this process keeps XLA's persistent compile cache:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment names one, else
    ``<checkout>/.pathway-cache/xla``. The path is part of the cache key,
    so it is fixed: no temporary directory, process id or timestamp."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(__file__).resolve().parents[2] / ".pathway-cache" / "xla"
    )


def _configure_compile_cache() -> None:
    """Point JAX at :func:`compile_cache_dir`, once, when the plane is
    built. Where the environment names the directory JAX has already
    read it and no code sets another. Every program is kept, however
    small or quick to compile: a server restart recompiles nothing."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # a compile that ran before the plane existed (a model's parameter
    # init) latched "no cache" for the process; start over
    compilation_cache.reset_cache()


def get_device_plane() -> DevicePlane:
    global _plane
    with _plane_lock:
        if _plane is None:
            _configure_compile_cache()
            _plane = DevicePlane()
        return _plane


def reset_quarantines() -> int:
    """Generation-boundary slate wipe on the registered plane, if any —
    never *constructs* a plane just to clear it (a supervisor that ran no
    device work has nothing to reset)."""
    with _plane_lock:
        plane = _plane
    return plane.reset_quarantines() if plane is not None else 0
