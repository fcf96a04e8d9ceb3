"""Continuous batching for LLM decode: slot-based scheduling over one
persistent KV cache.

The wave-aligned serving path (`JaxLMChat._generate_batch`) dispatches a
whole generation as ONE jitted program per wave: every request in the
batch prefills together and decodes together, and a request arriving one
millisecond after the dispatch waits for the entire wave to drain —
p99 latency under load is bounded below by the full generation time of
the slowest co-batched wave. Continuous batching (the vLLM/Orca model)
replaces that with a **slot scheduler**:

* the KV cache is one persistent multi-row buffer (a device-plane lease,
  `init_kv_cache(cfg, n_slots)`); each row is a **slot**
  (:class:`~pathway_tpu.engine.device_plane.SlotPool`);
* a new request is admitted at the next **step boundary**: a b=1
  prefill (`models/transformer.prefill_into_slot`) scatters its prompt
  K/V into a free cache row — the in-flight neighbours never stop
  decoding for it;
* every decode step advances ALL occupied slots by one token through a
  single jitted program with per-row positions
  (`models/transformer.decode_step_slots`);
* a request releases its slot at the boundary after its **last step was
  dispatched**, and the same boundary re-fills the row from the admission
  queue (the cache is donated from program to program, so the device runs
  the prefill after the step that last used the row);
* a boundary runs **one prefill at most**: with a queue behind several
  free slots the loop goes prefill, step, prefill, step. A decoding
  answer then waits for one prefill between two of its tokens (with
  prompts of 10k tokens a prefill is 0.4 s, and eight in a row held
  every answer for 3.4 s), and slots filled from one queue finish a step
  apart and not in one burst that frees the whole pool at once. A burst
  of k requests into an idle pool pays k - 1 steps more for its last
  answer; a standing backlog pays nothing (a step with a free row costs
  what a full one costs, and the free row is filled one step later).

**The loop dispatches ahead of what it reads.** Decoding is argmax with a
fixed number of steps, so the host knows every slot's position, pad length
and last step without reading a token. The order of one turn of the loop:
at the boundary, dispatch the prefill of one queued request if a slot is
free, then read what the rule below lets it read; prepare ``pos`` and
``pad_len``, dispatch the step, give back the slots whose last step that
was, then read again in the same way. **The rule**: the oldest program out
is read, and read first, once more than ``_AHEAD`` are out, except a
prefill with only steps behind it: that one is waited for once a later
prefill is out behind it too, or once nothing is left to dispatch. A step
is read ``_AHEAD`` programs late: ``np.asarray`` on step n returns when n
has run, by which time n + 1 and n + 2 are queued behind it and the device
goes from one to the next without the host (the thread's wake-up, the
accounting, the lock, the next boundary's preparation and dispatch all run
while n + 1 does, and a burst in which the thread does not get the
interpreter for longer than a step is covered by n + 2). A prefill runs as
long as dozens of steps, so the host does not stand in it with two steps
queued: it dispatches the steps of the prefill's cycle and the next
boundary's prefill while the prefill runs, and the device finds them
queued when it ends, whatever the host is doing then. Where answers have
steps, at most two prefills are out (the one waited for and the one that
let the loop wait for it); a run of prefills alone (answers of one token)
is read ``_AHEAD`` late like steps. With no queue the steps behind an
unread prefill stop at the active requests' last steps. The device runs
the same programs in the same order under any rule: only the reads move.
The host still follows the device program for program, since every read
blocks until its program has run: a reply leaves when its last step has
been read, in the order the programs went out. ``_AHEAD`` is two, fixed in
the code. What lags with the read: a request's tokens, its device
counters, ``prefills`` / ``decode_steps`` / ``completed`` and its clocks,
and its reply, which never leaves before its last token is on the host;
``queue_depth()`` counts a request until then.
What does not lag: the slot. **The token vector stays on the device**: the
step program is the model's ``decode_step_slots`` behind one line that
makes its ``token`` operand from the last step's result and, in the slot a
prefill has filled since, that prefill's first token (a free slot's token
is whatever was left there: it runs at position 0, and the next prefill
overwrites its row). When nothing is left to dispatch the loop reads what
is still out, and only then exits or hands the cache lease back. A dispatch
or a read that raises fails every waiter (queued, holding a slot, or with
its last step out and unread) and gives every held slot back.
``stats["dispatched_ahead"]`` counts the dispatches that found the program
before them still running (``jax.Array.is_ready()`` false on its result
once the new one was out): over ``decode_steps + prefills`` it is near 1 in
a backlog, and where it is low the host is the pace.
``stats["dispatched_past_prefill"]`` counts the dispatches made while more
than ``_AHEAD`` programs were out behind an unread prefill: those before
which a reader of the oldest program at a depth of ``_AHEAD`` would have
waited for the prefill.

Both programs ride the device plane: the compile ledger proves a request
joining mid-generation costs **zero new XLA compilations** (the step
program is one shape; prefill is one shape per prompt bucket), and slot
counters flow into the metrics registry
(``pathway_serving_slot_refills_total``,
``pathway_serving_joined_inflight_total``,
``pathway_serving_decode_steps_total``, ``pathway_serving_slots_active``).

**One rule chooses the scheduler**: `JaxLMChat` builds this batcher at
temperature 0 and the wave-aligned coalescer above it; nothing else
selects. `JaxLMChat._generate_batch` stays callable on any chat as the
oracle: `decode_step_slots` is the same math as `generate_serving`'s
scanned step with the shared scalar position replaced by a per-row
vector, so a request's tokens are byte-identical on both, pinned by
``tests/test_continuous_batching.py``.

**Mesh-spanning slot pools** (``mesh_span=True``): on a multi-device
mesh the persistent KV cache's slot axis is sharded over the mesh's
``data`` axis and the pool grows to ``n_slots x shards`` — one slot
scheduler drives decode slots spread across every chip, so serving
concurrency scales with the pod instead of one chip's HBM. The decode
step stays ONE program (jit partitions the per-row vectors along the
same axis); scheduling, admission, and the step-boundary protocol are
unchanged, and per-request tokens are byte-identical to the
single-device pool (the slot axis is batch — rows never read each
other's slots). The attention kernels have no partitioning rule, so a
pool that spans a mesh runs its programs with ``fused_attention`` off
(``TransformerConfig``: the plain attention, which jit partitions).

**The scheduler times itself.** The decode loop is cut into contiguous
leaf phases (:data:`PHASES`): each opens a profiler span (visible when a
JAX profiler session is active) and adds its wall time to a cumulative
``stats`` key, always on like the counts beside it. The two waits are the
reads above, the only places the thread blocks on the device: a step's
wait is for the step ``_AHEAD`` *before* the one just dispatched, a
prefill's for a prefill with its cycle's steps and the next prefill queued
behind it (or the last programs, when nothing is left). ``loop_s`` is the
loop's own wall time, ``host_cpu_s`` the thread's CPU time outside the
two waits for the device, and ``queue_wait_s`` / ``first_token_s`` /
``residence_s`` sum each request's life from ``submit`` (counted by
``prefills`` and ``completed``); ``tokenize_s`` is the part of it that
``submit`` itself spends tokenising on the caller's thread (counted by
``submitted``). The five ``cpu_*_s`` keys mirror
``observability.thread_cpu()`` over ``loop_s``: what the process's other
threads burnt while the loop ran, by the role their names give them
(``engine``, ``udf``, ``edge``, ``pool``, and ``foreign``: Python threads
the program did not start), refreshed between two passes of the loop, once
a second of its time.
Where ``submit`` runs for a REST request (``observability.current_clock()``)
it stamps the request's clock, and ``_finish`` copies the request's three
instants onto it. Catalog: docs/observability.md.

**The step program is loaded when the batcher is built.** It has one
shape, known at construction, so the batcher's thread starts once with
an empty queue: it leases the slot cache, dispatches the step with no
slot occupied (traced, fetched from the persistent compile cache or
compiled, and loaded, off the caller's thread) and returns the lease. A
``submit`` arriving meanwhile queues and is served by that same thread.
It counts as no decode step: its time goes to ``preload_s`` alone. A
prefill's shape follows a prompt's length, which nothing knows before
the prompt arrives: no prefill program is loaded ahead of its first
prompt. ``prompt_tokens`` and ``padded_tokens`` count what the admitted
prompts held and the widths they ran at.

Decoding is temperature-0 (argmax) here; sampled generation keeps the
wave-aligned path (a per-request RNG stream inside a shared step program
is future work and the chat constructor routes accordingly).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any

from pathway_tpu.internals import observability as _obs
from pathway_tpu.analysis import lockgraph as _lockgraph

__all__ = ["ContinuousBatcher"]


# `stats` key (seconds, cumulative) -> the profiler span of that phase of
# the decode loop. Leaf phases only, one after another and never nested: a
# trace reduction gives an idle gap to the span that covers most of it.
PHASES = {
    "admit_prep_s": _obs.SPAN_CB_ADMIT_PREP,
    "admit_dispatch_s": _obs.SPAN_CB_ADMIT_DISPATCH,
    "admit_wait_s": _obs.SPAN_CB_ADMIT_WAIT,
    "step_prep_s": _obs.SPAN_CB_STEP_PREP,
    "step_dispatch_s": _obs.SPAN_CB_STEP_DISPATCH,
    "step_wait_s": _obs.SPAN_CB_STEP_WAIT,
    "account_s": _obs.SPAN_CB_ACCOUNT,
}
_WAITS = ("admit_wait_s", "step_wait_s")  # blocked on the device
# role of `observability.thread_cpu` -> the `stats` key that mirrors it:
# the roles whose threads can hold the interpreter the loop waits for (the
# batcher's own is `host_cpu_s`, which leaves the two waits out)
CPU_KEYS = {
    role: f"cpu_{role}_s" for role in ("engine", "udf", "edge", "pool", "foreign")
}
# seconds of loop time between two refreshes of the mirror: a reader windows
# it over tens of seconds, and a refresh reads every thread's clock
_CPU_EVERY_S = 1.0
# programs the loop keeps dispatched and unread behind the oldest step. One
# is enough where the host's time a dispatch is always under the running
# program's; where it is so only on average (a step of 8.75 ms, a host of 6
# that waits for the interpreter in bursts), a second carries the device
# over the bursts (PERF.md section 6: 0.75 -> 0.88 of the dispatches ahead
# and 2.7% more answers in a backlog). An unread prefill is not read at
# this depth: the loop waits for it once a later prefill is out as well
# (`_read_behind`), so that its whole cycle is queued behind it and the
# host's bursts fall while it runs
_AHEAD = 2


class _Phase:
    """One pass through a phase: its span, its wall time into `stats`.
    A wait also closes and reopens the thread's CPU clock around itself,
    so that `host_cpu_s` is what the thread burnt outside the waits."""

    __slots__ = ("owner", "key", "wait", "span", "t0")

    def __init__(self, owner: "ContinuousBatcher", key: str, meta: dict):
        self.owner = owner
        self.key = key
        self.wait = key in _WAITS
        self.span = _obs.span(PHASES[key], **meta)

    def __enter__(self) -> None:
        self.span.__enter__()
        if self.wait:
            self.owner._cpu_tick()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc: Any) -> None:
        self.owner.stats[self.key] += time.perf_counter() - self.t0
        if self.wait:
            self.owner._cpu_mark = time.thread_time()
        self.span.__exit__(*exc)


class _Request:
    __slots__ = (
        "row", "length", "future", "tokens", "steps_out", "slot",
        "pad_len", "width", "id", "t_submit", "t_admit", "t_first", "clock",
    )

    def __init__(
        self, row: list, future: Future, t_submit: float, clock: Any = None
    ):
        self.id = 0  # the batcher's `submitted` count when it came in
        # the REST request's clock, where `submit` was called for one
        self.clock = clock
        # on time.monotonic(): `submit` entered, a slot acquired, the
        # prefill's token on the host
        self.t_submit = t_submit
        self.t_admit = 0.0
        self.t_first = 0.0
        self.row = row  # token ids (already budget-truncated)
        self.length = len(row)
        self.future = future
        self.tokens: list[int] = []  # output tokens read back so far
        self.steps_out = 0  # decode steps dispatched for it, read or not
        self.slot: int | None = None
        self.pad_len = 0  # left-pad of the prompt bucket
        self.width = 0  # physical prompt width (the seq bucket)


class _Dispatched:
    """A program that is out and not read yet: its result, still on the
    device, and the requests whose tokens it holds: the one a prefill
    admitted (`batch` is None), or a step's rows, slot -> request."""

    __slots__ = ("out", "req", "slot", "batch")

    def __init__(
        self, out: Any, req: "_Request | None" = None, slot: int = -1,
        batch: "dict[int, _Request] | None" = None,
    ):
        self.out = out
        self.req = req
        self.slot = slot
        self.batch = batch


class ContinuousBatcher:
    """Slot-based decode scheduler over one leased multi-row KV cache.

    ``submit(prompt)`` returns a :class:`concurrent.futures.Future`
    resolving to the generated token string (the `JaxLMChat` output
    format). A background decode thread runs only while requests are in
    flight: it re-fills freed slots from the queue at every step
    boundary, advances all occupied slots one token per dispatch, and
    exits (restoring the cache lease) when the pool drains. It runs
    once at construction too, to load the step program (module
    docstring). A load that fails there follows the plane's rule for a
    failed first compile: it is written to the global error log, and
    the first request's step is a first compile again, which fails
    that request's future.
    """

    def __init__(
        self,
        *,
        params: Any,
        cfg: Any,
        tokenizer: Any,
        n_steps: int,
        n_slots: int = 8,
        plane: Any = None,
        name: str | None = None,
        mesh_span: bool = False,
    ):
        import dataclasses
        import functools

        from pathway_tpu.engine.device_plane import get_device_plane
        from pathway_tpu.models import transformer

        if n_steps < 1:
            raise ValueError("n_steps must be >= 1")
        self.params = params
        self.cfg = cfg
        # the decoder: the cache its configuration builds, its two programs
        # and the names of the counters they send back
        self._model = transformer
        self.tokenizer = tokenizer
        self.n_steps = n_steps
        # mesh-spanning pool: n_slots PER SHARD, the KV cache's slot axis
        # sharded over the mesh `data` axis (module docstring)
        self.mesh = None
        if mesh_span:
            import jax

            if len(jax.devices()) > 1:
                from pathway_tpu.parallel.mesh import default_mesh

                self.mesh = default_mesh(("data",))
                n_slots = n_slots * self.mesh.shape["data"]
                # a kernel cannot be partitioned along the slot axis
                cfg = self.cfg = dataclasses.replace(cfg, fused_attention=False)
        self.n_slots = n_slots
        self.budget = cfg.max_len - n_steps
        self._plane = plane or get_device_plane()
        self.name = name or self._plane.unique_name("cb")
        self.pool = self._plane.slot_pool(f"{self.name}/slots", n_slots)
        self._prefill = self._plane.program(
            f"{self.name}/prefill",
            functools.partial(transformer.prefill_into_slot, cfg=cfg),
            donate_argnums=(3,),  # the shared cache rides the lease cycle
        )
        # the model's step, looked up now (what stands under that name
        # while the batcher is built is what it serves), under a wrapper of
        # the same name, which is the name of the XLA module
        step = functools.partial(transformer.decode_step_slots, cfg=cfg)
        rows = n_slots

        def decode_step_slots(params, cache, last, first, fresh, pos, pad_len):
            """The model's step on a token vector that never left the
            device: the last step's tokens and, in the slot a prefill has
            filled since (`fresh`; -1 for none), that prefill's first."""
            import jax.numpy as jnp

            token = jnp.where(
                jnp.arange(rows) == fresh, first[0], last[:rows]
            )
            return step(params, cache, token, pos, pad_len)

        self._step = self._plane.program(
            f"{self.name}/step", decode_step_slots, donate_argnums=(1,),
        )
        self._cache_key = ("cb_kv_cache", self.name, n_slots)
        self._lock = _lockgraph.register_lock(
            "serving.slot_scheduler", threading.Lock()
        )
        self._queue: deque[_Request] = deque()
        self._active: dict[int, _Request] = {}  # slot -> request
        # requests whose last step is out and whose slot is back in the
        # pool, until that step is read and their future resolved
        self._leaving: set[_Request] = set()
        # programs dispatched and not read yet, oldest first: the loop
        # reads one when more than _AHEAD are out
        self._out: deque[_Dispatched] = deque()
        # what the next step's tokens are made of, all of it on the
        # device: the last step's result, the last prefill's, and the slot
        # that prefill filled if no step has run since (else -1)
        self._last: Any = None
        self._first: Any = None
        self._fresh = -1
        self._slot_ids: list | None = None  # -1 .. n_slots - 1, on the device
        self._running = False
        self._thread: threading.Thread | None = None
        # every key from the start: readers copy the dict from other
        # threads while the loop adds to it, so no key may appear later
        self.stats: dict[str, float] = {
            "submitted": 0, "completed": 0, "decode_steps": 0,
            "prefills": 0, "max_queue": 0,
            # dispatches that found the program before them still running
            "dispatched_ahead": 0,
            # dispatches made with more than _AHEAD programs out behind an
            # unread prefill, where a read at that depth would have waited
            "dispatched_past_prefill": 0,
            **dict.fromkeys(PHASES, 0.0),
            "loop_s": 0.0, "host_cpu_s": 0.0,
            # the process's other CPU time while the loop ran, by the role
            # of the thread that burnt it (observability.thread_cpu)
            **dict.fromkeys(CPU_KEYS.values(), 0.0),
            # `submit` entered until the request was queued: the prompt's
            # tokenising, on the caller's thread; counted by `submitted`
            # and inside `queue_wait_s`, which starts where it does
            "tokenize_s": 0.0,
            "queue_wait_s": 0.0, "first_token_s": 0.0, "residence_s": 0.0,
            # real tokens of the admitted prompts, and their widths
            "prompt_tokens": 0, "padded_tokens": 0,
            "preload_s": 0.0,  # the step program's load at construction
            # what a decoder's programs count on the device and send back
            # behind their tokens (0 where the block has no such layer)
            **dict.fromkeys(self._model.COUNTERS, 0),
        }
        # the counters this decoder's two programs append, in their order
        self._prefill_tail = transformer.prefill_counters(cfg)
        self._step_tail = transformer.step_counters(cfg)
        self.pool.scheduler_stats = self.stats
        self._loop_mark = 0.0  # perf_counter at the last `loop_s` tick
        self._cpu_mark = 0.0  # thread_time at the last `host_cpu_s` tick
        # the `cpu_*_s` mirror: `_loop_mark` at its last refresh, and what
        # `thread_cpu()` read then
        self._roles_mark = 0.0
        self._roles_last: dict[str, float] = {}
        with self._lock:
            self._start_thread(preload=True)

    # ------------------------------------------------------------- surface

    def submit(self, prompt: str) -> Future:
        """Queue one prompt; the future resolves to the token string."""
        t_submit = time.monotonic()
        row = list(self.tokenizer.tokenize(prompt))[-self.budget:]
        fut: Future = Future()
        clock = _obs.current_clock()
        req = _Request(row, fut, t_submit, clock)
        t_queued = time.monotonic()
        if clock is not None:
            clock.stamp(_obs.STAGE_PROMPT, t_submit)
            clock.stamp(_obs.STAGE_TOKENIZE, t_queued)
        with self._lock:
            self._queue.append(req)
            self.stats["submitted"] += 1
            self.stats["tokenize_s"] += t_queued - t_submit
            req.id = self.stats["submitted"]
            self.stats["max_queue"] = max(
                self.stats["max_queue"], len(self._queue)
            )
            if not self._running:
                self._start_thread()
        return fut

    def _start_thread(self, preload: bool = False) -> None:
        """Start the decode thread; the caller holds the lock. It waits
        for its predecessor, which may still be handing the cache lease
        back: a second lease would be a second slot cache on the device."""
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, args=(preload, self._thread), daemon=True,
            name=f"pw-cb-{self.name}",
        )
        self._thread.start()

    def queue_depth(self) -> int:
        with self._lock:
            return (
                len(self._queue) + len(self._active) + len(self._leaving)
            )

    def drain(self, timeout: float | None = 30.0) -> None:
        """Block until the in-flight work finishes (tests/teardown)."""
        t = self._thread
        if t is not None:
            t.join(timeout)

    def close(self) -> None:
        """Release plane registrations (programs, slot pool, cache lease).
        Called by the owner's finalizer; in-flight work is drained first."""
        self.drain()
        self._plane.drop_namespace(self.name)

    # ---------------------------------------------------------- decode loop

    def _init_cache(self):
        """Fresh multi-slot KV cache; with a mesh, the slot axis is
        sharded over `data` so the pool's rows live across every chip."""
        cache = self._model.init_kv_cache(self.cfg, self.n_slots)
        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            # whatever leaves the block's cache has: [layers, slots, ...]
            cache = jax.tree.map(
                lambda v: jax.device_put(v, NamedSharding(
                    self.mesh, P(None, "data", *[None] * (v.ndim - 2))
                )),
                cache,
            )
        return cache

    def _placed(self, arr: Any, *axes: Any) -> Any:
        """`arr` on the device; when the pool spans the mesh, laid out
        along `axes` of it (none: a copy on every chip)."""
        import jax.numpy as jnp

        arr = jnp.asarray(arr)
        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            arr = jax.device_put(arr, NamedSharding(self.mesh, P(*axes)))
        return arr

    def _step_vectors(self, pos, pad):
        """The per-slot positions and pad lengths as device arrays, which
        the host knows without reading a token back — sharded along the
        same `data` axis as the cache rows when the pool spans the mesh
        (jit then partitions the step program instead of replicating)."""
        return self._placed(pos, "data"), self._placed(pad, "data")

    def _device_state(self) -> None:
        """What a step takes besides the cache, before any program has
        made it: a token vector and a prefill's result of the shapes the
        two programs return (their counters ride behind the tokens), and
        the slot numbers, which never change."""
        import numpy as np

        if self._last is None:
            self._last = self._placed(
                np.zeros(self.n_slots + len(self._step_tail), np.int32)
            )
        if self._first is None:
            self._first = self._placed(
                np.zeros(1 + len(self._prefill_tail), np.int32)
            )
        if self._slot_ids is None:
            self._slot_ids = [
                self._placed(np.int32(i)) for i in range(-1, self.n_slots)
            ]

    def _slot_id(self, slot: int) -> Any:
        """Slot number `slot` (-1: none) as a scalar on the device."""
        return self._slot_ids[slot + 1]

    def _phase(self, key: str, **meta: Any) -> _Phase:
        """Context manager of one phase of the loop (a key of PHASES)."""
        return _Phase(self, key, meta)

    def _loop_tick(self) -> None:
        now = time.perf_counter()
        self.stats["loop_s"] += now - self._loop_mark
        self._loop_mark = now

    def _cpu_tick(self) -> None:
        now = time.thread_time()
        self.stats["host_cpu_s"] += now - self._cpu_mark
        self._cpu_mark = now

    def _roles_tick(self, count: bool = True) -> None:
        """Adds what each role's threads have burnt since the last refresh
        into its `cpu_*_s`. A thread that ended in between takes its
        seconds out of its role's sum: that refresh then adds nothing to
        the role, rather than less than nothing."""
        now = _obs.thread_cpu()
        if count:
            for role, key in CPU_KEYS.items():
                self.stats[key] += max(0.0, now[role] - self._roles_last[role])
        self._roles_last = now
        self._roles_mark = self._loop_mark

    def _count(self, names: tuple, tail: Any) -> None:
        """Add the device counters a program sent back behind its tokens
        (none, where the block counts nothing) into `stats`."""
        for name, value in zip(names, tail):
            self.stats[name] += int(value)

    def _dispatch_step(self, cache: Any, pos: Any, pad: Any) -> tuple:
        """One step over every slot, its tokens taken from what the
        device holds; its result is the next step's token vector."""
        nxt, cache = self._step(
            self.params, cache, self._last, self._first,
            self._slot_id(self._fresh), pos, pad, bucket=self.n_slots,
        )
        self._last, self._fresh = nxt, -1
        return nxt, cache

    def _preload(self, cache: Any) -> Any:
        """Dispatch the step once with no slot occupied and wait for it:
        the program is traced, compiled or fetched, and loaded. The rows
        it writes (position 0 of every slot) are what every step writes
        for a free slot; a prefill overwrites its slot's whole row."""
        import numpy as np

        zeros = np.zeros(self.n_slots, np.int32)
        nxt, cache = self._dispatch_step(
            cache, *self._step_vectors(zeros, zeros)
        )
        np.asarray(nxt)
        return cache

    def _loop(
        self, preload: bool = False, after: threading.Thread | None = None
    ) -> None:
        import numpy as np

        if after is not None:
            after.join()
        cache = None
        # set when the loop's clocks start: the construction's pass counts
        # nothing into `stats` but `preload_s` unless a request came in
        serving = False
        try:
            t0 = time.perf_counter()
            cache = self._plane.lease(self._cache_key, self._init_cache)
            self._device_state()
            if preload:
                cache = self._preload(cache)
                self.stats["preload_s"] += time.perf_counter() - t0
                with self._lock:
                    if not self._queue:
                        self._running = False
                        return
            self._loop_mark = time.perf_counter()
            self._cpu_mark = time.thread_time()
            self._roles_tick(count=False)
            serving = True
            while True:
                # ---- step boundary: re-fill ONE freed slot from the
                # queue. A decoding answer waits for one prefill between
                # two of its tokens, never for a queue of them, and slots
                # filled from a queue finish a step apart, not together
                with self._phase("admit_prep_s"), self._lock:
                    slot = self.pool.acquire() if self._queue else None
                    if slot is not None:
                        req = self._queue.popleft()
                        self._active[slot] = req
                        req.slot = slot
                        req.t_admit = time.monotonic()
                if slot is not None:
                    cache = self._admit(req, slot, cache)
                    self._read_behind()
                    self._loop_tick()
                with self._phase("step_prep_s"):
                    with self._lock:
                        batch = dict(self._active)
                        queued = bool(self._queue)
                        if not batch and not queued and not self._out:
                            # nothing left, nothing out; exit under the
                            # lock so a submit racing this check either
                            # sees _running=True (we loop again) or
                            # starts a fresh thread
                            self._running = False
                            return
                    if batch:
                        # ---- one decode step over every occupied slot
                        pos = np.zeros(self.n_slots, np.int32)
                        pad = np.zeros(self.n_slots, np.int32)
                        for slot, req in batch.items():
                            pos[slot] = req.width + req.steps_out
                            pad[slot] = req.pad_len
                        pos_d, pad_d = self._step_vectors(pos, pad)
                if not batch:
                    # no slot to step. With a queue the next boundary's
                    # prefill goes out first; without one the program
                    # still out is read, and the check above is made again
                    if not queued:
                        self._read_behind(keep=0)
                        self._loop_tick()
                    continue
                with self._phase("step_dispatch_s"):
                    nxt, cache = self._dispatch_step(cache, pos_d, pad_d)
                    self._sent(_Dispatched(nxt, batch=batch))
                    for slot, req in batch.items():
                        req.steps_out += 1
                        if req.steps_out >= self.n_steps - 1:
                            self._release(slot, req)
                self._read_behind()
                self._loop_tick()
                if self._loop_mark - self._roles_mark >= _CPU_EVERY_S:
                    self._roles_tick()
        except BaseException as e:  # noqa: BLE001 — fail every waiter loudly
            with self._lock:
                self._running = False
                held = list(self._active.keys())
                waiting = (
                    list(self._active.values()) + list(self._leaving)
                    + list(self._queue)
                )
                self._active.clear()
                self._leaving.clear()
                self._queue.clear()
            # what is out is not read: its requests have just failed. The
            # next thread starts from a blank token vector (a result that
            # failed on the device would fail every step fed with it)
            self._out.clear()
            self._last = self._first = None
            self._fresh = -1
            for slot in held:
                # slots must go back to the pool: leaking them would
                # shrink the batch forever and leave a later submit
                # spinning on an exhausted pool with nothing in flight
                self.pool.release(slot)
            for req in waiting:
                if not req.future.done():
                    req.future.set_exception(e)
            if isinstance(e, (KeyboardInterrupt, SystemExit)):
                raise
        finally:
            if serving:
                self._loop_tick()
                self._cpu_tick()
                self._roles_tick()
            # restore the cache lease ONLY if our namespace still exists:
            # a finalizer may have dropped it while this thread was
            # mid-generation, and restore() would re-create the lease
            # entry under the dropped key — pinning the multi-slot KV
            # cache in the process-global plane with no owner left
            with self._plane._lock:
                alive = (
                    self._plane._slot_pools.get(self.pool.name) is self.pool
                )
            if alive and cache is not None:
                self._plane.restore(self._cache_key, cache)

    def _admit(self, req: _Request, slot: int, cache: Any):
        """Prefill one queued request into its freshly acquired slot (the
        join-at-step-boundary event). The prefill is dispatched and not
        waited for: the loop reads it when `_AHEAD` more programs are out."""
        import jax.numpy as jnp

        from pathway_tpu.engine.device_plane import pad_left_rows

        with self._phase("admit_prep_s", req=req.id, slot=slot):
            ids, mask = pad_left_rows([req.row], self.budget, n_rows=1)
            req.width = ids.shape[1]
            req.pad_len = req.width - req.length
            ids_d, mask_d = jnp.asarray(ids), jnp.asarray(mask)
        # who is being admitted rides as span metadata, never in the name
        meta = {"req": req.id, "slot": slot, "width": req.width}
        with self._phase("admit_dispatch_s", **meta):
            first, cache = self._prefill(
                self.params, ids_d, mask_d, cache, self._slot_id(slot),
                bucket=(1, req.width),
            )
            self._sent(_Dispatched(first, req=req, slot=slot))
            if self.n_steps > 1:
                # the slot's next step takes its token from `first`
                self._first, self._fresh = first, slot
            else:
                self._release(slot, req)
        return cache

    def _sent(self, out: _Dispatched) -> None:
        """A program has just been dispatched. Counted as dispatched ahead
        if the one before it is still running: the device then goes from
        one to the other without the host."""
        if self._out and not self._out[-1].out.is_ready():
            self.stats["dispatched_ahead"] += 1
        if len(self._out) > _AHEAD and self._out[0].batch is None:
            self.stats["dispatched_past_prefill"] += 1
        # its way to the host starts when it ends, not when it is asked for
        out.out.copy_to_host_async()
        self._out.append(out)

    def _release(self, slot: int, req: _Request) -> None:
        """The request's last program is out: its slot is free for the
        next boundary's prefill, which the device runs after that program
        (the cache is donated from one to the next). Its reply leaves
        when the program is read."""
        with self._lock:
            self._active.pop(slot, None)
            self._leaving.add(req)
        self.pool.release(slot)

    def _read_behind(self, keep: int = _AHEAD) -> None:
        """Read the programs behind the newest `keep`, oldest first: each
        has run, or is running with the newer ones queued behind it. A
        prefill with only steps behind it is left out until `keep` is 0
        (module docstring: the rule)."""
        while len(self._out) > keep:
            if keep and self._out[0].batch is None and all(
                d.batch is not None for d in itertools.islice(self._out, 1, None)
            ):
                return
            self._read(self._out.popleft())

    def _read(self, done: _Dispatched) -> None:
        """Wait for a program's result and account for it: the tokens into
        their requests, the counters behind them, the replies of the
        requests it finished. With `_preload`'s, the only places the
        thread blocks on the device."""
        import numpy as np

        if done.batch is None:
            req, slot = done.req, done.slot
            meta = {"req": req.id, "slot": slot, "width": req.width}
            with self._phase("admit_wait_s", **meta):
                first = np.asarray(done.out)
            with self._phase("account_s"):
                req.t_first = time.monotonic()
                req.tokens.append(int(first[0]))
                self.stats["prefills"] += 1
                self._count(self._prefill_tail, first[1:])
                self.stats["prompt_tokens"] += req.length
                self.stats["padded_tokens"] += req.width
                self.stats["queue_wait_s"] += req.t_admit - req.t_submit
                self.stats["first_token_s"] += req.t_first - req.t_submit
                if len(req.tokens) >= self.n_steps:  # n_steps == 1
                    self._finish(slot, req)
            return
        with self._phase("step_wait_s"):
            nxt = np.asarray(done.out)
        with self._phase("account_s"):
            self.stats["decode_steps"] += 1
            self._count(self._step_tail, nxt[self.n_slots:])
            if _obs.PLANE is not None:
                _obs.PLANE.metrics.counter(
                    "pathway_serving_decode_steps_total",
                    {"pool": self.pool.name},
                    help="continuous-batching decode steps dispatched",
                )
            for slot, req in done.batch.items():
                req.tokens.append(int(nxt[slot]))
                if len(req.tokens) >= self.n_steps:
                    self._finish(slot, req)

    def _finish(self, slot: int, req: _Request) -> None:
        """The request's last token is on the host: its reply leaves."""
        now = time.monotonic()
        total = now - req.t_submit
        clock = req.clock
        if clock is not None:
            # before the future resolves: the caller stamps on from here
            clock.stamp(_obs.STAGE_QUEUE, req.t_admit)
            clock.stamp(_obs.STAGE_FIRST, req.t_first)
            clock.stamp(_obs.STAGE_DECODE, now)
        with self._lock:
            self._leaving.discard(req)
            self.stats["completed"] += 1
            self.stats["residence_s"] += total
        if not req.future.done():
            req.future.set_result(
                " ".join(f"<{int(t)}>" for t in req.tokens)
            )
        plane = _obs.PLANE
        if plane is not None:
            # per request, never per step
            queued = req.t_admit - req.t_submit
            first = req.t_first - req.t_submit
            pool = {"pool": self.pool.name}
            plane.metrics.observe(
                "pathway_serving_queue_wait_seconds", queued, pool,
                help="submit until a decode slot was acquired",
            )
            plane.metrics.observe(
                "pathway_serving_first_token_seconds", first, pool,
                help="submit until the prefill's token was on the host",
            )
            plane.metrics.observe(
                "pathway_serving_request_seconds", total, pool,
                help="submit until the last token (residence in the batcher)",
            )
            plane.record(
                "serving.request", export=False, req=req.id, slot=slot,
                queue_us=int(queued * 1e6), first_us=int(first * 1e6),
                total_us=int(total * 1e6), width=req.width,
            )
