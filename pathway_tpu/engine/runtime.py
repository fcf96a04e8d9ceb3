"""Engine runtime: the per-worker pump loop.

Reference parity: run_with_new_dataflow_graph (src/engine/dataflow.rs:5506)
— connector pollers feeding input sessions, commit timestamps on an
even-millisecond total order (src/engine/timestamp.rs:20-27), a pump that
finalizes one timestamp per wave, and end-of-stream flush.
"""

from __future__ import annotations

import asyncio
import os
import queue
import threading
import time as _time
from typing import Any, Callable, Iterable, Sequence

import numpy as np

from pathway_tpu.engine import faults
from pathway_tpu.internals import observability as _obs
from pathway_tpu.engine.core import (
    CaptureNode,
    Entry,
    Graph,
    InputNode,
    KeyedState,
    Node,
    _kv_cols,
    _kvs_of,
    _tok_plane,
    _wave_arrays,
    consolidate,
    freeze_row,
    iterate_native_on,
    nks_decode,
    nks_encode,
)
from pathway_tpu.internals.errors import ERROR
from pathway_tpu.internals.keys import Key, key_for_values, sequential_key
from pathway_tpu.analysis import lockgraph as _lockgraph


class OffsetMark:
    """In-stream frontier marker (reference: OffsetAntichain,
    src/persistence/frontier.rs): every event staged BEFORE this mark is
    covered by `frontier` — a {partition: position} dict whose shape the
    source owns (file -> byte position / ('done', mtime, size); kafka
    topic:partition -> next offset). The persistence layer checkpoints the
    frontier instead of journaling seekable sources' events; plain runs
    drop marks at poll time."""

    __slots__ = ("frontier",)

    def __init__(self, frontier: dict):
        self.frontier = frontier


class InputSession:
    """Thread-safe staging buffer feeding an InputNode.

    Mirrors the reference's input session + upsert session
    (src/connectors/adaptors.rs:23): `upsert` overwrites by key, `insert`/
    `remove` are plain z-set deltas.
    """

    def __init__(self, node: InputNode, upsert: bool = False):
        self.node = node
        self.upsert_mode = upsert
        self._lock = _lockgraph.register_lock(
            "runtime.input_session", threading.Lock()
        )
        self._staged: list[Entry] = []
        self._current: dict[Key, tuple] = {}  # for upsert sessions
        self.closed = False
        self.has_marks = False
        # persistence sets this before the reader starts: sources seek
        # past everything a committed checkpoint already covers
        self.resume_frontier: dict | None = None

    def mark_frontier(self, frontier: dict) -> None:
        """Stage an offset-frontier mark covering everything staged so
        far (offset-aware sources call this at record-aligned positions)."""
        self.has_marks = True
        with self._lock:
            self._staged.append(OffsetMark(dict(frontier)))

    def insert(self, key: Key, row: tuple) -> None:
        with self._lock:
            if self.upsert_mode:
                old = self._current.get(key)
                if old is not None:
                    self._staged.append((key, old, -1))
                self._current[key] = row
            self._staged.append((key, row, 1))

    def insert_batch(self, nbatch) -> None:
        """Stage a token-resident NativeBatch segment whole (plain insert
        sessions only — upsert bookkeeping is inherently per-row)."""
        assert not self.upsert_mode
        with self._lock:
            self._staged.append(nbatch)

    def remove(self, key: Key, row: tuple | None = None) -> None:
        with self._lock:
            if self.upsert_mode:
                old = self._current.pop(key, None)
                if old is not None:
                    self._staged.append((key, old, -1))
            elif row is not None:
                self._staged.append((key, row, -1))

    def drain(self) -> list[Entry]:
        with self._lock:
            staged, self._staged = self._staged, []
        return staged

    def close(self) -> None:
        self.closed = True


class Connector:
    """A data source with its own reader thread (reference:
    src/connectors/mod.rs:427 Connector::run — one thread per input
    connector, poller drained by the main pump).

    `replay_style` drives persistence resume (reference: seekable vs
    non-seekable sources in src/persistence/frontier.rs offset logic):
      * 'seekable' — the source re-reads deterministically from the start
        on every run (files, scripted subjects); resume skips the first N
        live events already journaled.
      * 'live' — the source only ever delivers new events (message
        queues); nothing is skipped, the journal supplies history.
    """

    replay_style = "seekable"

    def __init__(self, name: str, session: InputSession):
        self.name = name
        self.session = session
        self.thread: threading.Thread | None = None
        self.finished = threading.Event()

    def start(self) -> None:
        pass

    def poll(self) -> list[Entry]:
        staged = self.session.drain()
        if self.session.has_marks:
            # frontier marks matter only under persistence (the
            # PersistentConnector drains the session itself); plain runs
            # drop them here so they never reach the engine
            staged = [s for s in staged if type(s) is not OffsetMark]
        return staged

    @property
    def done(self) -> bool:
        return self.finished.is_set() and not self.session._staged


class ThreadConnector(Connector):
    """Runs a read function on a dedicated thread."""

    def __init__(self, name: str, session: InputSession, read_fn: Callable[[InputSession], None]):
        super().__init__(name, session)
        self.read_fn = read_fn

    def start(self) -> None:
        def run() -> None:
            try:
                self.read_fn(self.session)
            finally:
                self.finished.set()

        self.thread = threading.Thread(target=run, daemon=True, name=f"pw-connector-{self.name}")
        self.thread.start()


class Runtime:
    """Per-worker pump. Timestamps are even milliseconds from run start.

    Streaming and mesh execution are frontier-driven (engine/frontier.py):
    every source owns a watermark, waves carry (time, batch), and an
    operator fires for time t as soon as its input frontier passes t —
    there is no global wave barrier. ``run_static`` keeps the exact
    deterministic batch pump for debug computations.
    """

    # hard ceiling on one checkpoint-fence/end quiesce (_mesh_quiesce):
    # a genuinely livelocked mesh fails loudly with a state dump instead
    # of hanging forever; generous because a legitimate wave mid-fence
    # may be arbitrarily slow (first-touch XLA compile)
    _QUIESCE_TIMEOUT_S = 120.0

    def __init__(self, graph: Graph, autocommit_ms: int = 2):
        self.graph = graph
        self.autocommit_ms = max(2, autocommit_ms - autocommit_ms % 2)
        self.time = 0
        self.connectors: list[Connector] = []
        self.monitors: list[Callable[[int], None]] = []
        # checkpoint/resume orchestration (persistence.CheckpointManager)
        self.checkpointer: Any = None
        # cooperative stop: ends the pump at the next wave boundary
        self.stop_event: Any = None
        # inter-process data plane (parallel/process_mesh.py)
        self.mesh: Any = None
        # session sequence for namespacing mesh control tags
        self.session_seq = 0
        # the live FrontierScheduler (set by run/run_mesh; tests inspect)
        self.scheduler: Any = None

    def next_time(self) -> int:
        self.time += 2  # even-ms granule, reference timestamp.rs:20-27
        return self.time

    def add_connector(self, connector: Connector) -> None:
        self.connectors.append(connector)

    # ------------------------------------------------------ frontier pumps

    def _make_scheduler(self):
        from pathway_tpu.engine.frontier import FrontierScheduler

        if getattr(self.graph, "_cones", None):
            # the frontier scheduler fires finish_time per node and
            # stashes emissions per slot — an installed cone would never
            # fire there. Dissolve loudly (plan report + flight event)
            # so the fallback to per-node dispatch is visible, never
            # silent (engine/cone.py).
            from pathway_tpu.engine.cone import dissolve_cones

            dissolve_cones(self.graph, "frontier-scheduler")
        sched = FrontierScheduler(self.graph, monitors=self.monitors)
        self.scheduler = sched
        self.graph.scheduler = sched
        return sched

    def _kick_sources(self, sched) -> dict:
        """Register kick sources for capability-holding operators
        (iterate scopes with truncated convergence): the pump schedules
        empty waves through their cones until they drop the capability."""
        return {
            node: sched.add_kick_source(node)
            for node in self.graph.nodes
            if hasattr(node, "_pending_convergence")
        }

    def _stage_kicks(self, sched, kicks: dict) -> None:
        for node, tok in kicks.items():
            if node._pending_convergence:
                sched.stage(tok, self.next_time())

    def run(self) -> None:
        """Streaming pump: poll until all connectors are done, then
        flush + end.

        Each connector is its own SOURCE: a poll that yields data
        becomes a wave at a fresh timestamp of that source alone, and
        only that source's downstream cone fires. A slow source
        therefore delays nothing outside its own cone — operators
        downstream of other sources keep processing newer timestamps
        while the straggler catches up (frontier semantics; previously
        every wave stepped the whole graph at one shared timestamp).
        """
        try:
            self._run_streaming()
        except BaseException as e:
            if _obs.PLANE is not None:
                _obs.PLANE.record(
                    "runtime.error", error=f"{type(e).__name__}: {e}"[:500]
                )
                _obs.dump_flight("error")
            raise

    def _run_streaming(self) -> None:
        for c in self.connectors:
            c.start()
        if not self.connectors:
            t = self.next_time()
            self.graph.step(t)
            self.graph.end(t)
            return
        sched = self._make_scheduler()
        sched.allow_async = True  # deferred device waves pipeline here
        src = {c: sched.add_source(c.session.node) for c in self.connectors}
        kicks = self._kick_sources(sched)
        closed: set = set()
        ckpt_dirty = False
        # metrics-fed re-planning at safe epoch fences (fully-drained
        # scheduler): needs the observability plane for its signal and
        # the optimizer enabled (docs/planner.md)
        policy = None
        from pathway_tpu.internals import planner as _planner

        if (
            _obs.PLANE is not None
            and _planner.fuse_enabled()
            and _planner.adaptive_enabled()
        ):
            policy = _planner.AdaptivePolicy(
                self.graph, getattr(self.graph, "plan_report", None)
            )
        while True:
            plane = _obs.PLANE
            if plane is None:
                _time.sleep(self.autocommit_ms / 1000.0)
                for c in self.connectors:
                    entries = c.poll()
                    if entries:
                        sched.stage(src[c], self.next_time(), entries)
            else:
                t0 = _time.perf_counter()
                _time.sleep(self.autocommit_ms / 1000.0)
                t1 = _time.perf_counter()
                plane.stage_seconds("idle", t1 - t0)
                for c in self.connectors:
                    entries = c.poll()
                    if entries:
                        sched.stage(src[c], self.next_time(), entries)
                plane.stage_seconds("poll", _time.perf_counter() - t1)
            stopped = self.stop_event is not None and self.stop_event.is_set()
            for c in self.connectors:
                if (stopped or c.done) and src[c] not in closed:
                    closed.add(src[c])
                    sched.close(src[c])
            self._stage_kicks(sched, kicks)
            sched.advance_local(self.time)
            if sched.pump():
                ckpt_dirty = True
                # chaos drills: die hard right after a wave retired, with
                # its input offsets consumed but no checkpoint cut yet
                faults.crash("runtime.wave")
            if plane is not None:
                plane.tick_sources(
                    self.time,
                    lambda: [
                        (c.name, sched.watermark(src[c]))
                        for c in self.connectors
                    ],
                    sched.global_frontier,
                )
            # checkpoint on cadence whenever there is anything new to
            # commit — retired waves OR offset-frontier advances (a
            # quiet stream whose source finished a file still needs its
            # frontier made durable). The cut is at the global frontier:
            # after a pump every staged wave at or below it has retired.
            if (
                self.checkpointer is not None
                and self.checkpointer.due()
                and (ckpt_dirty or self.checkpointer.frontier_advanced())
                # never cut while a deferred device wave is in flight:
                # its input offsets are consumed but its results exist
                # only in the (non-persisted) in-flight future — a crash
                # after this cut would drop the wave. Holds resolve
                # within a dispatch, so the cut lands next cadence.
                and not sched.has_async()
            ):
                if plane is None:
                    self.checkpointer.checkpoint(self.time)
                else:
                    t0 = _time.perf_counter()
                    self.checkpointer.checkpoint(self.time)
                    plane.stage_seconds(
                        "checkpoint", _time.perf_counter() - t0
                    )
                ckpt_dirty = False
            # adaptive re-planning: only at a true epoch fence (nothing
            # in flight, nothing deferred) so a rewired cone can never
            # strand a staged wave on a replaced node
            if (
                policy is not None
                and sched.fully_drained()
                and not sched.has_async()
            ):
                # refresh pathway_spill_{runs,bytes} gauges at the fence
                # (seal/compact publish too, but an idle store's gauges
                # would otherwise go stale after restore)
                from pathway_tpu.engine import spill as _spill

                _spill.publish_metrics()
                policy.maybe_replan(sched)
            if len(closed) == len(self.connectors):
                # final drain: anything staged between the last poll and
                # the connector finishing
                final = False
                for c in self.connectors:
                    entries = c.poll()
                    if entries:
                        sched.stage(src[c], self.next_time(), entries)
                        final = True
                if final:
                    sched.advance_local(self.time)
                    sched.pump()
                # deferred device waves may still be computing: pump
                # until every hold resolves before ending the stream
                self._drain(sched, "streaming drain")
                t = self.next_time()
                self.graph.end(t)
                if self.checkpointer is not None:
                    self.checkpointer.checkpoint(t)
                    self.checkpointer.close()
                break

    # ---------------------------------------------------------- mesh pump

    def _drain_mesh(self, sched, mesh, remote_tokens) -> bool:
        """Pull watermark announcements + data buckets from the mesh
        into the scheduler. The watermark snapshot is taken atomically
        with (and logically before) the inbox drain, so a wire watermark
        of W is never acted on before every bucket at or below W from
        that peer has been staged (TCP frames from one peer arrive in
        send order)."""
        wm, buckets = mesh.take_frontier_updates()
        staged = False
        for (wire, time, peer, payload) in buckets:
            if not isinstance(time, (int, float)):
                # a peer already at the END BARRIER tags buckets with
                # ('end', t): they belong to the keyed blocking
                # exchange this process will run at its own graph.end
                mesh.restore_bucket(wire, time, peer, payload)
                continue
            tok = remote_tokens.get((wire, peer))
            if tok is None:
                # another session's wire on the shared process-wide
                # mesh: put it back for that session to claim (its
                # enable_frontier_inbox sweep recovers keyed buckets)
                mesh.restore_bucket(wire, time, peer, payload)
                continue
            sched.stage(tok, time, payload)
            staged = True
            if time > self.time:
                # keep the local clock ahead of every observed remote
                # time so fresh local waves never sort behind them
                self.time = time + (time % 2)
        for (wire, peer), value in wm.items():
            tok = remote_tokens.get((wire, peer))
            if tok is not None:
                sched.advance(tok, value)
        return staged

    def _pump_mesh(self, sched, mesh, xnodes, sent: dict) -> bool:
        """Fire until stable, in small chunks: after every few
        notifications, announce each wire's advanced frontier (min over
        the sources reaching its exchange node, bounded by in-flight
        waves — nothing at or below it will ever be sent on the wire
        again) and drain newly-arrived remote buckets/watermarks. The
        chunking keeps this process's outgoing frontiers moving even
        through a long grind of slow operator waves — peers gated on
        these wires progress concurrently instead of freezing until
        the grind ends."""
        fired_any = False
        while True:
            fired = sched.pump(budget=8)
            if fired:
                fired_any = True
                # chaos drills: one worker dies right after waves retired
                # — whether this pump serves the main loop or a fence
                # quiesce round. Peers observe the death on their wires
                # and abort with WorkerLost for the supervisor to restart.
                faults.crash("runtime.mesh.wave")
            moved = False
            for x in xnodes:
                f = sched.frontier_of_node(x)
                if f > sent[x.wire_id]:
                    sent[x.wire_id] = f
                    mesh.send_wm(x.wire_id, f)
                    moved = True
            if self._drain_mesh(sched, mesh, self._remote_tokens):
                moved = True
            if not moved and not fired:
                return fired_any

    def _mesh_quiesce(self, sched, mesh, xnodes, sent, tag: str, rounds: int):
        """Barrier-drain rounds until the mesh is PROVABLY quiescent.

        Each round: advance the local clock over everything staged so
        far (a remote bucket above the step-1 watermark must become
        admissible, or it would sit stashed forever), allgather
        (local_time, fully_drained, data_frames_sent), sync the local
        clock to the mesh-wide max (announcements are capped by the
        local clock, and nothing advances it inside a fence — without
        the sync a peer's wave stashed above a slow process's clock
        livelocks the mesh), then drain+pump.
        The loop ends — identically on every process, because the
        decision reads only the allgathered view — once

          * at least ``rounds`` (= 2*exchange_depth+2) rounds ran, AND
          * every process entered the round fully drained, AND
          * no process's data-frame counter moved since the previous
            round (frames sent before a peer's barrier frame are
            ordered before it, so an unchanged counter means nothing
            is in flight anywhere).

        A fixed round count alone is NOT enough: a wave can lawfully
        stay stashed across many rounds while watermarks catch up, and
        a checkpoint cut with a stashed wave commits its input offsets
        without its effects — the recovered run silently loses it (the
        chaos drill's supervised-mesh case caught exactly this).
        Returns the final allgather view {proc: local_time}."""
        prev_sent: dict | None = None
        r = 0
        q0 = _time.perf_counter()
        deadline = _time.monotonic() + self._QUIESCE_TIMEOUT_S
        while True:
            sched.advance_local(self.time)
            view = mesh.allgather(
                f"{tag}-r{r}",
                (self.time, sched.fully_drained(), mesh.data_frames_sent),
            )
            # clock sync: my wire announcements are capped by my local-
            # source watermark = my clock, and with no connector polls
            # inside the fence the clock is FROZEN. A peer wave stashed
            # above it (its clock ran ahead and its bucket routed only
            # to itself) would wait on my announcement forever — the
            # mesh livelocks. Jumping to the mesh-wide max is safe for
            # the same reason _drain_mesh's bump on observed bucket
            # times is: every future local wave is stamped via
            # next_time() strictly above self.time.
            tmax = max(v[0] for v in view.values())
            if tmax > self.time:
                self.time = tmax
            self._drain_mesh(sched, mesh, self._remote_tokens)
            sched.advance_local(self.time)  # drained buckets moved the clock
            self._pump_mesh(sched, mesh, xnodes, sent)
            drained = all(v[1] for v in view.values())
            sent_now = {p: v[2] for p, v in view.items()}
            if r + 1 >= rounds and drained and sent_now == prev_sent:
                if _obs.PLANE is not None:
                    # metric only: waves fired inside the fence window are
                    # already attributed per-operator by the scheduler's
                    # span hook — feeding the window to the profiler too
                    # would count that wall-clock twice
                    _obs.PLANE.stage_seconds(
                        "quiesce", _time.perf_counter() - q0, profile=False
                    )
                    _obs.PLANE.record(
                        "mesh.quiesce", export=False, tag=tag, rounds=r + 1,
                        time=self.time,
                    )
                return {p: v[0] for p, v in view.items()}
            prev_sent = sent_now
            r += 1
            if _time.monotonic() > deadline:
                # wall-clock, not round-count: rounds are cheap on a
                # localhost mesh, and a legitimately slow wave (huge
                # first-touch compile) must not trip a spurious failure
                pend = {
                    slot: sorted(times)[:4]
                    for slot, times in sched._pending.items()
                    if times
                }
                # poison the wires BEFORE raising: peers are blocked in
                # the next round's allgather (which has no deadline of
                # its own) — closing our sockets flips us to dead on
                # their side, so they abort with WorkerLost instead of
                # hanging if this process survives the error
                try:
                    mesh.close()
                except Exception:  # noqa: BLE001 — best-effort poison
                    pass
                raise RuntimeError(
                    f"mesh quiesce {tag!r} failed to converge after "
                    f"{self._QUIESCE_TIMEOUT_S:.0f}s ({r} rounds): "
                    f"time={self.time} pending={pend} "
                    f"async={sorted(sched._async_waves)} view={view}"
                )

    def _mesh_rebalance_exit(self, mesh: Any, sid: int) -> None:
        """End this generation at a membership fence. Every process just
        committed the same epoch; an rb-ack flag barrier proves it mesh-
        wide (a process must not exit — killing its wires — while a peer
        is still quiescing toward that fence). Process 0, the only one
        holding the lowered graph, then re-homes the persisted shards
        before exiting. Never returns: raises SystemExit(REBALANCE_EXIT),
        which the supervisor treats as a planned generation boundary."""
        from pathway_tpu.parallel import membership as _mb
        from pathway_tpu.parallel.process_mesh import WorkerLost

        mesh.send_flag(("rb-ack", sid), 1)
        mesh.set_flag(("rb-ack", sid), 1)
        deadline = _time.monotonic() + 120.0
        while not all(
            mesh.flag_of(("rb-ack", sid), p, 0) for p in mesh.peers
        ):
            if mesh._dead:
                raise WorkerLost(
                    f"process {mesh.process_id}: peer(s) "
                    f"{sorted(mesh._dead)} died during the rebalance "
                    "quiesce; resume from the last committed checkpoint"
                )
            if _time.monotonic() > deadline:
                raise RuntimeError(
                    f"process {mesh.process_id}: rebalance quiesce ack "
                    "timed out"
                )
            mesh.wait_frames(0.05)
        if self.checkpointer is not None:
            self.checkpointer.close()
        if mesh.process_id == 0:
            _mb.rebalance_at_fence(self)
        _obs.record("runtime.rebalance_exit", process=mesh.process_id)
        raise SystemExit(_mb.REBALANCE_EXIT)

    def run_mesh(
        self, static_batches: list[tuple[int, InputNode, list[Entry]]] | None = None
    ) -> None:
        """Multi-process frontier pump: asynchronous progress tracking
        across the mesh, with no global wave barrier.

        Every process pumps its OWN sources at its own pace; exchange
        channels carry (time, batch) plus per-wire watermark
        announcements, and a downstream operator fires for time t as
        soon as its input frontier — local sources AND incoming wires —
        passes t. A straggling process therefore delays only the
        operators that causally consume its data; causally-independent
        cones on every peer keep processing at full speed (reference:
        timely's distributed progress protocol, progress/frontier.rs).

        Checkpoints cut at globally fully-retired times: the cadence
        owner (process 0) raises a FENCE; every process stops admitting
        input, the mesh drains to quiescence over barrier rounds, and
        all processes snapshot the same epoch — mutually consistent by
        construction (no wave is half-absorbed anywhere).
        """
        try:
            self._run_mesh(static_batches)
        except BaseException as e:
            if isinstance(e, SystemExit) and e.code == 75:
                # planned rebalance exit (parallel/membership.py), not a
                # crash: no postmortem
                raise
            # postmortem before the supervisor restarts the generation:
            # the recorder holds the last waves/frames/faults this worker
            # saw, which is exactly what "why did the mesh die" needs
            if _obs.PLANE is not None:
                _obs.PLANE.record(
                    "runtime.error", error=f"{type(e).__name__}: {e}"[:500]
                )
                _obs.dump_flight("error")
            raise

    def _run_mesh(
        self, static_batches: list[tuple[int, InputNode, list[Entry]]] | None = None
    ) -> None:
        from pathway_tpu.engine.frontier import DONE
        from pathway_tpu.engine.workers import ProcessExchangeNode
        from pathway_tpu.parallel import membership as _mb
        from pathway_tpu.parallel.process_mesh import WorkerLost

        mesh = self.mesh
        assert mesh is not None
        sched = self._make_scheduler()
        sid = self.session_seq
        for c in self.connectors:
            c.start()
        src = {c: sched.add_source(c.session.node) for c in self.connectors}
        statics_by_node: dict[int, Any] = {}
        for t, node, entries in sorted(
            static_batches or [], key=lambda b: b[0]
        ):
            tok = statics_by_node.get(node.node_id)
            if tok is None:
                tok = statics_by_node[node.node_id] = sched.add_source(node)
            sched.stage(tok, t, entries)
            self.time = max(self.time, t + (t % 2))
        for tok in statics_by_node.values():
            sched.close(tok)
        kicks = self._kick_sources(sched)
        xnodes = [
            n for n in self.graph.nodes if isinstance(n, ProcessExchangeNode)
        ]
        self._remote_tokens: dict[tuple[int, int], Any] = {}
        for x in xnodes:
            for p in mesh.peers:
                self._remote_tokens[(x.wire_id, p)] = sched.add_remote_source(
                    x, p
                )
        mesh.enable_frontier_inbox()
        wm_sent = {x.wire_id: -1 for x in xnodes}
        rounds = 2 * sched.reach.exchange_depth() + 2
        fences_handled = 0
        fences_raised = 0
        closed: set = set()
        done_sent = False
        ckpt_dirty = False
        # elastic membership (parallel/membership.py): process 0 watches
        # for quiesce requests under the SHARED persistence root; every
        # process stops admitting input once the quiesce flag is seen and
        # exits REBALANCE_EXIT after the final fence commits
        shared_root: str | None = None
        if self.checkpointer is not None:
            shared_root = os.path.dirname(
                os.path.abspath(self.checkpointer.config.backend.path)
            )
        elastic = shared_root is not None and _mb.elastic_enabled()
        if elastic:
            _mb.write_source_map(
                self.checkpointer.config.backend.path, self.connectors
            )
        try:
            while True:
                quiescing = elastic and bool(
                    mesh.flag_value(("quiesce", sid), default=0)
                )
                if mesh._dead:
                    # supervised recovery: abort THIS wave cleanly (no
                    # partial checkpoint — the last committed epoch stays
                    # the resume point) and surface a typed error the
                    # supervisor restarts the whole mesh on. Every peer
                    # observes the death on its own wires, so the mesh
                    # drains instead of hanging on a barrier.
                    raise WorkerLost(
                        f"process {mesh.process_id}: peer(s) "
                        f"{sorted(mesh._dead)} died mid-run; resume from "
                        "the last committed checkpoint"
                    )
                # 1. local ingestion: one fresh wave per source per poll
                # (suspended during a rebalance quiesce: anything consumed
                # after the final fence would be lost to the next
                # generation, which resumes from that fence's offsets)
                if not quiescing:
                    for c in self.connectors:
                        entries = c.poll()
                        if entries:
                            sched.stage(src[c], self.next_time(), entries)
                            ckpt_dirty = True
                stopped = (
                    self.stop_event is not None and self.stop_event.is_set()
                )
                for c in self.connectors:
                    if (stopped or c.done) and src[c] not in closed:
                        closed.add(src[c])
                        sched.close(src[c])
                self._stage_kicks(sched, kicks)
                sched.advance_local(self.time)
                # 2. remote ingestion + watermark announcements
                self._drain_mesh(sched, mesh, self._remote_tokens)
                # 3. fire everything the frontier allows; announce wires
                # (the runtime.mesh.wave crash point probes inside
                # _pump_mesh, so fence-quiesce waves count too)
                if self._pump_mesh(sched, mesh, xnodes, wm_sent):
                    ckpt_dirty = True
                if _obs.PLANE is not None:
                    _obs.PLANE.tick_sources(
                        self.time,
                        lambda: [
                            (c.name, sched.watermark(src[c]))
                            for c in self.connectors
                        ],
                        sched.global_frontier,
                    )
                # 4. checkpoint fences (cadence owned by process 0)
                if (
                    elastic
                    and mesh.process_id == 0
                    and not done_sent
                    and not quiescing
                    and _mb.quiesce_requested(shared_root)
                ):
                    # membership change pending: broadcast the quiesce
                    # (flag value = the fence number that seals this
                    # generation) BEFORE raising that fence — per-peer
                    # frame ordering makes every process see the quiesce
                    # no later than the fence itself
                    quiescing = True
                    fences_raised += 1
                    mesh.send_flag(("quiesce", sid), fences_raised)
                    mesh.set_flag(("quiesce", sid), fences_raised)
                    mesh.send_flag(("fence", sid), fences_raised)
                    mesh.set_flag(("fence", sid), fences_raised)
                elif (
                    mesh.process_id == 0
                    and not done_sent
                    and not quiescing
                    and self.checkpointer is not None
                    and self.checkpointer.due()
                    and (ckpt_dirty or self.checkpointer.frontier_advanced())
                ):
                    fences_raised += 1
                    mesh.send_flag(("fence", sid), fences_raised)
                    mesh.set_flag(("fence", sid), fences_raised)
                pending_fence = mesh.flag_value(("fence", sid), default=0)
                while fences_handled < pending_fence:
                    fences_handled += 1
                    self._mesh_quiesce(
                        sched, mesh, xnodes, wm_sent,
                        f"s{sid}-fence-{fences_handled}", rounds,
                    )
                    if not sched.fully_drained():
                        # committing here would persist input offsets for
                        # waves whose effects are still in flight — the
                        # recovered run would silently drop them
                        raise RuntimeError(
                            f"process {mesh.process_id}: checkpoint fence "
                            f"{fences_handled} reached with undrained waves"
                        )
                    if self.checkpointer is not None:
                        self.checkpointer.checkpoint(self.time)
                        ckpt_dirty = False
                    pending_fence = mesh.flag_value(("fence", sid), default=0)
                # 4b. rebalance exit: the quiesce flag names the fence
                # that seals this generation; once THAT fence's epoch is
                # committed everywhere, acknowledge and hand the roots to
                # the rebalancer (process 0) / exit (peers)
                quiesce_fence = (
                    mesh.flag_value(("quiesce", sid), default=0)
                    if elastic
                    else 0
                )
                if quiesce_fence and fences_handled >= quiesce_fence:
                    self._mesh_rebalance_exit(mesh, sid)  # never returns
                # 5. termination: local done -> announce; global done ->
                # drain to quiescence and end together
                local_done = len(closed) == len(self.connectors)
                if local_done and not done_sent:
                    final = False
                    for c in self.connectors:
                        entries = c.poll()
                        if entries:
                            sched.stage(src[c], self.next_time(), entries)
                            final = True
                    if final:
                        sched.advance_local(self.time)
                        self._pump_mesh(sched, mesh, xnodes, wm_sent)
                    for tok in kicks.values():
                        sched.close(tok)
                    sched.advance_local(DONE)
                    self._pump_mesh(sched, mesh, xnodes, wm_sent)
                    done_sent = True
                    mesh.send_flag(("done", sid), 1)
                    mesh.set_flag(("done", sid), 1)
                if done_sent and all(
                    mesh.flag_of(("done", sid), p) for p in mesh.peers
                ):
                    # a fence raised just before a peer announced done is
                    # ordered before its done flag: handle it first
                    pending_fence = mesh.flag_value(("fence", sid), default=0)
                    if fences_handled < pending_fence:
                        continue
                    vals = self._mesh_quiesce(
                        sched, mesh, xnodes, wm_sent, f"s{sid}-end", rounds
                    )
                    t_end = max(max(vals.values()), self.time) + 2
                    self.time = t_end
                    mesh.frontier_inbox = False
                    for x in xnodes:
                        x.end_barrier = True
                    self.graph.end(t_end)
                    if self.checkpointer is not None:
                        self.checkpointer.checkpoint(t_end)
                        self.checkpointer.close()
                    break
                if _obs.PLANE is None:
                    mesh.wait_frames(self.autocommit_ms / 1000.0)
                else:
                    t0 = _time.perf_counter()
                    mesh.wait_frames(self.autocommit_ms / 1000.0)
                    _obs.PLANE.stage_seconds(
                        "idle", _time.perf_counter() - t0
                    )
        finally:
            mesh.frontier_inbox = False

    def run_static(self, batches: list[tuple[int, InputNode, list[Entry]]]) -> None:
        """Batch mode: feed pre-timed batches, run each wave, then end.

        `batches` are (time, node, entries); times must use the even-ms
        domain. Pipelines with deferrable device stages (async-apply
        under stage overlap) run through the frontier scheduler so waves
        at distinct timestamps pipeline across operators; everything
        else runs one whole-graph wave per timestamp, in time order.
        """
        if self._wants_stage_overlap():
            return self._run_static_frontier(batches)
        by_time: dict[int, list[tuple[InputNode, list[Entry]]]] = {}
        for t, node, entries in batches:
            by_time.setdefault(t, []).append((node, entries))
        last_t = 0
        for t in sorted(by_time):
            for node, entries in by_time[t]:
                node.push(entries)
            self.graph.step(t)
            last_t = t
        self.graph.end(last_t + 2)

    # the longest a single deferred device wave may reasonably take
    # (a wave can hold several cold compiles of a 2B decoder); past
    # this the drain raises instead of hanging silently
    _ASYNC_STALL_S = 900.0

    def _drain(self, sched, what: str) -> None:
        """Pump until fully drained; loud failure on both stall modes
        (pending-but-inadmissible forever, and an async hold whose
        future never resolves)."""
        stalls = 0
        last_progress = _time.monotonic()
        while not sched.fully_drained():
            if sched.pump():
                stalls = 0
                last_progress = _time.monotonic()
            elif sched.has_async():
                if _time.monotonic() - last_progress > self._ASYNC_STALL_S:
                    raise RuntimeError(
                        f"{what}: deferred device wave unresolved after "
                        f"{self._ASYNC_STALL_S:.0f}s"
                    )
                t0 = _time.perf_counter()
                _time.sleep(0.0005)
                if _obs.PLANE is not None:
                    _obs.PLANE.stage_seconds(
                        "idle", _time.perf_counter() - t0
                    )
            else:
                stalls += 1
                if stalls > 10_000:
                    raise RuntimeError(f"{what} stalled with undrained waves")

    def _wants_stage_overlap(self) -> bool:
        return any(
            isinstance(n, AsyncApplyNode) and n.is_async
            for n in self.graph.nodes
        )

    def _run_static_frontier(
        self, batches: list[tuple[int, InputNode, list[Entry]]]
    ) -> None:
        """Static batches through the frontier scheduler: each (time,
        node) wave is staged on its source and operators fire per-
        timestamp, so a deferred device dispatch of wave t (embed,
        generate) overlaps the staging and compute of wave t+1 — the
        serving pipeline the device plane is built around. Results are
        identical to one whole-graph wave per timestamp (same per-operator
        time order); only the interleaving differs.
        """
        sched = self._make_scheduler()
        sched.allow_async = True
        kicks = self._kick_sources(sched)
        tokens: dict[int, Any] = {}
        for t, node, entries in sorted(batches, key=lambda b: b[0]):
            tok = tokens.get(node.node_id)
            if tok is None:
                tok = tokens[node.node_id] = sched.add_source(node)
            sched.stage(tok, t, entries)
            if t > self.time:
                self.time = t + (t % 2)
        for tok in tokens.values():
            sched.close(tok)
        stalls = 0
        last_progress = _time.monotonic()
        while True:
            fired = sched.pump()
            self._stage_kicks(sched, kicks)
            sched.advance_local(self.time)
            if sched.fully_drained():
                if any(n._pending_convergence for n in kicks):
                    continue  # truncated convergence: keep kicking
                break
            if fired:
                stalls = 0
                last_progress = _time.monotonic()
            elif sched.has_async():
                if _time.monotonic() - last_progress > self._ASYNC_STALL_S:
                    raise RuntimeError(
                        "static frontier pump: deferred device wave "
                        f"unresolved after {self._ASYNC_STALL_S:.0f}s"
                    )
                t0 = _time.perf_counter()
                _time.sleep(0.0005)  # a deferred wave is still computing
                if _obs.PLANE is not None:
                    _obs.PLANE.stage_seconds(
                        "idle", _time.perf_counter() - t0
                    )
            else:
                stalls += 1
                if stalls > 10_000:
                    raise RuntimeError(
                        "static frontier pump stalled with undrained waves"
                    )
        self.graph.end(self.next_time())


class IterateNode(Node):
    """Incremental fixpoint iteration (reference: iterate dataflow.rs:3737,
    which runs the loop body in a nested product-timestamp scope).

    One PERSISTENT body graph lives across outer timestamps and
    iterations; every stateful operator inside it keeps its arrangement,
    so each round processes only deltas:

      * outer input deltas are pushed into the body's placeholder inputs;
      * per round, the feedback delta into an iterated placeholder is
        (capture's wave delta) ⊖ (what was pushed into that placeholder
        this round) — an O(changes) identity: with P the placeholder's
        accumulated collection and C = F(P) the capture state, the desired
        push is C ⊖ P, and after each previous push P equaled C, so the
        difference is exactly the new wave delta minus this round's push;
      * the loop stops when the feedback consolidates to nothing (P = C,
        the fixpoint) or `iteration_limit` rounds elapse.

    An input update therefore re-converges from the previous fixpoint in
    O(affected) work — e.g. one edge insert into pagerank touches only the
    vertices whose ranks actually move. The body is expected to be a
    convergent fixpoint (the reference's iterate contract); with a warm
    start, `iteration_limit` bounds the re-convergence rounds per update.
    """

    def __init__(
        self,
        graph: Graph,
        inputs: Sequence[Node],
        input_names: list[str],
        iterated_names: list[str],
        output_names: list[str],
        sub_graph: Graph,
        placeholder_nodes: dict[str, InputNode],
        captures: dict[str, "CaptureNode"],
        static_batches: list[tuple[int, InputNode, list[Entry]]],
        iteration_limit: int | None = None,
    ):
        super().__init__(graph, inputs)
        self.persist_signature = lambda: (  # type: ignore[method-assign]
            f"IterateNode/{input_names}/{iterated_names}/{output_names}"
            f"/{iteration_limit}/"
            + ",".join(n.persist_signature() for n in sub_graph.nodes)
        )
        self.input_names = input_names
        self.iterated_names = iterated_names
        self.output_names = output_names
        self.sub_graph = sub_graph
        self.placeholder_nodes = placeholder_nodes
        self.captures = captures
        self.static_batches = static_batches
        self.iteration_limit = iteration_limit
        self.out_nodes: dict[str, InputNode] = {}
        self.inner_t = 0
        # body-closure static batches not yet released (outer-time gated)
        self._pending_statics = sorted(static_batches, key=lambda b: b[0])
        # the sub-scope's frontier (engine/frontier.py ScopeFrontier):
        # outer times released into the body + the inner round watermark.
        # A non-quiescent scope holds its feedback capability — a limit-
        # truncated convergence left deltas queued in the placeholders —
        # and the runtime keeps scheduling waves through this node's
        # cone (kick source) until the capability drops.
        from pathway_tpu.engine.frontier import ScopeFrontier

        self.scope = ScopeFrontier()
        self._ended = False
        # capture-stream read positions (per output name)
        self._read_pos = {name: 0 for name in output_names}
        # mirror of each iterated placeholder's accumulated collection:
        # outer deltas arrive against the INPUT rows but the placeholder
        # holds the CONVERGED rows, so updates/retractions must be
        # translated onto the current iterate value per key (iterate
        # bodies are key-preserving — the reference requires the returned
        # iterated table to keep the input universe)
        self._fed = {name: KeyedState() for name in iterated_names}
        # Token plane (docs/iterate.md): the whole feedback loop —
        # translate, capture wave deltas, the C ⊖ P subtraction
        # (zs_difference) and per-round consolidation — runs on NativeBatch
        # flat arrays, matching the reference's typed nested-scope iterate
        # (dataflow.rs:3737). PATHWAY_ITERATE_NATIVE=0 kill switch keeps
        # today's object plumbing for bit-identical A/B; the object code
        # below doubles as the permanent demotion fallback (exotic rows).
        self._tok = iterate_native_on()
        self._ext: dict | None = None
        self._out_start: dict | None = None
        # boundary round-trip audit (tests/test_iterate_native.py): rows
        # this node's own plumbing interned/materialized, sampled from the
        # InternTable counter hooks, plus rows the WHOLE scope (body
        # operators included) decoded back to Python per round
        self.plane_stats = {
            "boundary_intern_rows": 0,
            "boundary_materialize_rows": 0,
            "scope_materialize_rows": 0,
            "rounds": 0,
        }
        if self._tok:
            from pathway_tpu.engine import native as _nat

            self._nat = _nat
            self._dp = _tok_plane()
            self._tab = self._dp.default_table()
            self._fed_tok: dict | None = {
                name: _nat.NativeKeyedState() for name in iterated_names
            }
            for cap in captures.values():
                cap.on_demote = self._capture_demoted
        else:
            self._fed_tok = None

    def set_output_node(self, name: str, node: InputNode) -> None:
        self.out_nodes[name] = node

    # The feedback capability, expressed as scope-frontier state: True
    # while a truncated convergence still holds deltas to push around
    # the loop. Kept as a (settable) property so operator snapshots and
    # the runtime's kick machinery read/write one source of truth.
    @property
    def _pending_convergence(self) -> bool:
        return not self.scope.quiescent

    @_pending_convergence.setter
    def _pending_convergence(self, value: bool) -> None:
        if value:
            self.scope.hold()
        else:
            self.scope.drop()


    # --------------------------------------------------- plane transitions

    def _capture_demoted(self, cap: "CaptureNode", bounds: list[int]) -> None:
        """A capture fell off the token plane mid-run (body emitted a
        plane-unrepresentable row): remap this scope's read positions
        through the materialization bounds and demote the whole scope —
        mixed-plane feedback bookkeeping is not worth its complexity."""
        for name, c in self.captures.items():
            if c is cap:
                self._remap_positions(name, bounds)
        self._demote_scope()

    def _remap_positions(self, name: str, bounds: list[int]) -> None:
        last = len(bounds) - 1
        pos = self._read_pos.get(name, 0)
        self._read_pos[name] = bounds[min(pos, last)]
        if self._out_start is not None and name in self._out_start:
            self._out_start[name] = bounds[min(self._out_start[name], last)]

    def _demote_scope(self) -> None:
        """One-way switch of the whole iterate scope to the object
        plumbing: captures materialize their logs (positions remapped),
        the fed mirrors decode, and any mid-wave external batches fall
        back to entry lists. Correctness never depends on the plane."""
        if not self._tok:
            return
        self._tok = False
        for name, cap in self.captures.items():
            if getattr(cap, "_tok", False):
                cap.on_demote = None
                self._remap_positions(name, cap.demote())
        if self._fed_tok is not None:
            for name, st in self._fed_tok.items():
                self._fed[name] = nks_decode(st, self._tab)
            self._fed_tok = None
        if self._ext:
            for name, v in list(self._ext.items()):
                if v is not None and type(v) is not list:
                    self._ext[name] = v.materialize()

    def _boundary(self, fn):
        """Run one piece of this node's own boundary plumbing with the
        InternTable round-trip counters sampled around it (the audit the
        acceptance test reads: zero on an all-native pipeline)."""
        tab = self._tab
        i0 = tab.stat_intern_rows
        m0 = tab.stat_materialize_rows
        try:
            return fn()
        finally:
            st = self.plane_stats
            st["boundary_intern_rows"] += tab.stat_intern_rows - i0
            st["boundary_materialize_rows"] += tab.stat_materialize_rows - m0

    # ------------------------------------------------- operator snapshots

    def persist_state(self) -> dict:
        # snapshots always export the OBJECT form (portable across the
        # kill switch and process restarts): fed mirrors decode, and read
        # positions are mapped onto each capture log's object form — the
        # same expansion CaptureNode.persist_state performs, so the pair
        # stays consistent.
        if self._tok:
            read_pos = dict(self._read_pos)
            fed = {}
            for name, cap in self.captures.items():
                if getattr(cap, "_tok", False):
                    _stream, bounds = cap._log_object_form()
                    last = len(bounds) - 1
                    if name in read_pos:
                        read_pos[name] = bounds[min(read_pos[name], last)]
            for name, st in (self._fed_tok or {}).items():
                fed[name] = nks_decode(st, self._tab)
        else:
            read_pos = self._read_pos
            fed = self._fed
        return {
            "inner_t": self.inner_t,
            "pending_statics": self._pending_statics_state(),
            "pending_convergence": self._pending_convergence,
            "read_pos": read_pos,
            "fed": fed,
            "sub": [n.persist_state() for n in self.sub_graph.nodes],
        }

    def _pending_statics_state(self) -> list:
        # static batch entries pickle in object form; node identity maps
        # by index (NativeBatch closures materialize — they are rare and
        # only survive until their scripted release time)
        idx = {id(n): i for i, n in enumerate(self.sub_graph.nodes)}
        return [
            (
                t,
                idx[id(node)],
                entries if type(entries) is list else entries.materialize(),
            )
            for (t, node, entries) in self._pending_statics
        ]

    def restore_state(self, st: dict) -> None:
        self.inner_t = st["inner_t"]
        self._pending_convergence = st["pending_convergence"]
        self._pending_statics = [
            (t, self.sub_graph.nodes[i], entries)
            for (t, i, entries) in st["pending_statics"]
        ]
        self._read_pos = st["read_pos"]
        self._fed = st["fed"]
        if self._tok and not self._encode_fed(st["fed"]):
            self._fed_tok = None
            self._demote_scope()
        for node, sub_st in zip(self.sub_graph.nodes, st["sub"]):
            if sub_st is not None:
                node.restore_state(sub_st)
        if self._tok and any(
            not getattr(c, "_tok", False) for c in self.captures.values()
        ):
            # a capture could not re-encode its snapshot: whole scope
            # follows it down (positions are already object-form here)
            self._demote_scope()

    def _encode_fed(self, fed: dict) -> bool:
        """Re-encode restored object-form fed mirrors into the C keyed
        stores; False when a row is not plane-representable."""
        new = {}
        for name in self.iterated_names:
            st = nks_encode(fed[name].rows, self._tab)
            if st is None:
                return False
            new[name] = st
        self._fed_tok = new
        return True

    # ------------------------------------------------------------- pumping

    def _translate(self, name: str, batch: list[Entry]) -> list[Entry]:
        """Map outer input deltas onto the iterated collection's current
        rows: an update restarts key k's iteration from its new input
        value; a retraction removes key k's converged row."""
        fed = self._fed[name]
        per_key: dict[Key, tuple | None] = {}
        for key, row, diff in batch:
            if diff > 0:
                per_key[key] = row
            else:
                per_key.setdefault(key, None)
        out: list[Entry] = []
        for key, new_row in per_key.items():
            cur = fed.get(key)
            if cur is not None:
                out.append((key, cur, -1))
            if new_row is not None:
                out.append((key, new_row, 1))
        out = consolidate(out)
        fed.update(out)
        return out

    def _translate_tok(self, name: str, nb):
        """Token twin of ``_translate``: per-key resolution over flat
        (key128, token) columns with the fed mirror queried in one C
        call — no row ever decodes to a tuple."""
        fed = self._fed_tok[name]
        kvs = _kvs_of(nb.key_lo, nb.key_hi)
        toks = nb.token.tolist()
        dfs = nb.diff.tolist()
        per: dict[int, int | None] = {}
        for i, kv in enumerate(kvs):
            if dfs[i] > 0:
                per[kv] = toks[i]
            else:
                per.setdefault(kv, None)
        u_kvs = list(per.keys())
        lo_u, hi_u = _kv_cols(u_kvs)
        old = fed.get(lo_u, hi_u).tolist()
        absent = (1 << 64) - 1
        o_kv: list[int] = []
        o_tok: list[int] = []
        o_diff: list[int] = []
        for j, kv in enumerate(u_kvs):
            cur = old[j] if old[j] != absent else None
            new = per[kv]
            if cur == new:
                continue  # unchanged row: the object plane consolidates
            if cur is not None:
                o_kv.append(kv)
                o_tok.append(cur)
                o_diff.append(-1)
            if new is not None:
                o_kv.append(kv)
                o_tok.append(new)
                o_diff.append(1)
        n = len(o_kv)
        lo, hi = _kv_cols(o_kv)
        out = self._dp.NativeBatch(
            self._tab, lo, hi,
            np.fromiter(o_tok, np.uint64, n),
            np.fromiter(o_diff, np.int64, n),
        )
        fed.update(out.key_lo, out.key_hi, out.token, out.diff)
        return out

    def _wave_delta(self, name: str) -> list[Entry]:
        """Capture-stream entries appended since the last read."""
        cap = self.captures[name]
        pos = self._read_pos.get(name, 0)
        new = cap.stream[pos:]
        self._read_pos[name] = len(cap.stream)
        return [(k, row, d) for (_t, k, row, d) in new]

    def _read_log(self, cap: "CaptureNode", pos: int):
        """Log items appended since `pos`, split by plane (order within
        each kind preserved — z-set math is commutative across them).
        Does NOT advance any read position."""
        batches: list = []
        entries: list[Entry] = []
        for item in cap.stream[pos:]:
            if len(item) == 4:
                _t, k, row, d = item
                entries.append((k, row, d))
            else:
                batches.append(item[1])
        return batches, entries

    def _wave_quad(self, cap: "CaptureNode", pos: int):
        """Log items since `pos` as one (lo, hi, tok, diff) array quad, or
        None when an object item is not plane-representable (caller
        demotes the scope). Boundary-audited."""
        batches, entries = self._read_log(cap, pos)
        if not batches and not entries:
            return np.empty(0, np.uint64), np.empty(0, np.uint64), \
                np.empty(0, np.uint64), np.empty(0, np.int64)
        return self._boundary(
            lambda: _wave_arrays(self._tab, batches, entries)
        )

    def _feedback_delta(self, name: str, external: dict):
        """One round's feedback for an iterated placeholder: the capture's
        new wave delta ⊖ this round's external push (the C ⊖ P identity
        from the class docstring). Returns a NativeBatch (token plane), an
        entry list (object plane), or None when the feedback is empty.
        Advances the capture read position and updates the fed mirror."""
        if self._tok:
            cap = self.captures[name]
            pos = self._read_pos.get(name, 0)
            ext = external.get(name)
            # convert a (rare) object-form external first: the demotion
            # paths below then run with the external dict intact
            e_quad = None
            if type(ext) is list and ext:
                e_quad = self._boundary(
                    lambda: _wave_arrays(self._tab, [], ext)
                )
                if e_quad is None:
                    self._demote_scope()
                    return self._feedback_obj(name, external)
            elif ext is not None and type(ext) is not list and len(ext):
                e_quad = (ext.key_lo, ext.key_hi, ext.token, ext.diff)
            quad = self._wave_quad(cap, pos)
            if quad is None:
                self._demote_scope()  # read position remapped, not consumed
                return self._feedback_obj(name, external)
            self._read_pos[name] = len(cap.stream)
            external[name] = []
            if e_quad is None:
                lo, hi, tok, diff = (a.copy() for a in quad)
                m = self._nat.consolidate_tokens(lo, hi, tok, diff)
            else:
                lo, hi, tok, diff = self._nat.difference_tokens(quad, e_quad)
                m = len(lo)
            if m == 0:
                return None
            fb = self._dp.NativeBatch(
                self._tab, lo[:m], hi[:m], tok[:m], diff[:m]
            )
            self._fed_tok[name].update(
                fb.key_lo, fb.key_hi, fb.token, fb.diff
            )
            return fb
        return self._feedback_obj(name, external)

    def _feedback_obj(self, name: str, external: dict):
        delta = self._wave_delta(name)
        ext = external.pop(name, [])
        if type(ext) is not list:  # demoted mid-wave with a token external
            ext = ext.materialize()
        external[name] = []
        feedback = consolidate(
            delta + [(k, row, -d) for (k, row, d) in ext]
        )
        if not feedback:
            return None
        self._fed[name].update(feedback)
        return feedback

    def _release_statics(self, time: int) -> bool:
        """Push body-closure static batches whose scripted time has come
        (outer and scripted times share the even-ms domain for static
        runs; streaming wall-clock times release everything at once).
        Advances the sub-scope frontier's outer coordinate: releases are
        keyed off the wave time, never past the node's input frontier —
        data for an earlier outer time can no longer arrive once the
        frontier passed it, so the release point is exactly the scope's
        input frontier restricted to the scripted domain."""
        released = False
        while self._pending_statics and self._pending_statics[0][0] <= time:
            _t, node, entries = self._pending_statics.pop(0)
            node.push(list(entries) if type(entries) is list else entries)
            released = True
        self.scope.release(time)
        return released

    def finish_time(self, time: int) -> None:
        raws = [self.take_segments(i) for i in range(len(self.input_names))]
        released = self._release_statics(time)
        has_input = any(b or e for b, e in raws)
        if not has_input and not released and not self._pending_convergence:
            return
        self._pending_convergence = False
        # External (outer) pushes put the placeholder out of sync with the
        # capture; they are compensated exactly once, in the first round's
        # feedback. Feedback pushes re-establish P = C, so from round 2 on
        # the feedback is the wave delta alone.
        external: dict[str, Any] = {name: [] for name in self.iterated_names}
        self._ext = external
        if self._tok and not self._push_inputs_tok(raws, external):
            self._demote_scope()  # outer rows not plane-representable
        if not self._tok:
            self._push_inputs_obj(raws, external)
        out_start = {name: self._read_pos[name] for name in self.output_names}
        self._out_start = out_start
        tab = self._tab if self._tok else None
        m0 = tab.stat_materialize_rows if tab is not None else 0
        rounds = 0
        while True:
            self.inner_t += 2
            self.scope.advance_round(self.inner_t)
            self.sub_graph.step(self.inner_t)
            rounds += 1
            quiescent = True
            for name in self.iterated_names:
                feedback = self._feedback_delta(name, external)
                if feedback is not None:
                    quiescent = False
                    self.placeholder_nodes[name].push(feedback)
            if quiescent:
                break
            if self.iteration_limit is not None and rounds >= self.iteration_limit:
                # the final feedback is already queued in the placeholders
                # (so P tracks C — the loop invariant survives truncation);
                # convergence resumes on the next wave
                self._pending_convergence = True
                break
        self.plane_stats["rounds"] += rounds
        if tab is not None:
            # whole-scope decode audit: rows ANY body operator pulled back
            # to Python during the fixpoint loop (zero = every round ran
            # on the token plane end to end; the acceptance gate)
            self.plane_stats["scope_materialize_rows"] += (
                tab.stat_materialize_rows - m0
            )
        # emit each output's net change over this outer timestamp
        self._emit_outputs(time, out_start)
        self._out_start = None
        self._ext = None
        # consumed capture prefixes are dead: truncate so memory and
        # checkpoint size track the live collection, not total history
        for name in self.output_names:
            cap = self.captures[name]
            if self._read_pos[name] == len(cap.stream):
                cap.stream.clear()
                self._read_pos[name] = 0

    def _push_inputs_obj(self, raws: list, external: dict) -> None:
        from pathway_tpu.engine.core import _flatten_segments

        for i, name in enumerate(self.input_names):
            b, e = raws[i]
            batch = _flatten_segments(b, e)
            if not batch:
                continue
            batch = consolidate(batch)
            if name in external:
                batch = self._translate(name, batch)
                external[name] = batch
            if batch:
                self.placeholder_nodes[name].push(batch)

    def _push_inputs_tok(self, raws: list, external: dict) -> bool:
        """Batch-first outer push: every input wave becomes ONE
        consolidated NativeBatch; iterated inputs translate through the C
        fed mirror. False (nothing pushed) when a wave holds a
        plane-unrepresentable row — the caller demotes and replays."""
        converted: list[tuple[str, Any]] = []
        for i, name in enumerate(self.input_names):
            b, e = raws[i]
            if not b and not e:
                continue
            quad = self._boundary(lambda b=b, e=e: _wave_arrays(self._tab, b, e))
            if quad is None:
                return False
            nb = self._dp.NativeBatch(
                self._tab,
                np.ascontiguousarray(quad[0]),
                np.ascontiguousarray(quad[1]),
                np.ascontiguousarray(quad[2]),
                np.ascontiguousarray(quad[3]),
            )
            if not nb.is_distinct_insert():
                nb = nb.consolidate()
            converted.append((name, nb))
        for name, nb in converted:
            if name in external:
                nb = self._boundary(lambda n=name, x=nb: self._translate_tok(n, x))
                external[name] = nb
            if nb is not None and len(nb):
                self.placeholder_nodes[name].push(nb)
        return True

    def _emit_outputs(self, time: int, out_start: dict) -> None:
        for name in self.output_names:
            cap = self.captures[name]
            out_node = self.out_nodes.get(name)
            if self._tok:
                quad = self._wave_quad(cap, out_start[name])
                if quad is None:
                    self._demote_scope()  # positions remapped; fall through
                else:
                    self._read_pos[name] = len(cap.stream)
                    if out_node is None or not len(quad[0]):
                        continue
                    lo, hi, tok, diff = (a.copy() for a in quad)
                    m = self._nat.consolidate_tokens(lo, hi, tok, diff)
                    if not m:
                        continue
                    out_node.push(
                        self._dp.NativeBatch(
                            self._tab, lo[:m], hi[:m], tok[:m], diff[:m]
                        )
                    )
                    # downstream of out_node runs later in topo order
                    # within this same wave (out_node was created after
                    # self)
                    out_node.finish_time(time)
                    continue
            delta = consolidate(
                [
                    (k, row, d)
                    for (_t, k, row, d) in cap.stream[out_start[name]:]
                ]
            )
            self._read_pos[name] = len(cap.stream)
            if out_node is not None and delta:
                out_node.push(delta)
                out_node.finish_time(time)

    def on_end(self, time: int) -> None:
        """End-of-stream: release any remaining closure statics, flush the
        body graph's own on_end behavior (buffers etc.), and run the loop
        to quiescence one final time. The emission happens in the
        finish_time that Graph.end calls right after this."""
        if self._ended:
            return
        self._ended = True
        released = False
        while self._pending_statics:
            _t, node, entries = self._pending_statics.pop(0)
            node.push(list(entries) if type(entries) is list else entries)
            released = True
        self.inner_t += 2
        self.scope.advance_round(self.inner_t)
        for node in self.sub_graph.nodes:
            node.on_end(self.inner_t)
        # did end-flushing produce anything to process?
        flushed = any(
            any(buf for buf in node.buffers) for node in self.sub_graph.nodes
        ) or any(n.pending for n in self.placeholder_nodes.values())
        if released or flushed:
            self._pending_convergence = True


class AsyncApplyNode(Node):
    """Async UDF application (reference: async_apply_table dataflow.rs:1442,
    MapWithConsistentDeletions operators.rs:308).

    Insertions run the (async) function — concurrently within a wave via an
    event loop; results are memoized per key so retractions retract exactly
    the value the insertion produced, even for non-deterministic functions.

    Stage overlap: under a frontier pump that allows it, an async wave is
    DEFERRED — the batch is submitted to the loop and the node returns
    without blocking, holding its outgoing watermark at the wave's time
    via ``FrontierScheduler.hold_async``. The pump keeps firing other
    admissible work (including this node's own later waves: that is the
    double buffer — wave t+1 stages/tokenizes while wave t computes on
    the device), and when the batch resolves the node fires again at the
    held time to emit. Under a scheduler without ``allow_async`` the wave
    runs to its end inside the fire (the synchronous branch below).
    """

    _state_routing = {"memo": "keytup"}  # memo keys are (key.value, row)

    def __init__(
        self,
        graph: Graph,
        inp: Node,
        fn: Callable[[Key, tuple], Any],
        is_async: bool,
        deterministic: bool = False,
    ):
        super().__init__(graph, [inp])
        self._persist_attrs = ("memo",)
        self.fn = fn
        self.is_async = is_async
        self.deterministic = deterministic
        self.memo: dict[tuple, Any] = {}
        # time -> (entries, concurrent Future[results dict]) for deferred
        # waves; never persisted — checkpoints cut at the global frontier,
        # which a hold keeps below any half-done wave
        self._inflight: dict[float, tuple[list, Any]] = {}

    def finish_time(self, time: int) -> None:
        held = self._inflight.pop(time, None)
        if held is not None:
            # completion pass: the deferred batch resolved (the scheduler
            # only re-fires a held time once its future is done)
            entries, fut = held
            try:
                results = fut.result()
            except Exception as e:  # noqa: BLE001 — per-row errors are
                # already caught inside the batch; this is a belt for
                # loop teardown races
                self.log_error(f"async apply: {type(e).__name__}: {e}")
                results = {}
            self._emit_resolved(time, entries, results)
            return
        entries = self.take_input()
        if not entries:
            return
        insertions = [(k, r) for k, r, d in entries if d > 0]
        sched = self.graph.scheduler
        if (
            self.is_async
            and sched is not None
            and getattr(sched, "allow_async", False)
            # a retraction-only wave behind an in-flight one must chain
            # through the same hold queue: its tokens may be exactly the
            # values the earlier wave is still computing (emitting ERROR
            # for them would poison downstream arrangements)
            and (insertions or self._inflight)
        ):
            fut = _submit_async_batch(self.fn, insertions, self.graph)
            self._inflight[time] = (entries, fut)
            sched.hold_async(self, time, lambda t=time: self._hold_done(t))
            return
        results: dict[tuple, Any] = {}
        if insertions:
            if self.is_async:
                results = _run_async_batch(self.fn, insertions, self.graph)
            else:
                for k, r in insertions:
                    try:
                        results[(k.value, freeze_row(r))] = self.fn(k, r)
                    except Exception as e:  # noqa: BLE001
                        self.log_error(f"apply: {type(e).__name__}: {e}")
                        results[(k.value, freeze_row(r))] = ERROR
        self._emit_resolved(time, entries, results)

    def _hold_done(self, time: float) -> bool:
        """A deferred wave releases only when its batch resolved AND it
        is the EARLIEST in-flight wave: computes overlap freely, but
        emissions (and with them the memo the retraction path reads)
        stay in per-operator time order."""
        held = self._inflight.get(time)
        if held is None:
            return True
        return held[1].done() and min(self._inflight) >= time

    def _emit_resolved(
        self, time: int, entries: list[Entry], results: dict[tuple, Any]
    ) -> None:
        out: list[Entry] = []
        for key, row, diff in entries:
            token = (key.value, freeze_row(row))
            if diff > 0:
                value = results.get(token, self.memo.get(token, ERROR))
                if not self.deterministic:
                    self.memo[token] = value
            else:
                if token in self.memo:
                    value = self.memo.pop(token)
                elif token in results:
                    value = results[token]
                elif self.deterministic:
                    # recompute for retraction — allowed for deterministic fns;
                    # async fns (every batched=True UDF) must go through the
                    # loop or the "value" would be a bare coroutine object
                    if self.is_async:
                        value = _run_async_batch(
                            self.fn, [(key, row)], self.graph
                        ).get(token, ERROR)
                    else:
                        try:
                            value = self.fn(key, row)
                        except Exception as e:  # noqa: BLE001
                            self.log_error(f"apply: {type(e).__name__}: {e}")
                            value = ERROR
                else:
                    value = ERROR
            out.append((key, row + (value,), diff))
        self.emit(time, consolidate(out))


_async_loop: asyncio.AbstractEventLoop | None = None
_async_loop_lock = _lockgraph.register_lock(
    "runtime.async_loop", threading.Lock()
)


def _get_async_loop() -> asyncio.AbstractEventLoop:
    """Dedicated event-loop thread (reference: graph_runner/async_utils.py)."""
    global _async_loop
    with _async_loop_lock:
        if _async_loop is None or _async_loop.is_closed():
            loop = asyncio.new_event_loop()

            def run() -> None:
                asyncio.set_event_loop(loop)
                loop.run_forever()

            threading.Thread(target=run, daemon=True, name="pw-async-loop").start()
            _async_loop = loop
    return _async_loop


def _submit_async_batch(
    fn: Callable, insertions: list[tuple[Key, tuple]], graph: Graph
):
    """Start a wave's row coroutines on the loop; returns a concurrent
    Future resolving to {(key, row): value}. The caller decides whether
    to block (`_run_async_batch`) or defer (stage overlap)."""
    loop = _get_async_loop()
    # REST requests in flight, by their row's key (one test a wave: rows
    # of an ingest look nothing up while no request is open)
    clocks = _obs.CLOCKS or None

    async def one(k: Key, r: tuple) -> Any:
        clock = clocks.get(k.value) if clocks is not None else None
        if clock is not None:
            # the row has left the pump and the waves behind its staging;
            # what follows is stamped by whoever does it (the embedder, the
            # index, the batcher, the answering UDF), through the clock
            # that `one`, a task of its own, carries from here
            if clock.staged_only():
                clock.stamp(_obs.STAGE_INGRESS)
            _obs.clocked(clock)
        try:
            res = fn(k, r)
            if asyncio.iscoroutine(res):
                res = await res
            return res
        except Exception as e:  # noqa: BLE001
            graph.log_error(f"async apply: {type(e).__name__}: {e}")
            return ERROR

    async def batch() -> dict[tuple, Any]:
        values = await asyncio.gather(*[one(k, r) for k, r in insertions])
        return {
            (k.value, freeze_row(r)): v
            for (k, r), v in zip(insertions, values)
        }

    return asyncio.run_coroutine_threadsafe(batch(), loop)


def _run_async_batch(
    fn: Callable, insertions: list[tuple[Key, tuple]], graph: Graph
) -> dict[tuple, Any]:
    return _submit_async_batch(fn, insertions, graph).result()


class OutputNode(Node):
    """Sink: formats consolidated batches and hands them to a writer callback
    with retries (reference: output_table dataflow.rs:3542, OUTPUT_RETRIES=5).

    The retry loop rides the unified ``pw.io.RetryPolicy`` (same default
    timings as the old hand-rolled loop: 5 attempts, 10 ms apart), which
    makes every sink fault-injectable at ``io.retry.sink``.

    Exactly-once mode (persistence attached + PATHWAY_EXACTLY_ONCE!=0):
    ``attach_outbox`` reroutes every wave into a per-sink transactional
    outbox WAL (io/outbox.py) — writes happen at checkpoint fences,
    after the epoch's metadata commit sealed them, and the writer close
    waits for the final ack. ``write_keyed`` (optional) is the
    idempotent delivery surface: like ``write_batch`` plus a per-record
    content-key list for consumer-side dedup of replays; ``txn``
    (optional) carries a sink's atomic-commit hooks (the fs writer's
    offset-named temp+fsync+rename segments)."""

    RETRIES = 5

    def __init__(
        self,
        graph: Graph,
        inp: Node,
        write_batch: Callable[[int, list[Entry]], None],
        flush: Callable[[], None] | None = None,
        close: Callable[[], None] | None = None,
        write_native: Callable[[int, Any], None] | None = None,
        retry_policy: Any = None,
        write_keyed: Callable[[int, list[Entry], list], None] | None = None,
        txn: dict | None = None,
    ):
        super().__init__(graph, [inp])
        self.write_batch = write_batch
        self.flush = flush
        self.close = close
        # optional token-resident fast path: write_native(time, NativeBatch)
        # formats whole batches in C (e.g. the csv writer); sinks without
        # it get materialized entries as before
        self.write_native = write_native
        self.write_keyed = write_keyed
        self.txn = txn
        self._outbox: Any = None
        self._closed = False
        if retry_policy is None:
            # lazy import: pathway_tpu.io's package init imports modules
            # that import this one
            from pathway_tpu.io._retry import RetryPolicy

            retry_policy = RetryPolicy(
                "sink",
                max_attempts=self.RETRIES,
                initial_delay_ms=10,
                backoff_factor=1.0,
                jitter_ms=0,
                breaker_threshold=None,
            )
        self.retry_policy = retry_policy

    def _write_retrying(self, fn, time: int, payload) -> None:
        def attempt() -> None:
            fn(time, payload)
            if self.flush is not None:
                self.flush()

        try:
            self.retry_policy.call(attempt)
        except Exception as e:  # noqa: BLE001 — a sink must not kill the pump
            self.log_error(
                f"output failed after "
                f"{self.retry_policy.max_attempts} retries: {e}"
            )

    def attach_outbox(self, outbox: Any) -> None:
        """Switch to transactional staging: waves journal to the outbox
        WAL; delivery happens at epoch fences (io/outbox.py)."""
        self._outbox = outbox
        if self.txn and self.txn.get("enable") is not None:
            self.txn["enable"]()

    def finish_time(self, time: int) -> None:
        if self._outbox is not None:
            # exactly-once: stage in object form (the WAL's codec
            # domain); the native formatting fast path is a direct-write
            # optimization and does not apply to journaled delivery
            entries = self.take_input()
            if entries:
                self._outbox.stage(time, consolidate(entries))
            return
        if self.write_native is not None:
            batches, entries = self.take_segments()
            for b in batches:
                if not b.is_distinct_insert():
                    b = b.consolidate()
                self._write_retrying(self.write_native, time, b)
            if entries:
                self._write_retrying(self.write_batch, time, consolidate(entries))
            return
        entries = self.take_input()
        if not entries:
            return
        self._write_retrying(self.write_batch, time, consolidate(entries))

    def on_end(self, time: int) -> None:
        if self._outbox is not None:
            # the final wave is staged but not yet sealed: the runtime's
            # end-of-stream checkpoint delivers it, and the outbox closes
            # the writer after that ack (CheckpointManager.close)
            return
        if not self._closed and self.close is not None:
            self._closed = True
            self.close()
