"""Mesh supervisor: restart-the-mesh-from-checkpoint recovery.

A multi-process run (engine/runtime.py ``run_mesh``) detects a dead peer
on its wires and aborts with :class:`~pathway_tpu.parallel.process_mesh.
WorkerLost` instead of hanging — but *something* has to restart the job.
That something is this supervisor: it owns the worker processes of one
mesh, watches for any worker dying (injected crash, OOM-kill, WorkerLost
abort), and restarts the WHOLE generation. On restart the workers
re-negotiate the minimum committed checkpoint epoch across the mesh
(persistence/__init__.py allgather) and resume from it, so the job's
final output is identical to a crash-free run whenever the pipeline's
sources are journaled or seekable.

The whole-generation restart is deliberate: surviving workers hold
operator state *ahead* of the last committed epoch, and exchange wires
carry waves a rejoining worker never saw — a partial restart would need
distributed wave replay. Restarting the mesh from the agreed epoch is
the reference engine's model too (every worker rebuilds from
metadata → snapshots → journal tail).

By default restarted generations run with ``PATHWAY_FAULTS=0``: a
schedule is hit-count deterministic, so re-running it verbatim would
re-fire the same crash every generation. Pass
``faults_after_restart=`` to keep chaos flowing across restarts.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Sequence

__all__ = ["SupervisedMeshFailed", "chip_env", "run_supervised"]


class SupervisedMeshFailed(RuntimeError):
    """The mesh kept failing past ``max_restarts`` generations."""


def _local_tpu_chip_count() -> int:
    """How many TPU device files this host has (``/dev/vfio/N`` on v5e,
    ``/dev/accelN`` before it), read without touching JAX: the launcher
    must not open the device its workers need. Only the count is used:
    the numbers in the names are IOMMU group ids, not chip indices."""
    found = 0
    for directory, prefix in (("/dev/vfio", ""), ("/dev", "accel")):
        try:
            names = os.listdir(directory)
        except OSError:
            continue
        found += sum(
            1 for name in names
            if name.startswith(prefix) and name[len(prefix):].isdigit()
        )
    return found


def chip_env(
    pid: int, n: int, first_port: int, env: dict[str, str]
) -> dict[str, str]:
    """What worker ``pid`` of ``n`` adds to its environment to own one
    TPU chip. A chip belongs to one process at a time, so ``n`` workers
    started with one environment all reach for the same chip and every
    one but the first dies at backend start-up ("Unable to initialize
    backend 'tpu' ... libtpu multi-process lockfile" — measured on a
    one-chip v5e). With at least ``n`` chips on the host, worker k is
    bound to chip index k as a one-chip process of its own, on the port
    after the mesh's own (``first_port`` .. ``first_port + n - 1``, see
    process_mesh.py), so two launches that differ in ``first_port`` do
    not meet; with fewer chips the environment is left alone and the
    worker that loses the chip fails by that name — it neither hangs nor
    continues on the CPU. A run that is held to the CPU, or that already
    places its workers (``TPU_VISIBLE_CHIPS`` and friends set by the
    caller), is left alone too."""
    if n <= 1 or "tpu" not in (env.get("JAX_PLATFORMS") or "tpu"):
        return {}
    if any(
        k in env
        for k in ("TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES", "TPU_PROCESS_BOUNDS")
    ):
        return {}
    if _local_tpu_chip_count() < n:
        return {}
    port = first_port + n + pid
    return {
        "TPU_VISIBLE_CHIPS": str(pid),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
        # libtpu's one-process-per-host lockfile does not know about
        # disjoint chip sets
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def _spawn(
    argv: Sequence[str], n: int, first_port: int, env: dict[str, str]
) -> list[tuple[subprocess.Popen, Any]]:
    """Start the generation's workers. stdout/stderr go to unlinked spill
    files, NOT pipes: nobody drains a pipe while workers run, so a chatty
    worker (breaker warnings, chaos logging) would fill the ~64KB buffer,
    block on write, and stall the mesh until the overall timeout."""
    procs = []
    for pid in range(n):
        penv = {
            **env,
            **chip_env(pid, n, first_port, env),
            "PATHWAY_PROCESSES": str(n),
            "PATHWAY_PROCESS_ID": str(pid),
            "PATHWAY_FIRST_PORT": str(first_port),
        }
        spill = tempfile.TemporaryFile(mode="w+", prefix=f"pw-sup-{pid}-")
        procs.append(
            (
                subprocess.Popen(
                    list(argv),
                    env=penv,
                    stdout=subprocess.DEVNULL,
                    stderr=spill,
                    text=True,
                ),
                spill,
            )
        )
    return procs


def _reap(procs: list[tuple[subprocess.Popen, Any]]) -> list[str]:
    """Kill survivors, wait everyone, return per-worker stderr."""
    for p, _spill in procs:
        if p.poll() is None:
            p.kill()
    errs = []
    for p, spill in procs:
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        try:
            spill.seek(0)
            errs.append(spill.read())
        except (OSError, ValueError):
            errs.append("")
        finally:
            spill.close()
    return errs


def run_supervised(
    argv: Sequence[str],
    n_processes: int,
    first_port: int,
    *,
    max_restarts: int = 3,
    env: dict[str, str] | None = None,
    faults_after_restart: str = "0",
    poll_s: float = 0.1,
    timeout_s: float = 600.0,
    state_dir: str | None = None,
) -> dict[str, Any]:
    """Run ``argv`` as an ``n_processes`` mesh until every worker exits 0,
    restarting the whole mesh (same ports, same persistence roots) after
    any worker death. Returns ``{"generations": g, "stderr": [...],
    "rebalances": r, "members": n}`` of the successful generation; raises
    :class:`SupervisedMeshFailed` after ``max_restarts`` failed
    generations and :class:`TimeoutError` on the overall deadline.

    ``state_dir`` (the SHARED persistence root the workers put their
    ``proc-N`` roots under) switches on elastic membership (parallel/
    membership.py, unless ``PATHWAY_ELASTIC=0``): join/leave intents
    announced under ``state_dir/control/`` are folded into a pending
    membership record, the running generation is asked to quiesce to a
    checkpoint fence, and when every worker exits with the planned
    rebalance code the mesh respawns at the new size — without spending
    restart budget, because nothing failed."""
    from pathway_tpu.engine import device_plane as _dp
    from pathway_tpu.internals import observability as obs
    from pathway_tpu.parallel import membership as _mb

    # supervisor-side black box: generation lifecycles land in the flight
    # recorder (workers dump their own rings when they crash; this is the
    # restart-decision record that stitches those dumps together)
    obs.maybe_enable_from_env()
    base_env = {**os.environ, **(env or {})}
    deadline = time.monotonic() + timeout_s
    failures: list[str] = []
    elastic = state_dir is not None and _mb.elastic_enabled()
    n = n_processes
    if elastic:
        # finish any rebalance that crashed mid-commit, then honour the
        # committed membership record over the caller's initial size
        _mb.recover_rebalance(state_dir)
        rec = _mb.load_membership(state_dir)
        if rec is not None:
            n = int(
                rec["n"] if rec.get("rebalanced") else rec.get("prev_n", n)
            )
    generation = 0
    rebalances = 0
    while len(failures) <= max_restarts:
        gen_env = dict(base_env)
        if generation > 0:
            gen_env["PATHWAY_FAULTS"] = faults_after_restart
        procs = _spawn(argv, n, first_port, gen_env)
        if obs.PLANE is not None:
            obs.PLANE.metrics.gauge(
                "pathway_mesh_members", n,
                help="mesh size after the last committed rebalance",
            )
        failed: str | None = None
        rebalanced = False
        while True:
            if time.monotonic() > deadline:
                _reap(procs)
                raise TimeoutError(
                    f"supervised mesh did not finish within {timeout_s:.0f}s "
                    f"(generation {generation})"
                )
            codes = [p.poll() for p, _spill in procs]
            benign = (None, 0, _mb.REBALANCE_EXIT)
            if any(c not in benign for c in codes):
                dead = [i for i, c in enumerate(codes) if c not in benign]
                # one worker died: the survivors observe WorkerLost on
                # their wires and exit on their own — kill + wait the
                # stragglers to reclaim the ports for the next generation
                errs = _reap(procs)
                obs.record(
                    "supervisor.restart", generation=generation,
                    dead_workers=dead,
                    exit_codes=[codes[i] for i in dead],
                )
                failed = (
                    f"generation {generation}: worker(s) {dead} exited "
                    f"{[codes[i] for i in dead]}"
                )
                for i, err in enumerate(errs):
                    if err.strip():
                        failed += f"\n-- worker {i} stderr --\n{err[-2000:]}"
                break
            if all(c is not None for c in codes):
                if any(c == _mb.REBALANCE_EXIT for c in codes):
                    # planned generation boundary, not a failure
                    rebalanced = True
                    _reap(procs)
                    break
                if generation > 0:
                    # restarts happened: leave the decision record beside
                    # the workers' own crash dumps
                    obs.record(
                        "supervisor.recovered", generations=generation + 1,
                    )
                    obs.dump_flight("supervisor")
                return {
                    "generations": generation + 1,
                    "stderr": _reap(procs),
                    "rebalances": rebalances,
                    "members": n,
                }
            if elastic and not _mb.quiesce_requested(state_dir):
                joins, leaves = _mb.pending_intents(state_dir)
                if joins or leaves:
                    planned = _mb.plan_membership(state_dir, n)
                    if planned != n:
                        _mb.request_quiesce(state_dir)
                        obs.record(
                            "supervisor.quiesce_requested",
                            members=n, planned=planned,
                        )
            time.sleep(poll_s)
        # a fresh generation must not inherit the dead one's device-plane
        # quarantines: its failures died with its processes
        _dp.reset_quarantines()
        if rebalanced:
            # process 0 rebalanced the roots (or refused and reverted)
            # before exiting; roll forward if it crashed mid-commit and
            # respawn at whatever the membership record now says
            if elastic:
                _mb.recover_rebalance(state_dir)
                rec = _mb.load_membership(state_dir) or {}
                new_n = int(rec.get("n", n)) if rec.get("rebalanced") else n
                if new_n != n:
                    rebalances += 1
                    obs.record(
                        "supervisor.rebalanced", members=new_n, was=n,
                        generation=generation,
                    )
                n = new_n
            generation += 1
            continue
        failures.append(failed or "unknown failure")
        generation += 1
    obs.record("supervisor.gave_up", generations=len(failures))
    obs.dump_flight("supervisor")
    raise SupervisedMeshFailed(
        f"mesh failed {len(failures)} generations:\n" + "\n".join(failures)
    )


def main() -> int:
    """CLI shim: ``python -m pathway_tpu.parallel.supervisor N PORT -- cmd...``"""
    args = sys.argv[1:]
    if "--" not in args or len(args) < 4:
        print(
            "usage: python -m pathway_tpu.parallel.supervisor "
            "<n_processes> <first_port> [max_restarts] -- <cmd> [args...]",
            file=sys.stderr,
        )
        return 2
    split = args.index("--")
    head, argv = args[:split], args[split + 1:]
    n, port = int(head[0]), int(head[1])
    restarts = int(head[2]) if len(head) > 2 else 3
    out = run_supervised(
        argv, n, port, max_restarts=restarts,
        state_dir=os.environ.get("PATHWAY_STATE_DIR") or None,
    )
    print(f"supervised mesh ok after {out['generations']} generation(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
