#!/usr/bin/env python
"""Run the test suite on BOTH execution planes and record the result.

Leg 1 (native): the default token-plane engine (C dataplane + numpy waves).
Leg 2 (object): PATHWAY_TPU_NATIVE=0 — pure-Python object rows; tests that
assert native-plane internals skip themselves via `dataplane.available()`.
Leg 3 (workers-1x4): the worker-count invariance suite under BOTH
PATHWAY_THREADS=1 and =4 in the same leg — sharded-operator exchange and
the frontier scheduler's out-of-order firing must keep results
worker-count invariant (pins frontier-reordering regressions).
Leg 4 (chaos-quick): the fast crash-recovery equivalence drill
(scripts/chaos_drill.py --quick, 4 fault kinds x 1 seed) — a crashed,
torn, flapped, or degraded run must recover to output byte-identical to
the fault-free baseline (docs/robustness.md).
Leg 5 (iterate-object): the iterate equivalence suite with the
token-resident scope's kill switch thrown (PATHWAY_ITERATE_NATIVE=0) on
the otherwise-native engine — the object plumbing must stay
byte-identical to the token plane (docs/iterate.md). The token side of
the same suite already runs inside legs 1-2.
Leg 6 (observability): the engine suites with full instrumentation on
(PATHWAY_OBSERVABILITY=1) — wave tracing, metrics and the flight
recorder must be result-invariant (docs/observability.md); the A/B
byte-identical pipeline check itself lives in
tests/test_observability_plane.py::test_instrumentation_is_result_invariant.
Leg 8 (ann): the indexing suites with the ANN kill switch thrown
(PATHWAY_ANN=0) — every IVF-PQ-configured retriever must drop back to
the exact slab search with byte-identical ranking semantics
(docs/retrieval.md); the ANN-on side of the same suites already runs
inside legs 1-2.
Leg 9 (fusion-off): the engine suites with the plan optimizer killed
(PATHWAY_FUSE=0) — chain fusion, pushdowns, id elision and the adaptive
policy all bypassed; the unoptimized lowering must stay byte-identical
to what it was before the optimizer existed (docs/planner.md). The
optimizer-on side runs inside legs 1-2, and the per-pipeline fused-vs-
unfused A/B comparisons live in tests/test_plan_optimizer.py.
Legs 10-11 (exactly-once A/B): the io + chaos suites and the quick
chaos drill with the transactional sink outbox killed
(PATHWAY_EXACTLY_ONCE=0) — sinks must reproduce the pre-outbox direct
per-wave writes (the at-least-once rung of docs/robustness.md's
exactly-once ladder) byte-identically; sink-side fault kinds skip
themselves (their injection points never probe). The exactly-once side
of the same suites — outbox staging/seal/replay, atomic fs segments,
content-keyed dedup, delivered-output equivalence across the sink crash
windows — already runs inside legs 1-2 and the leg-5 chaos drill.
Leg 12 (multichip-dryrun): the sharded column plane FORCED ON
(PATHWAY_DEVICE_EXCHANGE=1) over the virtual 8-device mesh
(tests/conftest.py's XLA_FLAGS) — every NativeBatch exchange in the
column-plane, exchange and worker-invariance suites rides the compiled
all_to_all collective on a CPU-only host, and results must stay
byte-identical to the host wire (docs/parallelism.md §3).

Leg 13 (lint): ``python -m pathway_tpu.analysis.lint`` — the AST rule
suite encoding paid-for bug classes (hot-path env reads, swallowed I/O
errors, jit-under-lock, outbox bypass; docs/static-analysis.md) must be
green over the package; any violation exits nonzero so regressions
can't land silently.
Leg 14 (lock-order): the tier-1 suite under PATHWAY_LOCK_CHECK=1 — every
registered engine lock records its acquisition-order edges, and a cycle
in the merged graph (the PR 7/PR 8 ABBA deadlock precondition) fails
the process at exit via the lockgraph atexit gate (rc 86).
Leg 15 (chaos-quick-lockcheck): the quick chaos drill with the
lock-order recorder on — crash/recovery generations and fault paths
must stay cycle-free too (each workload subprocess carries its own
exit gate).
Leg 16 (megakernel-off): the engine + plan suites with the wave cone
killed (PATHWAY_MEGAKERNEL=0) — every wave fires per-node, the
byte-identity baseline the single-dispatch cone is pinned against
(docs/megakernel.md); the cone-on side runs inside legs 1-2 and the
per-pipeline A/B comparisons live in tests/test_megakernel.py.
Leg 17 (spill-off): the stateful-operator suites with the out-of-core
state tier killed (PATHWAY_SPILL=0) — join/groupby arrangements stay
fully resident and must be byte-identical to the spill-enabled default
(docs/persistence.md §out-of-core); the spill-on side (tiny-budget A/B,
probe ladder, compaction, manifest checkpoints) lives in
tests/test_spill.py and runs inside legs 1-2.
Leg 18 (morsel-off): the scan/wave suites with morsel-driven execution
killed (PATHWAY_MORSEL=0) — whole-chunk parses, one future per replica,
no stealing; the byte-identity baseline the morsel/steal path is pinned
against (docs/parallelism.md). The morsel-on A/B matrix and the seeded
straggler-determinism harness live in tests/test_morsel.py and run
inside legs 1-2.
Leg 19 (elastic-off): the supervision/recovery suites with elastic mesh
membership killed (PATHWAY_ELASTIC=0) — join/leave intents ignored, no
quiesce fence, no rebalance, no blue/green swap machinery; supervised
runs must behave exactly like the pre-elastic static mesh
(docs/robustness.md §elasticity). The elastic-on side — rebalance A/B
vs a static mesh, swap gates, crash roll-forward — lives in
tests/test_elastic.py and runs inside legs 1-2 plus the chaos drill's
elastic kinds.
Leg 20 (ann-tiered-off): the index suites with tiered ANN storage
killed (PATHWAY_ANN_TIERED=0) — tier-configured IVF-PQ indexes stay
all-resident, the byte-identity baseline the hot/warm/cold hierarchy
is pinned against (docs/retrieval.md §tier lifecycle); the tiered-on
side — placement, migration-vs-churn races, checkpoint shrink, the
index-tier verifier contract, reranking — lives in
tests/test_index_tiers.py and runs inside legs 1-2.

Writes TESTLEGS.json at the repo root: the artifact proving the legs ran
green on this checkout (VERDICT round-4 item: the equivalence leg must be
a real, runnable thing, not a docstring claim).

Usage: python scripts/test_both_planes.py [extra pytest args]
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the worker-count invariance surface: sharded-state pipelines, the
# frontier scheduler, and rescale (state re-partitioning across counts)
INVARIANCE_PATHS = [
    "tests/test_workers.py",
    "tests/test_frontier.py",
    "tests/test_rescale.py",
    "tests/test_tok_tail.py",
]


def run_leg(
    name: str, env_extra: dict, extra: list[str], paths: list[str] | None = None
) -> dict:
    env = dict(os.environ)
    env.update(env_extra)
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, "-m", "pytest", *(paths or ["tests/"]), "-q", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=3600,
    )
    tail = (r.stdout.strip().splitlines() or [""])[-1]
    m = re.search(r"(\d+) passed", tail)
    s = re.search(r"(\d+) skipped", tail)
    f = re.search(r"(\d+) failed", tail)
    leg = {
        "leg": name,
        "rc": r.returncode,
        "passed": int(m.group(1)) if m else 0,
        "skipped": int(s.group(1)) if s else 0,
        "failed": int(f.group(1)) if f else 0,
        "seconds": round(time.time() - t0, 1),
        "summary": tail,
    }
    # name the failures: later legs overwrite the pytest cache, so the
    # record here is the only trace of WHICH test failed in this leg
    fails = re.findall(r"^(?:FAILED|ERROR) (\S+)", r.stdout, re.MULTILINE)
    if fails:
        leg["failures"] = fails
    print(f"[{name}] {tail}")
    for t in fails:
        print(f"[{name}]   FAILED {t}")
    return leg


def run_chaos_leg(name: str = "chaos-quick", env_extra: dict | None = None) -> dict:
    """The --quick equivalence drill as its own leg: subprocess-driven
    (the drill spawns workload processes itself), JSON-report parsed."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PATHWAY_FAULTS": "0",
           **(env_extra or {})}
    report_path = os.path.join(REPO, f".{name.replace('-', '_')}_report.json")
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, "scripts/chaos_drill.py", "--quick",
         "--json", report_path],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    cases = equivalent = 0
    try:
        with open(report_path) as fh:
            rep = json.load(fh)
        cases = len(rep.get("cases", []))
        equivalent = sum(1 for c in rep["cases"] if c.get("equivalent"))
        os.unlink(report_path)
    except (OSError, ValueError, KeyError):
        pass
    tail = (r.stdout.strip().splitlines() or [""])[-1]
    leg = {
        "leg": name,
        "rc": r.returncode,
        "passed": equivalent,
        "skipped": 0,
        "failed": cases - equivalent,
        "seconds": round(time.time() - t0, 1),
        "summary": tail,
    }
    print(f"[{name}] {tail}")
    return leg


def run_lint_leg() -> dict:
    """The repo lint as its own leg: nonzero on ANY violation."""
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis.lint"],
        cwd=REPO, env=dict(os.environ), capture_output=True, text=True,
        timeout=600,
    )
    tail = (r.stdout.strip().splitlines() or [""])[-1]
    m = re.search(r"(\d+) violation", tail)
    violations = int(m.group(1)) if m else -1
    leg = {
        "leg": "lint",
        "rc": r.returncode,
        # "passed" carries the green-file signal for the all-legs gate
        "passed": 1 if r.returncode == 0 else 0,
        "skipped": 0,
        "failed": violations if violations > 0 else (0 if r.returncode == 0 else 1),
        "seconds": round(time.time() - t0, 1),
        "summary": tail,
    }
    print(f"[lint] {tail}")
    return leg


def main() -> int:
    extra = sys.argv[1:]
    legs = [
        run_leg("native", {}, extra),
        run_leg("object", {"PATHWAY_TPU_NATIVE": "0"}, extra),
        # worker-count invariance at BOTH default thread counts in one
        # leg: the suites flip PATHWAY_THREADS per pipeline internally,
        # and the session default is ALSO varied so every other node in
        # those files builds sharded vs unsharded — frontier reordering
        # must not leak into results either way
        run_leg("workers-t1", {"PATHWAY_THREADS": "1"}, extra, INVARIANCE_PATHS),
        run_leg("workers-t4", {"PATHWAY_THREADS": "4"}, extra, INVARIANCE_PATHS),
        run_chaos_leg(),
        run_leg(
            "iterate-object", {"PATHWAY_ITERATE_NATIVE": "0"}, extra,
            [
                "tests/test_iterate_native.py",
                "tests/test_iterate.py",
                "tests/test_iterate_matrix.py",
                "tests/test_graphs.py",
            ],
        ),
        # full instrumentation on: wave tracing + metrics + flight ring
        # must not change any engine result (the dedicated A/B
        # byte-identical pipeline test is in test_observability_plane.py)
        run_leg(
            "observability", {"PATHWAY_OBSERVABILITY": "1"}, extra,
            [
                "tests/test_observability_matrix.py",
                "tests/test_observability_plane.py",
                "tests/test_frontier.py",
                "tests/test_workers.py",
            ],
        ),
        # ANN kill switch thrown: IVF-PQ retrievers must reproduce the
        # exact slab rankings byte-identically across the index stack
        run_leg(
            "ann", {"PATHWAY_ANN": "0"}, extra,
            [
                "tests/test_ann_index.py",
                "tests/test_indexing.py",
                "tests/test_indexing_relevance.py",
                "tests/test_vector_store.py",
                "tests/test_ml.py",
            ],
        ),
        # tiered index storage killed: tier-configured indexes stay
        # all-resident, the byte-identity baseline the hot/warm/cold
        # hierarchy is pinned against
        # (tests/test_index_tiers.py::test_tiered_off_is_byte_identical)
        run_leg(
            "ann-tiered-off", {"PATHWAY_ANN_TIERED": "0"}, extra,
            [
                "tests/test_index_tiers.py",
                "tests/test_ann_index.py",
                "tests/test_indexing.py",
                "tests/test_vector_store.py",
            ],
        ),
        # plan optimizer killed: the unoptimized lowering is the
        # byte-identity baseline every optimizer pass is pinned against
        run_leg(
            "fusion-off", {"PATHWAY_FUSE": "0"}, extra,
            [
                "tests/test_plan_optimizer.py",
                "tests/test_common.py",
                "tests/test_table_ops_matrix.py",
                "tests/test_join_matrix.py",
                "tests/test_io_formats.py",
                "tests/test_filters.py",
                "tests/test_expression_matrix.py",
                "tests/test_native_plane.py",
            ],
        ),
        # transactional sink outbox killed: the direct per-wave write
        # path (at-least-once) must be byte-identical to pre-outbox
        # behavior across the io + chaos suites, and the drill must
        # still prove crash-recovery equivalence for the engine-side
        # kinds (sink kinds skip — their injection points never probe)
        run_leg(
            "exactly-once-off", {"PATHWAY_EXACTLY_ONCE": "0"}, extra,
            [
                "tests/test_outbox.py",
                "tests/test_chaos.py",
                "tests/test_io_streaming.py",
                "tests/test_io_formats.py",
                "tests/test_persistence_matrix.py",
            ],
        ),
        run_chaos_leg(
            "chaos-quick-eo-off", {"PATHWAY_EXACTLY_ONCE": "0"}
        ),
        # device-exchange forced on over the virtual mesh: the collective
        # column plane is exercised on CPU-only hosts (the multichip
        # dryrun's CI half); its A/B byte-identity test runs here too
        run_leg(
            "multichip-dryrun",
            {"PATHWAY_DEVICE_EXCHANGE": "1"},
            extra,
            [
                "tests/test_column_plane.py",
                "tests/test_parallel.py",
                "tests/test_workers.py",
            ],
        ),
        # megakernel killed: every wave fires per-node, which is the
        # byte-identity baseline the cone is pinned against; the
        # per-pipeline A/B comparisons live in tests/test_megakernel.py
        # (docs/megakernel.md)
        run_leg(
            "megakernel-off", {"PATHWAY_MEGAKERNEL": "0"}, extra,
            [
                "tests/test_megakernel.py",
                "tests/test_native_engine.py",
                "tests/test_plan_optimizer.py",
                "tests/test_column_plane.py",
                "tests/test_io_formats.py",
                "tests/test_persistence.py",
            ],
        ),
        # out-of-core state tier killed: arrangements stay fully
        # resident, the byte-identity baseline the LSM spill path is
        # pinned against; the spill-on A/B + corruption matrix lives in
        # tests/test_spill.py + test_persistence_matrix.py (legs 1-2)
        run_leg(
            "spill-off", {"PATHWAY_SPILL": "0"}, extra,
            [
                "tests/test_spill.py",
                "tests/test_join_matrix.py",
                "tests/test_reducers_matrix.py",
                "tests/test_iterate.py",
                "tests/test_persistence_matrix.py",
                "tests/test_persistence.py",
            ],
        ),
        # morsel execution killed: scans parse whole chunks, waves run
        # one future per replica, no stealing — the byte-identity
        # baseline the morsel/steal path is pinned against; the per-
        # pipeline A/B matrix + seeded straggler determinism live in
        # tests/test_morsel.py (docs/parallelism.md)
        run_leg(
            "morsel-off", {"PATHWAY_MORSEL": "0"}, extra,
            [
                "tests/test_morsel.py",
                "tests/test_workers.py",
                "tests/test_io_formats.py",
                "tests/test_megakernel.py",
                "tests/test_native_engine.py",
                "tests/test_persistence.py",
            ],
        ),
        # elastic membership killed: intents are ignored, no quiesce, no
        # rebalance, no swap machinery on the supervision path — the
        # static-mesh baseline the elastic protocol is pinned against;
        # the bypass byte-identity test itself is
        # tests/test_elastic.py::test_elastic_off_is_a_bypass, and the
        # rebalance tests skip themselves (docs/robustness.md)
        run_leg(
            "elastic-off", {"PATHWAY_ELASTIC": "0"}, extra,
            [
                "tests/test_elastic.py",
                "tests/test_chaos.py",
                "tests/test_persistence.py",
            ],
        ),
        # static soundness plane (docs/static-analysis.md): the repo
        # lint must be green, and the tier-1 suite + quick chaos drill
        # must run CYCLE-FREE with every registered engine lock
        # recording acquisition order (the lockgraph atexit gate turns
        # any ABBA cycle into rc 86)
        run_lint_leg(),
        run_leg(
            "lock-order", {"PATHWAY_LOCK_CHECK": "1"},
            ["-m", "not slow", *extra],
        ),
        run_chaos_leg(
            "chaos-quick-lockcheck", {"PATHWAY_LOCK_CHECK": "1"}
        ),
    ]
    ok = all(l["rc"] == 0 and l["failed"] == 0 and l["passed"] > 0 for l in legs)
    dirty = bool(
        subprocess.run(
            ["git", "status", "--porcelain"], cwd=REPO,
            capture_output=True, text=True,
        ).stdout.strip()
    )
    out = {
        "ok": ok,
        "git": subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO,
            capture_output=True, text=True,
        ).stdout.strip(),
        # a dirty tree means the recorded commit is NOT what actually ran
        "working_tree_dirty": dirty,
        "legs": legs,
    }
    with open(os.path.join(REPO, "TESTLEGS.json"), "w") as fh:
        json.dump(out, fh, indent=2)
    print("both legs green" if ok else "LEG FAILURE", "-> TESTLEGS.json")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
