"""The same code end to end on the CPU at the tiny preset under
bench/rehearsal/ (not a configuration of BENCHMARK.json). Prints counts
only: no time, rate or share measured here means anything.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload tiny.backlog] [--trace 1]
    python3 bench/rehearse.py --compile rag-gpt2-xl   # third rehearsal, by hand

``--compile`` lowers and compiles the configuration's encode, prefill and
step programs at their real shapes for a described v5e:2x2 device. It is
a compile, never a chip run, and nothing imports it."""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def rehearse(workload: str, seed: int, seconds: float, trace: bool,
             control: bool = False,
             bench_file: Path = HERE / "rehearsal" / "BENCHMARK.json") -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", str(ROOT / ".pathway-cache" / "xla-rehearsal")
    )
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT))
    from pwbench import harness

    result = harness.run_cell(
        bench_file, workload, seed, seconds, trace,
        t_start=T_START, require_tpu=False,
        out_dir=ROOT / ".bench-out" / "rehearsal", control=control,
    )
    counts = {
        "correct": result["correct"], "attempted": result["attempted"],
        "failed": result["failed"], "counters": result["counters"],
        "warmed": result["warmed"], "encode_waves": result["encode_waves"],
        "compiled_in_setup": [c["program"] for c in result["compiled_in_setup"]],
        "compared": result["compared"],
        "metric_names": sorted(result["metrics"]),
        "device": result["device"]["platform"],
    }
    print(json.dumps(counts, indent=1))
    return 0 if result["correct"] != control else 1


def compile_for_v5e(config_name: str) -> int:
    """Lower and compile the programs of a configuration at the real
    shapes for a described v5e chip: the decoder's (its family's
    ``compile_jobs``) and the encoder's kernel."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT))
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from pathway_tpu.ops.attention import fused_qkv_attention
    from pwbench import spec, weights

    path = Path(config_name)
    if not path.is_file():  # a configuration's name, or any file of sizes
        path = HERE / "configs" / f"{config_name}.json"
    with open(path) as f:
        cfg = json.load(f)
    config_name = cfg["name"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree
        )

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    esz = weights.encoder_sizes(cfg["encoder"])
    jobs = spec.family_of(cfg).compile_jobs(cfg, shaped, i32)
    for rows, seq in ((4096, esz["positions"]), (16, 32)):
        qkv = jax.ShapeDtypeStruct((rows, seq, 3 * esz["d"]), jnp.bfloat16, sharding=chip)
        jobs[f"encoder kernel rows={rows} seq={seq}"] = (
            lambda qkv=qkv, rows=rows, seq=seq: jax.jit(
                functools.partial(fused_qkv_attention, n_heads=esz["heads"])
            ).lower(qkv, i32(rows, seq))
        )
    failed = 0
    for name, lower in jobs.items():
        t = time.monotonic()
        try:
            compiled = lower().compile()
            mem = compiled.memory_analysis()
            print(
                f"{config_name} {name}: compiled for v5e in "
                f"{time.monotonic() - t:.0f} s; temp "
                f"{getattr(mem, 'temp_size_in_bytes', 0) / 1e9:.2f} GB, "
                f"arguments {getattr(mem, 'argument_size_in_bytes', 0) / 1e9:.2f} GB",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001 — report each refusal
            failed += 1
            print(f"{config_name} {name}: REFUSED {type(e).__name__}: {str(e)[:300]}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="tiny.backlog")
    ap.add_argument("--seed", type=int, default=2147489999)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="judge the fp8 control in the program's place")
    ap.add_argument("--compile", metavar="CONFIGURATION")
    a = ap.parse_args()
    if a.compile:
        code = compile_for_v5e(a.compile)
    else:
        code = rehearse(a.workload, a.seed, a.seconds, bool(a.trace), a.control)
    sys.stdout.flush()
    os._exit(code)
