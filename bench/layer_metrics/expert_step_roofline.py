"""Kernels: the expert layer of the decode-step program against the HBM
roofline. The device time of the operations of ``decode_step_slots`` that
read an expert leaf, per execution in the traced window, against the bytes
the algorithm needs for them: the three bf16 matrices of every distinct
expert the occupied rows hit (the program's own count, ``experts_touched``
summed over layers, over ``decode_steps``) over the HBM bandwidth. None
where the program counts no experts (a block without them)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import spec  # noqa: E402

expert_seconds = spec._module(
    "_reader_expert_prefill_roofline",
    Path(__file__).with_name("expert_prefill_roofline.py"),
).expert_seconds


def read(ctx):
    peaks, b = ctx["peaks"], ctx["counters"]["batcher"]
    touched, steps = b.get("experts_touched", 0), b.get("decode_steps", 0)
    took = expert_seconds(ctx["trace"], "decode_step_slots")
    if peaks is None or not touched or not steps or took is None:
        return None
    sz = ctx["dec_sizes"]
    elements = spec.family(sz["family"]).expert_matrix_elements(sz)
    least_s = 2 * elements * touched / steps / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / took
