"""Kernels: the decode-step program against the HBM roofline. Bytes the
algorithm needs for one step (opsbytes.decode_step_bytes: weights once,
keys and values of the live positions of the occupied slots, the new row)
at the window's mean occupancy and mean context, over the HBM bandwidth,
over the program's device time per execution in the traced window."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import opsbytes  # noqa: E402


def read(ctx):
    peaks, trace, c = ctx["peaks"], ctx["trace"], ctx["counters"]
    p = (trace or {}).get("programs", {}).get("decode_step_slots")
    steps = c["batcher"].get("decode_steps", 0)
    done = c["batcher"].get("completed", 0)
    if peaks is None or not p or not p["count"] or not steps or not ctx["prompt_tokens"]:
        return None
    occupied = done * (c["n_steps"] - 1) / steps  # rows in use, mean
    context = sum(ctx["prompt_tokens"]) / len(ctx["prompt_tokens"]) + c["n_steps"] / 2
    whole, part = int(occupied), occupied - int(occupied)
    sz = ctx["dec_sizes"]
    need = opsbytes.decode_step_bytes(sz, [context] * whole)
    if part:
        more = opsbytes.decode_step_bytes(sz, [context] * (whole + 1))
        need += part * (more - need)
    least_s = need / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (p["total_s"] / p["count"])
