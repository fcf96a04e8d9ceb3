"""Kernels: the prefill program against the compute roofline. Operations
the algorithm needs for the unpadded prompts of the window's answers
(opsbytes.prefill_flops, their mean), over the bf16 peak, over the
program's device time per execution in the traced window."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import opsbytes  # noqa: E402


def read(ctx):
    peaks, trace = ctx["peaks"], ctx["trace"]
    p = (trace or {}).get("programs", {}).get("prefill_into_slot")
    if peaks is None or not p or not p["count"] or not ctx["prompt_tokens"]:
        return None
    sz = ctx["dec_sizes"]
    need = sum(opsbytes.prefill_flops(sz, t) for t in ctx["prompt_tokens"])
    least_s = need / len(ctx["prompt_tokens"]) / peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (p["total_s"] / p["count"])
