"""Kernels: the latent attention of the prefill program against the compute
roofline. The operations the algorithm needs for the latent sub-layers'
attention over the window's prompts (the family's ``mla_prefill_flops`` of
the unpadded prompt lengths, their mean: 2 x heads x (192 + 128) lanes a
causal pair and sub-layer, so the kernel's zero lanes beside the 192 and its
masked halves of the diagonal tiles read as time and not as work), over the
bf16 peak, over the device time, per execution of ``prefill_into_slot`` in
the traced window, of the kernel (``latent_prefill_attention``). None where
the family has no such count, or the trace holds no such kernel (the
attention ran in XLA, or the program has no latent layer)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import spec  # noqa: E402

KERNEL = "latent_prefill_attention"

kernel_seconds = spec._module(
    "_reader_linear_prefill_roofline",
    Path(__file__).with_name("linear_prefill_roofline.py"),
).kernel_seconds


def read(ctx):
    peaks, sz = ctx["peaks"], ctx["dec_sizes"]
    family = spec.family(sz["family"])
    if peaks is None or not ctx["prompt_tokens"] or not hasattr(
        family, "mla_prefill_flops"
    ):
        return None
    took = kernel_seconds(ctx["trace"], "prefill_into_slot", {KERNEL})
    if took is None:
        return None
    need = sum(
        family.mla_prefill_flops(sz, t) for t in ctx["prompt_tokens"]
    ) / len(ctx["prompt_tokens"])
    return 100.0 * need / peaks["bf16_flops_per_s"] / took
