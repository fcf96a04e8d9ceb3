"""Kernels: the latent attention of the decode-step program against its
roofline. The roofline time of a step's attention over the latent rows its
occupied slots hold (the family's ``mla_step_seconds``: the larger of the
absorbed products over them at the bf16 peak and their bytes, 1,152 a row
and sub-layer, at the HBM bandwidth), from the program's own count of those
rows (``latent_rows_read`` over ``decode_steps``), over the device time,
per execution of ``decode_step_slots`` in the traced window, of the kernel
(``latent_decode_attention``). None where the program counts no such rows
(a block without latent layers, a program without the counter) or the trace
holds no such kernel (the step's attention ran in XLA)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import spec  # noqa: E402

KERNEL = "latent_decode_attention"

kernel_seconds = spec._module(
    "_reader_linear_prefill_roofline",
    Path(__file__).with_name("linear_prefill_roofline.py"),
).kernel_seconds


def read(ctx):
    peaks, b = ctx["peaks"], ctx["counters"]["batcher"]
    rows, steps = b.get("latent_rows_read", 0), b.get("decode_steps", 0)
    sz = ctx["dec_sizes"]
    family = spec.family(sz["family"])
    if peaks is None or not rows or not steps or not hasattr(
        family, "mla_step_seconds"
    ):
        return None
    took = kernel_seconds(ctx["trace"], "decode_step_slots", {KERNEL})
    if took is None:
        return None
    return 100.0 * family.mla_step_seconds(sz, rows / steps, peaks) / took
