"""Kernels: the linear mixer's scan in the prefill program against its
roofline. The roofline time of the scans of a prefill (the family's
``linear_scan_seconds``: the larger of 4 x heads x dh^2 a token at the bf16
peak and, at the HBM bandwidth, q, k, v and o of every token and one float32
state a layer), from the program's own count of the real tokens its linear
layers scanned (``linear_tokens`` over ``prefills``), over the device time,
per execution of ``prefill_into_slot`` in the traced window, of the scan's
kernel (``linear_prefill_attention``). None where the program counts no
such tokens (a block without linear layers, a program without the counter)
or the trace holds no such kernel (the scan ran in XLA)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import spec  # noqa: E402

KERNEL = "linear_prefill_attention"


def kernel_seconds(trace, program, names):
    """Device time, per execution of ``program`` in the traced window, of
    its operations named after one of ``names``; None where there are none."""
    p = (trace or {}).get("programs", {}).get(program)
    if not p or not p["count"]:
        return None
    total = sum(
        rec["total_s"] for key, rec in trace["ops"].items()
        if key.startswith(f"{program}: ")
        and key[len(program) + 2:].split("[")[0].split("(")[0] in names
    )
    return total / p["count"] if total else None


def read(ctx):
    peaks, b = ctx["peaks"], ctx["counters"]["batcher"]
    tokens, prefills = b.get("linear_tokens", 0), b.get("prefills", 0)
    took = kernel_seconds(ctx["trace"], "prefill_into_slot", {KERNEL})
    sz = ctx["dec_sizes"]
    family = spec.family(sz["family"])
    if peaks is None or not tokens or not prefills or took is None or not hasattr(
        family, "linear_scan_seconds"
    ):
        return None
    layers = sz["kinds"].count("linear")
    least_s = family.linear_scan_seconds(sz, tokens / prefills, layers, peaks)
    return 100.0 * least_s / took
