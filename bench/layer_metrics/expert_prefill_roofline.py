"""Kernels: the expert layer of the prefill program against the compute
roofline. The device time of the operations of ``prefill_into_slot`` that
read an expert leaf (the grouped products over ``expert_gate``,
``expert_up`` and ``expert_down``), per execution in the traced window,
against the operations the algorithm needs for them: 2 x one expert's three
matrices x the token-expert pairs of a prefill (the program's own count,
``routed_pairs`` over ``prefills``, real tokens only) over the bf16 peak.
None where the program counts no pairs (a block without experts)."""
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import spec  # noqa: E402

_LEAVES = re.compile(r"\(([a-z_0-9,]+)\)$")


def expert_seconds(trace, program):
    """Device time, per execution of ``program`` in the traced window, of
    its operations that read an expert leaf; None where there are none."""
    p = (trace or {}).get("programs", {}).get(program)
    if not p or not p["count"]:
        return None
    total = 0.0
    for key, rec in trace["ops"].items():
        leaves = _LEAVES.search(key)
        if key.startswith(f"{program}: ") and leaves and any(
            leaf.startswith("expert_") for leaf in leaves.group(1).split(",")
        ):
            total += rec["total_s"]
    return total / p["count"] if total else None


def read(ctx):
    peaks, b = ctx["peaks"], ctx["counters"]["batcher"]
    pairs, prefills = b.get("routed_pairs", 0), b.get("prefills", 0)
    took = expert_seconds(ctx["trace"], "prefill_into_slot")
    if peaks is None or not pairs or not prefills or took is None:
        return None
    sz = ctx["dec_sizes"]
    elements = spec.family(sz["family"]).expert_matrix_elements(sz)
    least_s = 2 * elements * pairs / prefills / peaks["bf16_flops_per_s"]
    return 100.0 * least_s / took
