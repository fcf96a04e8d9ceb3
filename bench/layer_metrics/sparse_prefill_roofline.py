"""Kernels: the sparse mixer of the prefill program against the compute
roofline. The operations the algorithm needs for the minicpm4 layers of the
window's prompts (the family's ``sparse_prefill_flops``: 4 x heads x dh a
causal pair inside the blocks a query chose and 2 x heads x dh a pooled key
it scored, from the unpadded prompt lengths; how many blocks a query chooses
is the algorithm's, min(topk, the blocks at or before it), and the
program's own ``sparse_blocks_read`` says that it did choose), over the bf16
peak, over the device time, per execution of ``prefill_into_slot`` in the
traced window, of the attention kernel over the chosen blocks
(``sparse_prefill_attention``) and of the selection: the loop over chunks of
queries in which the program scores, ranks and chooses, this prefill's only
``while`` (the reduced trace names an operation by its stem, not by its
scope: PERF.md, Open question 26; the pooled keys' means, outside the loop,
are not in it). Counts of what the algorithm needs: a kernel that computes
tiles nobody chose reads low. None where the program counts no chosen
blocks or the trace holds no such kernel."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import spec  # noqa: E402

NAMES = {"sparse_prefill_attention", "while"}


def read(ctx):
    peaks, trace = ctx["peaks"], ctx["trace"]
    b = ctx["counters"]["batcher"]
    sz = ctx["dec_sizes"]
    family = spec.family(sz["family"])
    if peaks is None or not b.get("sparse_blocks_read") or not ctx["prompt_tokens"] or (
        not hasattr(family, "sparse_prefill_flops")
    ):
        return None
    p = (trace or {}).get("programs", {}).get("prefill_into_slot")
    if not p or not p["count"]:
        return None
    prefix = "prefill_into_slot: "
    ops = {
        key[len(prefix):].split("[")[0].split("(")[0]: rec["total_s"]
        for key, rec in trace["ops"].items() if key.startswith(prefix)
    }
    if not ops.get("sparse_prefill_attention"):
        return None
    took = sum(s for name, s in ops.items() if name in NAMES) / p["count"]
    layers = sz["kinds"].count("sparse")
    need = sum(
        layers * family.sparse_prefill_flops(sz, t) for t in ctx["prompt_tokens"]
    ) / len(ctx["prompt_tokens"])
    return 100.0 * need / peaks["bf16_flops_per_s"] / took
