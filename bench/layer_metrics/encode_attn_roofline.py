"""Kernel: the Pallas ``fused_qkv_attention`` inside ``embed_encode``,
against its roofline: the larger of its operations over the bf16 peak and
its bytes over the HBM bandwidth, for the real tokens of the rows encoded
in the traced window, over the device time of its events."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import opsbytes  # noqa: E402


def read(ctx):
    peaks, trace = ctx["peaks"], ctx["trace"]
    if peaks is None or not trace or not ctx["encoder_rows"]:
        return None
    kernel_s = sum(
        v["total_s"] for name, v in trace["ops"].items()
        if "tpu_custom_call" in name  # Mosaic: the only one on this path
    )
    if not kernel_s:
        return None
    # rows of the whole window, scaled to the traced part of it
    share = trace["window_s"] / ctx["window_s"]
    sz = ctx["enc_sizes"]
    layers = sz["layers"]
    flops = layers * opsbytes.encode_attn_flops(sz, ctx["encoder_rows"]) * share
    nbytes = layers * opsbytes.encode_attn_bytes(sz, ctx["encoder_rows"]) * share
    least_s = max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
