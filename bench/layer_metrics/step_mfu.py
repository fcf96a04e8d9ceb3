"""The whole step's share of the chip's peak: 2 x non-embedding parameters
x tokens really processed in the window (unpadded prompt tokens and
generated tokens of the decoder; on the retrieve route the encoder's and
its question and document tokens), over window seconds x the bf16 peak."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench import opsbytes  # noqa: E402


def read(ctx):
    peaks = ctx["peaks"]
    if peaks is None:
        return None  # no chip: no share of a peak
    c = ctx["counters"]
    flops = opsbytes.encode_token_flops(ctx["enc_sizes"]) * ctx["encoder_tokens"]
    if ctx["mix"]["route"] == "/v2/answer":
        done = c["batcher"].get("completed", 0)
        tokens = sum(ctx["prompt_tokens"]) + done * (c["n_steps"] - 1)
        flops += opsbytes.token_flops(ctx["dec_sizes"]) * tokens
    if not flops:
        return None
    return 100.0 * flops / (ctx["window_s"] * peaks["bf16_flops_per_s"])
