"""Admission and batching: the share of the prefills' width that was
padding (%). From ``ContinuousBatcher.stats`` over the window: the prompts
admitted held ``prompt_tokens`` real tokens and ran at widths that sum to
``padded_tokens`` (each prompt's sequence bucket). A program without these
counters reads nothing."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    padded = b.get("padded_tokens", 0)
    if not padded or "prompt_tokens" not in b:
        return None
    return 100.0 * (1.0 - b["prompt_tokens"] / padded)
