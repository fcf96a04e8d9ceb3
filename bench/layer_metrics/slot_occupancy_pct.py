"""Admission and batching: the share of decode-step rows that carried a
request. From ``ContinuousBatcher.stats`` over the window: every finished
request took ``n_steps - 1`` rows of steps (its first token comes from its
prefill), out of ``decode_steps x slots`` rows dispatched."""


def read(ctx):
    c = ctx["counters"]
    steps = c["batcher"].get("decode_steps", 0)
    done = c["batcher"].get("completed", 0)
    if not steps or not done:
        return None
    return 100.0 * done * (c["n_steps"] - 1) / (steps * c["n_slots"])
