"""Admission and batching: the batcher's host time per dispatch (ms). From
``ContinuousBatcher.stats`` over the window: the wall time of its loop
outside the two waits for the device (``loop_s - admit_wait_s -
step_wait_s``: preparing, launching and accounting), over the prefills and
decode steps it dispatched. A program without these counters reads
nothing."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    dispatches = b.get("decode_steps", 0) + b.get("prefills", 0)
    if not dispatches or "loop_s" not in b:
        return None
    host = b["loop_s"] - b.get("admit_wait_s", 0.0) - b.get("step_wait_s", 0.0)
    return 1e3 * host / dispatches
