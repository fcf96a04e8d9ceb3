"""Admission and batching: the share of its host time in which the
batcher's thread did not run (%): waiting for the interpreter lock, for
the scheduler, or inside a blocking transfer. From
``ContinuousBatcher.stats`` over the window: ``host_cpu_s`` is the
thread's CPU time outside the two waits for the device, and ``loop_s -
admit_wait_s - step_wait_s`` the wall time outside them; not below 0 (the
two clocks are read a few microseconds apart)."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    if "loop_s" not in b or "host_cpu_s" not in b:
        return None
    host = b["loop_s"] - b.get("admit_wait_s", 0.0) - b.get("step_wait_s", 0.0)
    if host <= 0:
        return None
    return max(0.0, 100.0 * (1.0 - b["host_cpu_s"] / host))
