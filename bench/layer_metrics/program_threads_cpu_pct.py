"""Admission and batching: the CPU time that the program's other Python
threads burnt while the batcher's loop ran, as a share of the loop's wall
time (%): the engine's pump (``cpu_engine_s``), the async UDF loop
(``cpu_udf_s``), the webserver (``cpu_edge_s``) and the device plane's
pools (``cpu_pool_s``) of ``ContinuousBatcher.stats`` over ``loop_s``.
Each of them holds the interpreter the batcher's thread waits for
(``host_stall_pct``); 100 is one core."""

KEYS = ("cpu_engine_s", "cpu_udf_s", "cpu_edge_s", "cpu_pool_s")


def read(ctx):
    b = ctx["counters"]["batcher"]
    if not b.get("loop_s") or not any(k in b for k in KEYS):
        return None
    return 100.0 * sum(b.get(k, 0.0) for k in KEYS) / b["loop_s"]
