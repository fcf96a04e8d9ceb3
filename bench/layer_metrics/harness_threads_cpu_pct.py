"""Load generator: the CPU time burnt while the batcher's loop ran by
Python threads the program did not start, as a share of the loop's wall
time (%): ``cpu_foreign_s`` of ``ContinuousBatcher.stats`` over
``loop_s``. In the benchmark those are the client threads (their ``json``
work on requests and replies), the loader, the upserter and the main
thread, all in the server's process: the harness's own weight on the
interpreter the batcher's thread waits for; 100 is one core. The thread
that runs the profiler of a traced run (``bench-tracer``) is not among
them: the program gives it a role of its own
(``observability.thread_cpu()["tracer"]``), so that a traced run reads as
an untraced one does."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    if not b.get("loop_s") or "cpu_foreign_s" not in b:
        return None
    return 100.0 * b["cpu_foreign_s"] / b["loop_s"]
