"""Records the small trace the self-check reads (run by hand, on the
chip): two programs jitted from ``functools.partial`` objects, as the
DevicePlane jits its programs, so the device names both
``jit__unknown``, run under the harness's own request spans with the
tracer's options of trace_reduce.TraceWindow.

    python3 bench/testdata/record_trace.py <out dir>
"""
import functools
import glob
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def small_step(x, w, n):
    for _ in range(n):
        x = jnp.tanh(x @ w)
    return x


def small_prefill(x, w, n):
    return jnp.sum(small_step(x, w, n), axis=0)


if __name__ == "__main__":
    out = sys.argv[1]
    step = jax.jit(functools.partial(small_step, n=2))
    prefill = jax.jit(functools.partial(small_prefill, n=4))
    x = jnp.ones((256, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16) * 0.01
    step(x, w).block_until_ready()
    prefill(x, w).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out + "/raw", profiler_options=options)
    for i in range(3):
        with TraceAnnotation(f"bench.req {i}"):
            prefill(x, w).block_until_ready()
            for _ in range(4):
                step(x, w).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    f = sorted(glob.glob(out + "/raw/**/*.xplane.pb", recursive=True))[-1]
    shutil.copy(f, out + "/tiny.xplane.pb")
    print("wrote", out + "/tiny.xplane.pb")
