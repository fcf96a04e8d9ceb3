import os

# Force JAX onto a virtual 8-device CPU mesh for sharding tests. Unit
# tests never take the chip: a chip belongs to one process at a time,
# and `python chip_smoke.py` is what runs on it.
#
# A machine with a TPU sets JAX_PLATFORMS for it (the chip machine has
# "tpu,cpu"), and jax may already be imported when this file runs, so a
# setdefault is not enough: set the variable and the config directly,
# before the backend initializes (it is lazy until the first
# jax.devices()).
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Tests (and the subprocesses they start) neither read nor write the
# persistent compile cache the DevicePlane configures: a warm cache makes
# a second run's compiles near-instant, and the suite's timing-sensitive
# tests (background retrain and tier-migration threads) are only stable
# at the compile timing of a cold run. The rule itself is pinned by
# tests/test_bring_up.py, which reads the configuration, not the cache.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", "tests must run on the CPU mesh"

import socket  # noqa: E402

import pytest  # noqa: E402

# ---------------------------------------------------------------- ports
#
# A test that asks the kernel for a free port (bind to 0), closes the
# probe and hands the number to a server or a child process has lost the
# port by the time that one binds: the other xdist workers bind and
# connect in the same ephemeral range meanwhile. So ports come from below
# that range (32768 up on Linux), where the kernel hands out nothing by
# itself, each xdist worker from a slice of its own, and no block twice.
_PORT_FLOOR, _PORT_SLICE, _PORT_SLICES = 20000, 1000, 12
_port_offset = 0  # into this worker's slice: where the next block starts


def free_port_base(n: int = 1) -> int:
    """The first of `n` consecutive ports that are free now and that no
    other call, in this worker or another, is handed."""
    global _port_offset
    worker = os.environ.get("PYTEST_XDIST_WORKER", "gw0")
    lo = _PORT_FLOOR + (int(worker[2:] or 0) % _PORT_SLICES) * _PORT_SLICE
    for _ in range(_PORT_SLICE // n):
        if _port_offset + n > _PORT_SLICE:
            _port_offset = 0
        base = lo + _port_offset
        _port_offset += n
        probes = []
        try:
            for port in range(base, base + n):
                probe = socket.socket()
                probes.append(probe)
                probe.bind(("127.0.0.1", port))
            return base
        except OSError:
            continue  # held by another program, or still closing
        finally:
            for probe in probes:
                probe.close()
    raise RuntimeError(
        f"no {n} consecutive free ports in {lo}..{lo + _PORT_SLICE}"
    )


@pytest.fixture(autouse=True)
def _clear_parse_graph():
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test, excluded from tier-1 (-m 'not slow')",
    )
