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

import pytest  # noqa: E402


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
