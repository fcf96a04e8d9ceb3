"""The benchmark's own tests of a run's set-up (bench/tests/test_setup.py),
collected here so that they run with the tier-1 suite and count in it: the
ingest's waves are one list whatever the seed and the timing, the wait is
for the index and not the parser, `compiled_in_setup` is the cold run's
only, and a prefill cut by the trace's edge does not move `prefill_ms`
(PERF.md, Open question 25 (a)). The cases are the file's, imported as
they are; nothing under bench/ knows of this file.
"""

from __future__ import annotations

import importlib.util
import os
from pathlib import Path

import pytest

_FILE = Path(__file__).resolve().parents[1] / "bench" / "tests" / "test_setup.py"


def _load():
    # the file names a compile cache for the processes it is run by hand
    # in; in this suite's the variable stays as it was
    held = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    spec = importlib.util.spec_from_file_location("bench_tests_test_setup", _FILE)
    module = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(module)
    finally:
        if held is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = held
    return module


_setup = _load()

test_the_ingest_waves_are_the_same_list = _setup.test_the_ingest_waves_are_the_same_list
test_the_wait_is_for_the_index_not_the_parser = (
    _setup.test_the_wait_is_for_the_index_not_the_parser
)
test_compiled_in_setup_is_the_cold_run_only = (
    _setup.test_compiled_in_setup_is_the_cold_run_only
)
test_a_cut_prefill_does_not_move_prefill_ms = (
    _setup.test_a_cut_prefill_does_not_move_prefill_ms
)


def test_every_case_of_the_file_is_collected_here():
    theirs = {n for n in vars(_setup) if n.startswith("test_")}
    assert theirs == {n for n in globals() if n.startswith("test_")} - {
        "test_every_case_of_the_file_is_collected_here"
    }


@pytest.fixture(autouse=True)
def _children_keep_a_compile_cache(monkeypatch):
    """tests/conftest.py turns the persistent compile cache off for this
    suite's processes and their children; the cold-run case starts two
    children whose whole point is that cache, in a directory of its own."""
    monkeypatch.delenv("JAX_ENABLE_COMPILATION_CACHE", raising=False)
