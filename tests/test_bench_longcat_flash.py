"""The benchmark's own tests of the ``longcat_flash`` decoder family
(bench/tests/test_longcat_flash.py), collected here so that they run with
the tier-1 suite and count in it, the way tests/test_bench_minicpm_sala.py
collects bench/tests/test_minicpm_sala.py: at the tiny preset the fp8
control and both faults of the block (a c_kv without its factor, identity
picks that add nothing) come out not correct, a sound run correct with the
router's and the latent rows' counters in it; the four readers read what
they should; the family's counts are ISSUE 43's arithmetic at the published
widths. The cases are the file's, imported as they are; nothing under
bench/ knows of this file.
"""

from __future__ import annotations

import importlib.util
import os
from pathlib import Path

import pytest

_FILE = (
    Path(__file__).resolve().parents[1] / "bench" / "tests" / "test_longcat_flash.py"
)


def _load():
    # the file names a compile cache for the processes it is run by hand
    # in; in this suite's the variable stays as it was
    held = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    spec = importlib.util.spec_from_file_location("bench_tests_test_longcat_flash", _FILE)
    module = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(module)
    finally:
        if held is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = held
    return module


_cases = _load()

test_control_is_not_correct = _cases.test_control_is_not_correct
test_broken_timed_path_is_not_correct = _cases.test_broken_timed_path_is_not_correct
test_sound_run_is_correct_and_counts_its_shares = (
    _cases.test_sound_run_is_correct_and_counts_its_shares
)
test_the_readers_read_a_trace_and_nothing_of_another_program = (
    _cases.test_the_readers_read_a_trace_and_nothing_of_another_program
)
test_counts_at_the_published_widths = _cases.test_counts_at_the_published_widths


def test_every_case_of_the_file_is_collected_here():
    theirs = {n for n in vars(_cases) if n.startswith("test_")}
    assert theirs == {n for n in globals() if n.startswith("test_")} - {
        "test_every_case_of_the_file_is_collected_here"
    }


@pytest.fixture(autouse=True)
def _the_error_log_starts_empty(monkeypatch):
    """A run is not correct while the process's global error log holds an
    entry (`harness._error_log`), and in this suite the process has run other
    files' tests, some of which log errors on purpose: each case here starts
    from an empty log, as a run of the benchmark's own process does."""
    import pathway_tpu as pw

    monkeypatch.setattr(pw.global_error_log(), "entries", [])
