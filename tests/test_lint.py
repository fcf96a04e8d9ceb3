"""Repo lint suite tests (analysis/lint.py, docs/static-analysis.md).

Each rule is pinned on synthetic sources (the bug class it encodes must
be caught; the fixed form must pass), the pragma escape hatch works, and
— the acceptance gate — the lint is green over the real package, so a
regression of any paid-for bug class cannot land silently."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from pathway_tpu.analysis import lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(src: str, path: str = "pathway_tpu/engine/fake.py") -> set[str]:
    return {f.rule for f in lint.lint_file(path, src)}


# ------------------------------------------------------ env-hot-path


def test_env_read_in_node_method_flagged():
    src = """
import os

class MyNode:
    def finish_time(self, time):
        if os.environ.get("PATHWAY_FLAG") == "1":
            return
"""
    assert "env-hot-path" in _rules(src)


def test_env_read_in_hot_function_flagged():
    src = """
import os

def split_batch(batch):
    return os.getenv("PATHWAY_MODE")
"""
    assert "env-hot-path" in _rules(src)


def test_env_read_at_construction_passes():
    src = """
import os

class MyNode:
    def __init__(self):
        self.mode = os.environ.get("PATHWAY_MODE", "auto")

    def finish_time(self, time):
        return self.mode
"""
    assert "env-hot-path" not in _rules(src)


def test_env_read_outside_hot_paths_passes():
    src = """
import os

def lowering_helper():
    return os.environ.get("PATHWAY_FUSE", "1")
"""
    assert "env-hot-path" not in _rules(src)


# switches whose code paths the chip's records decided and PR 33 deleted:
# nothing may read them again, and no document may send a reader to them
_DELETED_ENV = (
    "PATHWAY_CONTINUOUS_BATCH", "PATHWAY_TPU_FUSED_ATTN",
    "PATHWAY_STAGE_OVERLAP", "PATHWAY_MESH_SLOTS", "PATHWAY_MESH_BSP",
)


@pytest.mark.parametrize("name", _DELETED_ENV)
def test_deleted_env_name_is_named_nowhere(name):
    hits = []
    for top in ("pathway_tpu", "scripts", "docs"):
        for folder, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith((".so", ".o", ".pyc")):
                    continue
                path = os.path.join(folder, f)
                with open(path, encoding="utf-8", errors="replace") as fh:
                    if name in fh.read():
                        hits.append(os.path.relpath(path, REPO))
    assert not hits, f"{name} is back in {hits}"


# ------------------------------------------------- swallowed-io-error


def test_except_oserror_pass_in_io_flagged():
    src = """
def close(sock):
    try:
        sock.close()
    except OSError:
        pass
"""
    assert "swallowed-io-error" in _rules(src, "pathway_tpu/io/fake.py")


def test_bare_except_pass_in_stdlib_flagged():
    src = """
def drain(f):
    try:
        f.result()
    except:
        pass
"""
    assert "swallowed-io-error" in _rules(
        src, "pathway_tpu/stdlib/utils/fake.py"
    )


def test_import_error_pass_is_fine():
    src = """
def probe():
    try:
        import pwd
    except ImportError:
        pass
"""
    assert "swallowed-io-error" not in _rules(src, "pathway_tpu/io/fake.py")


def test_logged_handler_passes():
    src = """
def close(sock, logger):
    try:
        sock.close()
    except OSError as e:
        logger.warning("close failed: %s", e)
"""
    assert "swallowed-io-error" not in _rules(src, "pathway_tpu/io/fake.py")


def test_io_rule_scoped_to_io_and_stdlib():
    src = """
def f(x):
    try:
        x()
    except OSError:
        pass
"""
    assert "swallowed-io-error" not in _rules(
        src, "pathway_tpu/internals/fake.py"
    )


# --------------------------------------------------- jit-under-lock


def test_jit_inside_with_lock_flagged():
    src = """
import jax

class Plane:
    def program(self, fn):
        with self._lock:
            return jax.jit(fn)
"""
    assert "jit-under-lock" in _rules(src)


def test_jit_built_outside_lock_passes():
    src = """
import jax

class Plane:
    def program(self, fn):
        jitted = jax.jit(fn)
        with self._lock:
            self._programs[fn] = jitted
"""
    assert "jit-under-lock" not in _rules(src)


def test_nested_def_under_lock_not_inherited():
    # a callback DEFINED under the lock runs later, without it
    src = """
import jax

class Plane:
    def program(self, fn):
        with self._lock:
            def later():
                return jax.jit(fn)
            self._thunk = later
"""
    assert "jit-under-lock" not in _rules(src)


# ---------------------------------------------------- outbox-bypass


def test_direct_write_batch_call_flagged():
    src = """
class OutputNode:
    def finish_time(self, time):
        self.write_batch(time, self.take_input())
"""
    assert "outbox-bypass" in _rules(src, "pathway_tpu/engine/fake.py")


def test_write_via_retrying_passes():
    src = """
class OutputNode:
    def _write_retrying(self, fn, time, payload):
        fn(time, payload)

    def finish_time(self, time):
        self._write_retrying(self.write_batch, time, self.take_input())
"""
    assert "outbox-bypass" not in _rules(src, "pathway_tpu/engine/fake.py")


def test_outbox_rule_scoped_to_engine():
    src = """
class Writer:
    def deliver_now(self):
        self.write_batch(0, [])
"""
    assert "outbox-bypass" not in _rules(src, "pathway_tpu/io/fake.py")


# ------------------------------------------------------------ pragmas


def test_pragma_suppresses_named_rule():
    src = """
def close(sock):
    try:
        sock.close()
    except OSError:
        pass  # lint: allow(swallowed-io-error)
"""
    # the pragma must sit on the LINE the finding anchors to (the
    # handler line) — on the pass line it suppresses nothing
    assert lint.lint_file("pathway_tpu/io/fake.py", src)
    src2 = src.replace(
        "except OSError:",
        "except OSError:  # lint: allow(swallowed-io-error)",
    )
    assert not lint.lint_file("pathway_tpu/io/fake.py", src2)


def test_pragma_does_not_suppress_other_rules():
    src = """
def close(sock):
    try:
        sock.close()
    except OSError:  # lint: allow(env-hot-path)
        pass
"""
    assert "swallowed-io-error" in _rules(src, "pathway_tpu/io/fake.py")


# --------------------------------------------------------- the repo


def test_repo_is_lint_clean():
    """The acceptance gate: the package itself is green — every finding
    the suite ever flags from here on is a REGRESSION of a bug class
    this repo already paid for."""
    findings = lint.run()
    assert not findings, "\n".join(map(repr, findings))


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "io" / "bad.py"
    bad.parent.mkdir()
    bad.write_text(
        "def f(s):\n    try:\n        s.close()\n"
        "    except OSError:\n        pass\n"
    )
    env = {**os.environ, "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis.lint",
         os.fspath(bad)],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 1
    assert "swallowed-io-error" in r.stdout
    good = tmp_path / "io" / "good.py"
    good.write_text("x = 1\n")
    r = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis.lint",
         os.fspath(good)],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 0
