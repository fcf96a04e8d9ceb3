"""Bring-up guards: the chip smoke driven at tiny widths on the CPU, the
one compile-cache rule, backend-free imports, and one process per chip."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def _python(code: str, env_extra: dict[str, str]) -> str:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=240,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1]


# ------------------------------------------------------------- chip_smoke


TINY = chip_smoke.Widths(
    encoder=dict(
        vocab_size=512, d_model=32, n_heads=4, n_layers=1, d_ff=64,
        max_len=32, embed_dim=32,
    ),
    decoder=dict(
        vocab_size=256, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_len=256,
    ),
    max_new_tokens=4,
    n_docs=24,
    kernel_seqs=(16, 32),
    kernel_rows=(8, 16),
)


def test_chip_smoke_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.run_smoke() == 2
    out, err = capsys.readouterr()
    assert out == ""  # no result is printed
    assert "needs a TPU" in err and "CpuDevice" in err


def test_chip_smoke_phases_at_tiny_widths(capsys):
    """The whole smoke — server, traffic, ledger checks, and on the
    virtual mesh the sharded legs — with only the TPU requirement
    lifted."""
    import jax

    assert chip_smoke.run_smoke(TINY, require_tpu=False) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    device = {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    report = json.loads(lines[-2])
    assert report["failed_checks"] == [] and report["claim"] is None
    assert list(report)[-1] == "claim"
    assert report["requests"] == {
        "answer_sent": 17, "answer_ok": 17, "retrieve_sent": 1,
    }
    assert report["native"] == {"zset": True, "dataplane": True}
    assert report["mosaic_call_in_encode"] is False  # the einsum, off the TPU
    # the plane is process-wide: other tests' programs may be on it too
    programs = {k.split("#")[0].split(" ")[0] for k in report["compile_counts"]}
    assert programs >= {"embed_encode", "cb", "knn_slab_search"}
    if len(jax.devices()) >= 4:
        assert all(
            len(ids) == len(jax.devices())
            for ids in report["sharded_legs"].values()
        )
        assert "continuous_batcher_mesh_span" in report["sharded_legs"]


def test_chip_smoke_reports_a_failed_check(capsys):
    checks = chip_smoke.Checks()
    assert checks.check("fine", True)
    assert not checks.check("quarantine empty", False, {("cb/step", 8): "x"})
    assert len(checks.failed) == 1 and "cb/step" in checks.failed[0]


# ------------------------------------------------------ compile-cache rule

_CACHE_PROBE = """
import jax
updates = []
orig = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), orig(k, v))[1]
from pathway_tpu.engine.device_plane import compile_cache_dir, get_device_plane
get_device_plane()
import json
print(json.dumps({
    "dir": jax.config.jax_compilation_cache_dir,
    "rule": compile_cache_dir(),
    "set_dir_in_code": "jax_compilation_cache_dir" in updates,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
    "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes,
}))
"""


def test_compile_cache_follows_the_environment_when_it_names_one(tmp_path):
    got = json.loads(
        _python(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    )
    assert got["dir"] == got["rule"] == str(tmp_path)
    assert got["set_dir_in_code"] is False


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout():
    first = json.loads(_python(_CACHE_PROBE, {}))
    second = json.loads(_python(_CACHE_PROBE, {}))
    assert first == second
    assert first["dir"] == first["rule"] == str(REPO / ".pathway-cache" / "xla")
    assert first["set_dir_in_code"] is True
    assert first["min_secs"] == 0 and first["min_bytes"] == 0


# ------------------------------------------------- one process for each chip


def test_imports_initialise_no_backend():
    """A launcher that imported the package must not hold the chip its
    workers need."""
    out = _python(
        "import pathway_tpu, pathway_tpu.cli, pathway_tpu.parallel.supervisor\n"
        "from jax._src import xla_bridge\n"
        "print(len(xla_bridge._backends))",
        {},
    )
    assert out == "0"


def test_chip_env_binds_worker_k_to_chip_index_k_when_there_are_chips_enough(
    monkeypatch,
):
    from pathway_tpu.parallel import supervisor

    # the device files of a host whose IOMMU groups are not 0..3: only
    # their number counts
    monkeypatch.setattr(
        supervisor.os, "listdir",
        lambda d: ["vfio", "12", "13", "14", "15"] if d == "/dev/vfio" else [],
    )
    env = {"JAX_PLATFORMS": "tpu,cpu"}
    bound = [supervisor.chip_env(k, 4, 10000, env) for k in range(4)]
    assert [b["TPU_VISIBLE_CHIPS"] for b in bound] == ["0", "1", "2", "3"]
    # after the mesh's own ports, so launches on other ports do not meet
    assert [b["TPU_PROCESS_PORT"] for b in bound] == [
        "10004", "10005", "10006", "10007",
    ]
    other = supervisor.chip_env(0, 4, 20000, env)
    assert other["TPU_PROCESS_ADDRESSES"] == "localhost:20004"
    assert all(b["TPU_PROCESS_BOUNDS"] == "1,1,1" for b in bound)
    # one worker, a CPU run, or a caller that already placed its workers
    assert supervisor.chip_env(0, 1, 10000, env) == {}
    assert supervisor.chip_env(1, 4, 10000, {"JAX_PLATFORMS": "cpu"}) == {}
    assert supervisor.chip_env(1, 4, 10000, {"TPU_VISIBLE_CHIPS": "2"}) == {}
    # fewer chips than workers: left alone, the loser fails by name
    assert supervisor.chip_env(1, 8, 10000, env) == {}


def test_exit_drains_retrains_and_starts_no_backend():
    """What `indexing.ann` registers with atexit is the two drains — a
    live retrain thread racing interpreter exit aborts the process — and
    nothing that would open a device on the way out."""
    out = _python(
        "import atexit, json\n"
        "seen = []\n"
        "register = atexit.register\n"
        "atexit.register = lambda f, *a, **k: (seen.append(f), register(f, *a, **k))[1]\n"
        "import pathway_tpu.indexing.ann as ann\n"
        "print(json.dumps(sorted(f.__name__ for f in seen if f.__module__ == ann.__name__)))",
        {},
    )
    assert json.loads(out) == ["_drain_retrain_threads", "_drain_tier_daemons"]


def test_run_takes_no_device_argument():
    """JAX picks the device; a `device=` that was accepted and ignored
    would be a quiet CPU run at the front door."""
    import pathway_tpu as pw

    with pytest.raises(TypeError, match="device"):
        pw.run(device="tpu")
