"""Finds a cell's files by the names in ``BENCHMARK.json``."""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
# where a decoder family's file is looked for: ``<directory>/<family>.py``
FAMILY_DIRS = [BENCH / "families"]


def _load(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)  # a file is run once, however often asked for
def _module(name: str, path: Path) -> Any:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: Any) -> Any:
    """The module of a decoder family: everything the benchmark knows of
    a decoder's block (bench/README.md says what the file gives)."""
    for d in FAMILY_DIRS if isinstance(name, str) else ():
        if (d / f"{name}.py").is_file():
            return _module(f"_family_{name}", d / f"{name}.py")
    there = sorted({p.stem for d in FAMILY_DIRS for p in d.glob("*.py")})
    raise SystemExit(
        f"unknown decoder family {name!r}: the configuration's file names "
        f"its family under the key 'family', and the families are {there}"
    )


def family_of(config: dict) -> Any:
    """The family a configuration's file names."""
    return family(config.get("family"))


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic mix
    and the metrics that apply to it."""

    def __init__(self, bench_file: Path, name: str):
        bench = _load(bench_file)
        base = bench_file.parent
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"unknown workload {name!r}; {bench_file} has {sorted(cells)}"
            )
        self.bench = bench
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = _load(base / cfg_entry["file"])
        self.config["_file"] = str(base / cfg_entry["file"])
        self.traffic_dir = base / bench["paths"][0] / "traffic"
        self.mix = _load(self.traffic_dir / f"{self.entry['traffic']}.json")
        self.readers_dir = base / bench["paths"][0] / "layer_metrics"
        self.run_seconds = int(bench["run_seconds"])

    def _applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    @property
    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"] if self._applies(m)]

    @property
    def per_layer(self) -> list[dict]:
        return [m for m in self.bench["per_layer"] if self._applies(m)]

    def reader(self, metric_name: str) -> Any:
        """The reader of a per-layer metric: the file named by the part of
        the metric's name before its first dot."""
        stem = metric_name.split(".", 1)[0]
        return _module(f"_reader_{stem}", self.readers_dir / f"{stem}.py").read


def peaks(device_kind: str) -> dict:
    table = _load(BENCH / "peaks.json")
    if device_kind not in table:
        raise SystemExit(
            f"device kind {device_kind!r} is not in bench/peaks.json "
            f"({sorted(table)}): add its published peaks with their source"
        )
    return table[device_kind]
