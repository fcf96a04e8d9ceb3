"""A device trace by scope: which `jax.named_scope`, source line and shape
each operation of each program has, and its milliseconds an execution.

    python3 scripts/trace_scopes.py <file.xplane.pb> [--min-ms 0.01]

`jax.profiler.ProfileData` shows an event's own stats (its duration) and
drops those of its *metadata*, where the TPU runtime keeps `tf_op` (the
operation's `op_name`: the scopes it was traced under), `source`,
`bytes_accessed` and `flops`. They are in the raw protobuf
(tsl/profiler/protobuf/xplane.proto), which this reads by its wire format
with nothing but the standard library (no TensorFlow, no second JAX), so
that it can run in the process that holds the chip. The benchmark's reduction (`bench/pwbench/trace_reduce.py`)
names an operation by its HLO stem and the leaves it reads (`op_key`, used
here too, so that a row has the name `breakdown.device_ops` gives it); this
adds the rest (PERF.md section 5: PRs 36 and 42 read a cell's unnamed
`fusion` so).

To keep a cell's trace, wrap `pwbench.trace_reduce.reduce_file` from a
scratch script before `bench/run.py`'s `main` runs: the harness deletes the
file after reducing it. As there, an execution that the trace's edge cut is
in no sum.
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import struct
import sys
from pathlib import Path
from typing import Iterator

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from pwbench.trace_reduce import (  # noqa: E402 — no JAX at import
    MODULE_LINE, OPS_LINE, op_key, program_name,
)


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[tuple[int, object]]:
    """(field number, value) of one message: varints as ints, the rest as
    the bytes they are."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            size = {1: 8, 5: 4}[wire]
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _stat(buf, names: dict[int, str]) -> tuple[str, object]:
    """XStat: metadata_id 1; double 2, uint64 3, int64 4, str 5, bytes 6,
    ref (a stat name's id used as a value) 7."""
    name = value = None
    for field, v in _fields(buf):
        if field == 1:
            name = names.get(v, str(v))
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field in (3, 4):
            value = v
        elif field == 5:
            value = _text(v)
        elif field == 6:
            value = bytes(v)
        elif field == 7:
            value = names.get(v, str(v))
    return name, value


def planes(path: str) -> Iterator[dict]:
    """Each XPlane: its name, its lines (name, events as (metadata id,
    start in ps, duration in ps)) and its event metadata by id (name and
    stats)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, lines, events, stat_names = "", [], {}, {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = _text(pv)
            elif pf == 3:
                lines.append(pv)
            elif pf in (4, 5):  # map entries: key 1, value 2
                entry = dict(_fields(pv))
                if 2 not in entry:
                    continue
                if pf == 5:
                    meta = dict(_fields(entry[2]))
                    stat_names[meta.get(1, entry.get(1))] = _text(meta.get(2, b""))
                else:
                    events[entry.get(1)] = entry[2]
        metadata = {}
        for key, buf in events.items():  # XEventMetadata: name 2, stats 5
            meta = metadata[key] = {"name": "", "stats": {}}
            for ef, ev in _fields(buf):
                if ef == 2:
                    meta["name"] = _text(ev)
                elif ef == 5:
                    stat, value = _stat(ev, stat_names)
                    meta["stats"][stat] = value
        out_lines = []
        for buf in lines:
            line_name, t0, evs = "", 0, []
            for lf, lv in _fields(buf):
                if lf == 2:
                    line_name = _text(lv)
                elif lf == 3:
                    t0 = lv
                elif lf == 4:
                    ev = dict(_fields(lv))
                    evs.append((ev.get(1, 0), t0 * 1000 + ev.get(2, 0), ev.get(3, 0)))
            out_lines.append({"name": line_name, "events": evs})
        yield {"name": name, "lines": out_lines, "event_metadata": metadata}


_SHAPE = re.compile(r" = (\(?[a-z0-9]+\[[^ ]*)")


def by_scope(path: str, min_ms: float = 0.01) -> dict:
    """{program: {executions, ms_per_execution, rows: [[scope, operation,
    shape, source, count an execution, ms an execution, MB an execution]]}}
    of the first TPU's plane, rows by their time."""
    out = {}
    for plane in planes(path):
        if not plane["name"].startswith("/device:TPU:0"):
            continue
        meta = plane["event_metadata"]
        mods, ops = [], []
        for line in plane["lines"]:
            if line["name"] == MODULE_LINE:
                mods = sorted((s, d, meta[m]["name"]) for m, s, d in line["events"])
            elif line["name"] == OPS_LINE:
                ops = [(s, d, meta[m]) for m, s, d in line["events"]]
        if not mods:
            continue
        lo = min([m[0] for m in mods] + [o[0] for o in ops])
        hi = max([m[0] + m[1] for m in mods] + [o[0] + o[1] for o in ops])
        starts = [m[0] for m in mods]
        cut = [s <= lo or s + d >= hi for s, d, _ in mods]
        programs: dict[str, dict] = {}
        for (s, d, name), is_cut in zip(mods, cut):
            if not is_cut:
                rec = programs.setdefault(program_name(name), {"n": 0, "ms": 0.0, "rows": {}})
                rec["n"] += 1
                rec["ms"] += d * 1e-9
        for s, d, md in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][0] + mods[i][1] or cut[i]:
                continue
            stats, text = md["stats"], md["name"]
            shape = _SHAPE.search(text)
            key = (
                re.sub(r"^jit\([^)]*\)/", "", str(stats.get("tf_op", ""))).rstrip(":"),
                op_key(text), shape.group(1)[:48] if shape else "",
                str(stats.get("source", "")).rsplit("/", 1)[-1],
            )
            row = programs[program_name(mods[i][2])]["rows"].setdefault(key, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += d * 1e-9
            row[2] += float(stats.get("bytes_accessed", 0) or 0) * 1e-6
        for name, rec in programs.items():
            n = max(rec["n"], 1)
            rows = sorted(rec["rows"].items(), key=lambda kv: -kv[1][1])
            out[name] = {
                "executions": rec["n"], "ms_per_execution": rec["ms"] / n,
                "rows": [
                    [*key, round(c / n, 2), round(ms / n, 4), round(mb / n, 1)]
                    for key, (c, ms, mb) in rows if ms / n >= min_ms
                ],
            }
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace")
    ap.add_argument("--min-ms", type=float, default=0.01)
    a = ap.parse_args()
    print(json.dumps(by_scope(a.trace, a.min_ms), indent=1))
