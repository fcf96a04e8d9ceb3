"""The one reduction from a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to what the per-layer readers use: the
device's busy time as the union of its operations' intervals, the time and
count of each XLA program (module) on the device, the operations that took
most time, and the longest idle gaps, each named by what the host was
doing in it.

How a TPU trace is laid out (jax 0.9, looked at by hand, PERF.md
"Reading a trace"): one plane per chip, ``/device:TPU:<n>``, with a line
``XLA Modules`` (one event per execution of a compiled program, named
``jit_<function>(<fingerprint>)``; the DevicePlane's are all
``jit__unknown(<id>)``, see ``name_modules``), a line ``XLA Ops`` (one
event per operation, its name the whole HLO text) and a line ``Async XLA
Ops`` (copies that overlap the operations: left out of the busy time);
one plane ``/host:CPU`` with an unnamed line per host thread, whose events
are the runtime's own spans (``PjitFunction(<function>)``, transfers,
allocations) and every ``TraceAnnotation``.

**Executions cut by the trace's edges are left out of the programs' and the
operations' sums.** The device is busy nine tenths of a window, so a trace
begins and ends inside an execution more often than not, and its module
event then holds what was left of it: one prefill of 15.0 ms among 23 of
142.0 read 136.7 at the mean, and the three prefill readings of one tree
moved by 9.7% between two traced runs (PERF.md, Open question 15). The
execution in flight at either edge is the device's earliest or latest
event, so: a module event that touches the first or the last instant of
its device's ``XLA Modules`` and ``XLA Ops`` lines is counted under
``cut``, not under ``count`` and ``total_s``, and its operations are in no
``ops`` entry. (A whole execution that happens to be the first or the last
thing on the device goes too: one sample of dozens.) Busy time and the
window are of everything, as before."""

from __future__ import annotations

import bisect
import glob
import re
import threading
import time
from pathlib import Path

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_FINGERPRINT = re.compile(r"\(\d+\)$")


class TraceWindow(threading.Thread):
    """Traces ``duration`` seconds starting at ``start_at`` (monotonic)."""

    def __init__(self, out_dir: Path, start_at: float, duration: float):
        super().__init__(name="bench-tracer", daemon=True)
        self.out_dir, self.start_at, self.duration = out_dir, start_at, duration
        self.error: BaseException | None = None

    def run(self) -> None:
        import jax

        try:
            wait = self.start_at - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            # the Python tracer halves the served rate (PERF.md, PR 25):
            # the runtime's own host spans and TraceAnnotations are kept
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(str(self.out_dir), profiler_options=options)
            try:
                time.sleep(self.duration)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — re-raised in finish()
            self.error = e

    def finish(self) -> dict:
        self.join()
        if self.error is not None:
            raise self.error
        files = sorted(glob.glob(str(self.out_dir / "**" / "*.xplane.pb"), recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {self.out_dir}")
        return reduce_file(files[-1])


def program_name(event_name: str) -> str:
    """``jit_decode_step_slots(1234)`` -> ``decode_step_slots``."""
    name = _FINGERPRINT.sub("", event_name)
    return name[4:] if name.startswith("jit_") else name


_LAUNCH = re.compile(r"^PjitFunction\((.+)\)$")
_STEM = re.compile(r"^%?([A-Za-z_\-]+?)(?:[.\d]*)?(?: = |$)")
_PARAM = re.compile(r"%params__(?:blocks___\d+___)?([a-z_0-9]+?)__")
_CALL_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_key(text: str) -> str:
    """A short name for an operation's HLO text: its stem without the
    number, and the model's leaves it reads, so that the 48 copies of one
    fusion add up: ``fusion(ff_in,ff_out)``."""
    m = _STEM.match(text)
    stem = m.group(1) if m else text[:24]
    target = _CALL_TARGET.search(text)
    if target:
        stem = f"{stem}[{target.group(1)}]"
    leaves = sorted(set(_PARAM.findall(text)))
    return f"{stem}({','.join(leaves)})" if leaves else stem


def _launches(host_events: list) -> list[tuple[float, str]]:
    """The host's launches in time order: ``PjitFunction(<name>)`` spans,
    a nested repeat of the same name inside one span counted once."""
    out: list[tuple[float, str]] = []
    open_until: dict[str, float] = {}
    for s, e, name in host_events:
        m = _LAUNCH.match(name.rsplit(": ", 1)[-1])
        if not m:
            continue
        if s < open_until.get(m.group(1), -1.0):
            continue
        open_until[m.group(1)] = e
        out.append((s, m.group(1)))
    return out


def name_modules(module_events: list, host_events: list) -> tuple[dict[str, str], float]:
    """Names for the device's module events, and the offset of the host's
    clock against the device's. ``DevicePlane`` jits a
    ``functools.partial``, so today every module is ``jit__unknown(<id>)``
    on the device, while the host's launch span carries the function's
    name: ``PjitFunction(decode_step_slots)``. The device runs programs in
    the order they were launched, so the i-th execution is paired with the
    (i + shift)-th launch, for the small shift (a launch before the trace
    began, an execution after it ended) at which the ids agree best with
    one name each; an id takes its majority. The clocks are not the same
    (the recorded trace has the host 1 ms ahead): the offset is the least
    that puts no execution before its own launch."""
    launches = _launches(host_events)
    names = {n for n, _s, _d in module_events}
    best: tuple[int, int, dict] | None = None
    for shift in sorted(range(-4, 5), key=abs):
        votes: dict[str, dict[str, int]] = {}
        for i, (name, _s, _d) in enumerate(module_events):
            j = i + shift
            if 0 <= j < len(launches):
                v = votes.setdefault(name, {})
                v[launches[j][1]] = v.get(launches[j][1], 0) + 1
        score = sum(max(v.values()) for v in votes.values())
        if best is None or score > best[0]:
            best = (score, shift, votes)
    _score, shift, votes = best if best else (0, 0, {})
    out = {}
    for name in names:
        plain = program_name(name)
        if plain != "_unknown" or name not in votes:
            out[name] = plain
        else:
            out[name] = max(votes[name].items(), key=lambda kv: kv[1])[0]
    offset = 0.0
    for i, (_name, s, _d) in enumerate(module_events):
        j = i + shift
        if 0 <= j < len(launches):
            offset = max(offset, launches[j][0] - s)
    return out, offset


def union_seconds(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Length of the union of [start, end) intervals, and the merged
    intervals, in the units given."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def reduce_planes(planes: list[dict]) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}]. Kept apart from the file reader so that the self-check
    can feed it hand-made planes too."""
    device_planes = [p for p in planes if p["name"].startswith("/device:TPU:")]
    host_planes = [p for p in planes if p["name"].startswith("/host:")]
    if not device_planes:
        return {
            "busy_s": None, "window_s": None, "programs": {}, "ops": {},
            "device_ops": [], "idle_gaps": [], "n_device_planes": 0,
            "requests": [], "cut_modules": [],
        }
    all_events = [
        (s, s + d) for p in planes for ln in p["lines"] for _n, s, d in ln["events"]
    ]
    t_lo = min(a for a, _ in all_events)
    t_hi = max(b for _, b in all_events)
    host_events = [
        (s, s + d, f"{ln['name']}: {name}" if ln["name"] else name)
        for p in host_planes for ln in p["lines"] for name, s, d in ln["events"]
    ]
    host_events.sort()
    busy_each, programs, ops = [], {}, {}
    clock_offset = 0.0
    gaps_of_first: list[tuple[float, float]] = []
    cut_modules: list[list] = []  # [program, seconds left of it], every plane's
    for pi, p in enumerate(device_planes):
        op_intervals = []
        modules = [
            ev for ln in p["lines"] if ln["name"] == MODULE_LINE
            for ev in ln["events"]
        ]
        modules.sort(key=lambda ev: ev[1])
        names, offset = name_modules(modules, host_events)
        if pi == 0:
            clock_offset = offset
        module_starts = [ev[1] for ev in modules]
        on_device = [
            (s, s + d) for ln in p["lines"]
            if ln["name"] in (MODULE_LINE, OPS_LINE) for _n, s, d in ln["events"]
        ]
        dev_lo = min((a for a, _ in on_device), default=0.0)
        dev_hi = max((b for _, b in on_device), default=0.0)
        cut = [s <= dev_lo or s + d >= dev_hi for _n, s, d in modules]
        for (name, _s, d), is_cut in zip(modules, cut):
            rec = programs.setdefault(
                names[name], {"count": 0, "total_s": 0.0, "cut": 0}
            )
            if is_cut:
                rec["cut"] += 1
                cut_modules.append([names[name], d * 1e-9])
                continue
            rec["count"] += 1
            rec["total_s"] += d * 1e-9
        for ln in p["lines"]:
            if ln["name"] == OPS_LINE:
                for name, s, d in ln["events"]:
                    op_intervals.append((s, s + d))
                    # the module this operation ran in
                    i = bisect.bisect_right(module_starts, s) - 1
                    inside = i >= 0 and s < modules[i][1] + modules[i][2]
                    if inside and cut[i]:
                        continue  # of an execution the trace's edge cut
                    prog = names[modules[i][0]] if inside else "?"
                    rec = ops.setdefault(
                        f"{prog}: {op_key(name)}", {"count": 0, "total_s": 0.0}
                    )
                    rec["count"] += 1
                    rec["total_s"] += d * 1e-9
        busy_ns, merged = union_seconds(op_intervals)
        busy_each.append(busy_ns * 1e-9)
        if pi == 0:
            edges = [(t_lo, t_lo)] + merged + [(t_hi, t_hi)]
            gaps_of_first = [
                (edges[i][1], edges[i + 1][0]) for i in range(len(edges) - 1)
                if edges[i + 1][0] > edges[i][1]
            ]
    n = len(device_planes)
    for rec in list(programs.values()) + list(ops.values()):
        rec["total_s"] /= n
        rec["count"] /= n
        if "cut" in rec:
            rec["cut"] /= n
    # onto the device's clock
    host_events = [(a - clock_offset, b - clock_offset, n) for a, b, n in host_events]
    # idle time by what the host was doing: each gap goes to the host span
    # that covers most of it
    by_host: dict[str, float] = {}
    starts = [e[0] for e in host_events]
    for a, b in gaps_of_first:
        best, best_cover = "no host span", 0.0
        i = bisect.bisect_right(starts, b)
        for s, e, name in host_events[max(0, i - 64):i]:
            cover = min(e, b) - max(s, a)
            if cover > best_cover:
                best, best_cover = name, cover
        by_host[best] = by_host.get(best, 0.0) + (b - a) * 1e-9
    # the harness's own spans, one per request: its length and the time
    # the device was busy inside it
    merged_first = _merged_first(device_planes[0])
    merged_starts = [m[0] for m in merged_first]
    requests = []
    for s, e, name in host_events:
        if "bench.req " in name:
            requests.append(
                [(e - s) * 1e-9, _covered(merged_first, merged_starts, s, e) * 1e-9]
            )
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1]["total_s"])
    return {
        "busy_s": sum(busy_each) / n,
        "window_s": (t_hi - t_lo) * 1e-9,
        "programs": programs,
        "ops": ops,
        "device_ops": [[k, v["total_s"]] for k, v in top_ops[:10]],
        "idle_gaps": [
            [k, v] for k, v in sorted(by_host.items(), key=lambda kv: -kv[1])[:10]
        ],
        "n_device_planes": n,
        "requests": requests,
        "cut_modules": cut_modules,
        "host_clock_ahead_s": clock_offset * 1e-9,
    }


def _merged_first(plane: dict) -> list[tuple[float, float]]:
    intervals = [
        (s, s + d) for ln in plane["lines"] if ln["name"] == OPS_LINE
        for _n, s, d in ln["events"]
    ]
    return union_seconds(intervals)[1]


def _covered(merged: list[tuple[float, float]], starts: list[float],
             a: float, b: float) -> float:
    """How much of [a, b) the merged intervals (``starts``: their starts)
    cover."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        total += max(0.0, min(merged[i][1], b) - max(merged[i][0], a))
        i += 1
    return total


def read_planes(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def reduce_file(path: str) -> dict:
    out = reduce_planes(read_planes(path))
    out["file"] = path
    return out


def describe(path: str, limit: int = 6) -> str:
    """Planes, lines and first events of a trace: for the look by hand."""
    rows = []
    for p in read_planes(path):
        rows.append(f"plane {p['name']!r}: {len(p['lines'])} lines")
        for ln in p["lines"]:
            rows.append(f"  line {ln['name']!r}: {len(ln['events'])} events")
            for name, s, d in ln["events"][:limit]:
                rows.append(f"    {name[:100]!r} start {s:.0f} ns dur {d:.0f} ns")
    return "\n".join(rows)
