"""What XLA compiled in this process, and what it loaded from the
persistent compilation cache instead.

``DevicePlane.compile_counts()`` counts a fresh signature either way, so a
run could not say whether its set-up compiled a program or found it in
``.pathway-cache/xla``. jax (0.9) tells the two apart itself, through
``jax.monitoring``: every backend compile, served from the cache or not,
ends with a ``/jax/core/compile/backend_compile_duration`` event that
carries the jitted function's name and the seconds it took, and a compile
that the cache served records ``/jax/compilation_cache/cache_hits`` first,
on the same thread. So an event with no hit before it on its thread is a
program that XLA compiled: no threshold on seconds, no guess."""

from __future__ import annotations

import threading
import time

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _plain(fun_name: str) -> str:
    """``jit(encode)`` -> ``encode``: the name a trace's module carries
    after ``jit_``, and the readers know a program by."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class CompileLog:
    """Listens from ``install()`` on; ``events`` holds one entry a backend
    compile: the function's name, the seconds, when it ended (monotonic)
    and whether the persistent cache served it."""

    def __init__(self) -> None:
        self.events: list[dict] = []
        self._thread = threading.local()

    def install(self) -> "CompileLog":
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_event(self, event: str, **_kw: object) -> None:
        if event == CACHE_HIT:
            self._thread.hit = True

    def _on_duration(self, event: str, seconds: float, **kw: object) -> None:
        if event != BACKEND_COMPILE:
            return
        hit = getattr(self._thread, "hit", False)
        self._thread.hit = False
        self.events.append({
            "program": _plain(str(kw.get("fun_name", "?"))),
            "seconds": float(seconds),
            "ended": time.monotonic(), "loaded": bool(hit),
            "thread": threading.get_ident(),
        })

    def between(self, t_from: float, t_to: float, phases: dict[str, float],
                encode_calls: list[tuple[int, float, float, int]]) -> dict:
        """The compiles that ended in [t_from, t_to). ``compiled``: those
        XLA compiled, each with its seconds, the phase it ended in
        (``phases``: name -> seconds, one after another from ``t_from``)
        and, where it ended inside one of the embedder's calls
        (``encode_calls``: rows, start, end, thread) on that call's thread,
        the call's rows. ``loaded``: of those the cache served, how many and their
        seconds together."""
        ends, t = {}, t_from
        for name, seconds in phases.items():
            t += seconds
            ends[name] = t
        compiled, loaded = [], {"programs": 0, "seconds": 0.0}
        for ev in list(self.events):
            if not t_from <= ev["ended"] < t_to:
                continue
            if ev["loaded"]:
                loaded["programs"] += 1
                loaded["seconds"] += ev["seconds"]
                continue
            entry = {
                "program": ev["program"], "seconds": ev["seconds"],
                "at_s": ev["ended"] - t_from,
                "phase": next(
                    (name for name, end in ends.items() if ev["ended"] <= end),
                    "?",
                ),
            }
            for rows, a, b, thread in encode_calls:
                if thread == ev["thread"] and a <= ev["ended"] <= b:
                    entry["rows"] = rows
            compiled.append(entry)
        return {"compiled": compiled, "loaded": loaded}
