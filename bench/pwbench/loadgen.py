"""Load from client threads in the server's own process (the chip belongs
to one process). Closed loop: each client posts its next question when its
answer returns. Open loop: requests are due at fixed times and are timed
from when they were due, however late they were sent."""

from __future__ import annotations

import threading
import time
from typing import Any

from .server import Client


class Record:
    __slots__ = ("index", "due", "sent", "done", "status", "reply", "question")

    def __init__(self, index: int, question: dict, due: float):
        self.index = index
        self.question = question
        self.due = due
        self.sent = 0.0
        self.done = 0.0
        self.status = 0
        self.reply: Any = None


def _payload(route: str, question: dict, k: int) -> dict:
    if route == "/v1/retrieve":
        return {"query": question["text"], "k": k}
    return {"prompt": question["text"], "return_context_docs": True}


def _send(client: Client, route: str, rec: Record, k: int) -> None:
    from jax.profiler import TraceAnnotation

    rec.sent = time.monotonic()
    # a span of the harness's own, on the profiler's clock when it runs
    with TraceAnnotation(f"bench.req {rec.index}"):
        try:
            rec.status, rec.reply = client.post(
                route, _payload(route, rec.question, k)
            )
        except OSError as e:  # refused, reset or timed out: a failed request
            rec.status, rec.reply = -1, repr(e)
            client.close()
    rec.done = time.monotonic()


def closed_loop(
    port: int, route: str, questions: list[dict], clients: int, k: int,
    t_begin: float, ramp_s: float, t_close: float,
) -> list[Record]:
    """Client i starts at ``t_begin + i * ramp_s / clients`` and posts its
    next question when its answer returns, until ``t_close``; a request in
    flight then is let finish and recorded with its real completion time.
    The caller opens its window at ``t_begin + ramp_s``: by then every
    client is in the loop."""
    records: list[Record] = []
    lock = threading.Lock()
    cursor = [0]

    def work(start_at: float) -> None:
        client = Client(port)
        try:
            time.sleep(max(0.0, start_at - time.monotonic()))
            while True:
                now = time.monotonic()
                if now >= t_close:
                    return
                with lock:
                    i = cursor[0]
                    cursor[0] += 1
                    if i >= len(questions):
                        return
                    rec = Record(i, questions[i], now)
                    records.append(rec)
                _send(client, route, rec, k)
        finally:
            client.close()

    threads = [
        threading.Thread(
            target=work, args=(t_begin + i * ramp_s / clients,),
            name=f"bench-client-{i}", daemon=True,
        )
        for i in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records


def open_loop(
    port: int, route: str, questions: list[dict], due: list[float], k: int,
    t0: float, max_in_flight: int, grace_s: float = 60.0,
) -> list[Record]:
    """Request i is due at ``t0 + due[i]``. A pool of ``max_in_flight``
    senders takes requests in order; one that finds no free sender waits,
    and its wait counts in its latency. Every request due in the window is
    sent; the call returns when each has an answer or ``grace_s`` past the
    last due time has gone by."""
    records = [Record(i, questions[i], t0 + d) for i, d in enumerate(due)]
    lock = threading.Lock()
    cursor = [0]
    deadline = t0 + (due[-1] if len(due) else 0.0) + grace_s

    def work() -> None:
        client = Client(port, timeout=grace_s)
        try:
            while True:
                with lock:
                    i = cursor[0]
                    if i >= len(records):
                        return
                    cursor[0] += 1
                rec = records[i]
                wait = rec.due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                if time.monotonic() > deadline:
                    rec.status, rec.reply = -2, "never sent: senders busy"
                    rec.sent = rec.done = time.monotonic()
                    continue
                _send(client, route, rec, k)
        finally:
            client.close()

    threads = [
        threading.Thread(target=work, name=f"bench-client-{i}", daemon=True)
        for i in range(max_in_flight)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile by the nearest-rank rule (no interpolation:
    a tail is one of the requests)."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    rank = max(1, int(-(-q * len(ordered) // 100)))
    return ordered[min(rank, len(ordered)) - 1]
