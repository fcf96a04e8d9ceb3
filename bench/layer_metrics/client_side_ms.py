"""Load generator: the part of an answer's time that no clock of the
program covers (ms): ``done - sent`` on the client's clock less the
residence in the REST handler of the same request's clock (entry to
reply), the mean over the window's clocks (``edge_inbound_ms``'s docstring
says which) that find their request among the window's 200s. What is left
is the client's ``json.dumps``, the socket both ways, aiohttp's parse
before the handler and its write after it, and the client's ``json.loads``
of the reply: the harness's own share of ``outside_batcher_ms``, spent in
threads of the server's process. Its source is the client's clock
(``host_clock``) less a counter of the program's, both on
``time.monotonic()`` of one process.

A reply names no request, so a clock is given the request that was sent
before its handler was entered and answered after its reply was built
(of several, the one sent last: a handler is entered about a millisecond
after its request is sent, and a client has one request out)."""
import importlib.util
from pathlib import Path


def _beside(name):
    """The reader file of that name beside this one, as a module (the
    interpreter's search path is left as it is)."""
    spec = importlib.util.spec_from_file_location(
        f"_reader_{name}", Path(__file__).with_name(f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


window_clocks = _beside("edge_inbound_ms").window_clocks


def read(ctx):
    found = window_clocks(ctx)
    if found is None:
        return None
    waiting = sorted(
        (r for r in ctx["records"] if r.status == 200), key=lambda r: r.sent
    )
    outside = []
    for clock in found[1]:
        mine = [r for r in waiting if r.sent <= clock[0] and clock[-1] <= r.done]
        if mine:
            waiting.remove(mine[-1])
            outside.append(
                (mine[-1].done - mine[-1].sent) - (clock[-1] - clock[0])
            )
    if not outside:
        return None
    return 1e3 * sum(outside) / len(outside)
