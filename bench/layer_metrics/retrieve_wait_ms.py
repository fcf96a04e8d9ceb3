"""HTTP edge and engine wave: how long a query's own two device programs
and their waits took (ms): the stages ``embed`` (the coalescer, the encode
program behind whatever the batcher has queued on the device) and
``search`` (the wave, the slab's refresh, the search program and its wait)
of the program's request clocks; a part of ``edge_inbound_ms``, whose
docstring says which clocks are averaged."""
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


stage_means = _beside("edge_inbound_ms").stage_means


def read(ctx):
    means = stage_means(ctx)
    if means is None:
        return None
    return 1e3 * (means["embed"] + means["search"])
