"""HTTP edge and engine wave: an answer's time from its last token on the
host until the REST handler had built the reply (ms): the stages
``payload`` (the answering UDF's ``context_docs``), ``egress`` (the async
node's completion pass, the output wave, the hand-over to the webserver's
loop) and ``reply`` (``Json.dumps``) of the program's request clocks;
``edge_inbound_ms``'s docstring says which clocks are averaged."""
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
    return 1e3 * (means["payload"] + means["egress"] + means["reply"])
