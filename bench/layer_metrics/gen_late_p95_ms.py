"""Load generator: how late requests were sent, against when they were
due (95th percentile, ms). A starved generator reads high here."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from pwbench.loadgen import percentile  # noqa: E402


def read(ctx):
    late = [1e3 * (r.sent - r.due) for r in ctx["records"] if r.sent]
    return percentile(late, 95) if late else None
