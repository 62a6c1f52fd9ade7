"""Load generator: 95th percentile of (sent - due) over the window's
requests. Decides nothing; a starved generator must not read as a fast
server."""
from benchmark.lib import reduce as R


def reduce(trace, run):
    vals = R.series(run["log"], run["seconds"], "late_ms")
    return R.percentile(vals, 95) if vals else None
