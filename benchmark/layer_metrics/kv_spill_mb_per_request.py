"""KV tier: megabytes spilled from HBM to host RAM
(engine_kv_tier_bytes_moved_total{direction=spill}; scale planes
included for an int8 cache) per completed request
(engine_requests_total, every reason), over the window's two scrapes.
What the spill POLICY costs: in a closed cell every admission spills a
session nothing returns for."""
from benchmark.lib import prom

BYTES = "engine_kv_tier_bytes_moved_total"


def reduce(trace, run):
    before, after = run.get("metrics_before"), run.get("metrics_after")
    if before is None or after is None or BYTES not in after:
        return None
    n = prom.delta(before, after, "engine_requests_total")
    if n <= 0:
        return None
    return prom.delta(before, after, BYTES, {"direction": "spill"}) / n / 1e6
