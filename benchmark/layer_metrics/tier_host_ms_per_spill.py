"""KV tier: host milliseconds the scheduler thread spent on the tier
per spill that landed — the self times of ``sched:admit:tier`` (the
tier's tick: spills landing page by page, IO results, the eviction
scan; the weight pager's tick rides it) and ``sched:admit:spill`` (the
gather's enqueue and the D2H start) from
engine_sched_span_seconds_total, over
engine_kv_tier_moves_total{direction=spill,outcome=ok}, between the
window's two scrapes."""
from benchmark.lib import prom

SPANS = "engine_sched_span_seconds_total"
MOVES = "engine_kv_tier_moves_total"


def reduce(trace, run):
    before, after = run.get("metrics_before"), run.get("metrics_after")
    if before is None or after is None or SPANS not in after \
            or MOVES not in after:
        return None
    n = prom.delta(before, after, MOVES,
                   {"direction": "spill", "outcome": "ok"})
    if n <= 0:
        return None
    host_s = prom.delta(before, after, SPANS, {
        "span": ["sched:admit:tier", "sched:admit:spill"]})
    return 1000.0 * host_s / n
