"""HTTP edge: mean client time to first content chunk (from SEND, so the
generator's own lateness is not in it) minus the engine's mean submit ->
first token (engine_ttft_seconds, delta of sum over delta of count in
the window): what the route, the template, the stream bridge and the
socket add."""
from benchmark.lib import prom
from benchmark.lib import reduce as R


def reduce(trace, run):
    b, a = run.get("metrics_before"), run.get("metrics_after")
    recs = [r for r in R.in_window(run["log"], run["seconds"])
            if r.get("chunk_t") and r.get("sent") is not None]
    if not recs or b is None or a is None:
        return None
    n = prom.delta(b, a, "engine_ttft_seconds_count")
    if n <= 0:
        return None
    engine_ms = prom.delta(b, a, "engine_ttft_seconds_sum") / n * 1e3
    client_ms = sum((r["chunk_t"][0] - r["sent"]) * 1e3
                    for r in recs) / len(recs)
    return client_ms - engine_ms
