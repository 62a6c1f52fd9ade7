"""Scheduler: host milliseconds of the admission pass
(engine_sched_phase_seconds_total{phase=admit}: ``sched:admit`` and its
``sched:admit:<part>`` sub-spans, self times) per completed request
(engine_requests_total, every reason), over the window's two scrapes.
The split of a request's admission by span name
(engine_sched_span_seconds_total: ``sched:admit:tier`` the KV tier's
tick, ``:prefix`` the prefix index's sync, ``:place`` slot choice,
``:spill`` the tier's capture, ``:assign``, and ``sched:admit`` itself:
what no part covers) is printed among the run's lines; a program
without the per-span family prints the total alone."""
from benchmark.lib import prom

FAMILY = "engine_sched_phase_seconds_total"
SPANS = "engine_sched_span_seconds_total"


def reduce(trace, run):
    before, after = run.get("metrics_before"), run.get("metrics_after")
    if before is None or after is None or FAMILY not in after:
        return None
    n = prom.delta(before, after, "engine_requests_total")
    if n <= 0:
        return None
    ms = 1000.0 * prom.delta(before, after, FAMILY, {"phase": "admit"}) / n
    names = sorted({labels.get("span", "") for labels, _v in
                    after.get(SPANS, [])
                    if labels.get("span", "").startswith("sched:admit")})
    split = {name: round(1000.0 * prom.delta(
        before, after, SPANS, {"span": name}) / n, 4) for name in names}
    print(f"admit_host_ms_per_request {ms:.4f} over {n:.0f} requests, by "
          f"span: {split} (sum {sum(split.values()):.4f})", flush=True)
    return ms
