"""Scheduler: host milliseconds the scheduler thread spent working —
the self times of every phase but ``wait``
(engine_sched_phase_seconds_total) — per engine-advancing dispatch
(engine_mixed_dispatch_total, all compositions), over the window's two
scrapes. A file of code and not a ``ratio`` spec: a program without the
counter must give nothing, and a ratio of an absent family reads 0."""
from benchmark.lib import prom

FAMILY = "engine_sched_phase_seconds_total"
WORKING = ["guards", "admit", "harvest", "emit", "dispatch", "enqueue",
           "gauges"]


def reduce(trace, run):
    before, after = run.get("metrics_before"), run.get("metrics_after")
    if before is None or after is None or FAMILY not in after:
        return None
    n = prom.delta(before, after, "engine_mixed_dispatch_total")
    if n <= 0:
        return None
    host_s = prom.delta(before, after, FAMILY, {"phase": WORKING})
    # the split by phase, among the run's earlier lines (not a metric)
    split = {ph: round(1000.0 * prom.delta(before, after, FAMILY,
                                           {"phase": ph}) / n, 3)
             for ph in WORKING + ["wait"]}
    print(f"sched_host_ms_per_dispatch by phase, over {n:.0f} dispatches: "
          f"{split}", flush=True)
    return 1000.0 * host_s / n
