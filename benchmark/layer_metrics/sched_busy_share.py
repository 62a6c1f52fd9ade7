"""Scheduler: the share of the scheduler thread's time it spent WORKING
— the self times of every phase but ``wait``
(engine_sched_phase_seconds_total; ``state`` included, which
``sched_host_ms_per_dispatch`` leaves out) over the self times of all
phases, between the window's two scrapes, in percent. The phases tile
the thread's wall time while the engine has work, so this is how close
the host is to being the pace-setter — over the WHOLE window, with no
capture."""
from benchmark.lib import prom

FAMILY = "engine_sched_phase_seconds_total"


def reduce(trace, run):
    before, after = run.get("metrics_before"), run.get("metrics_after")
    if before is None or after is None or FAMILY not in after:
        return None
    by = {}
    for labels, _v in after[FAMILY]:
        ph = labels.get("phase")
        by[ph] = prom.delta(before, after, FAMILY, {"phase": ph})
    total = sum(by.values())
    if total <= 0:
        return None
    return 100.0 * (total - by.get("wait", 0.0)) / total
