"""Scheduler: decode stalls inside the window — gaps between two
decode-advancing dispatches of >= 0.25 s and >= 3 x the running mean
gap (time-weighted), counted by the engine where they happen
(engine_sched_stalls_total, every cause). The causes and their seconds
(engine_sched_stall_seconds_total) are printed among the run's lines;
the server's log has one ``decode stall`` line a stall with the whole
split. ``tpot_p50_ms``, a median, reads through a stall: this does
not."""
from benchmark.lib import prom

FAMILY = "engine_sched_stalls_total"
SECONDS = "engine_sched_stall_seconds_total"


def reduce(trace, run):
    before, after = run.get("metrics_before"), run.get("metrics_after")
    if before is None or after is None or FAMILY not in after:
        return None
    seen = {}
    for labels, _v in after[FAMILY]:
        c = labels.get("cause")
        n = prom.delta(before, after, FAMILY, {"cause": c})
        if n > 0:
            seen[c] = (n, round(prom.delta(before, after, SECONDS,
                                           {"cause": c}), 4))
    print(f"decode_stalls_in_window by cause (count, seconds): {seen}",
          flush=True)
    return prom.delta(before, after, FAMILY)
