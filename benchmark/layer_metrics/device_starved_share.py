"""Device: the share of the window in which the device had NO step
queued while the engine had work (engine_device_starved_seconds_total:
from the harvest that emptied the flight queue to the next enqueue),
in percent. A lower bound of device idle time — a step may be queued
and the chip still wait for its operands, and the KV tier's gathers
are no steps — read over the WHOLE window with no capture: the
companion of the capture's ``device_idle_share``."""
from benchmark.lib import prom

FAMILY = "engine_device_starved_seconds_total"


def reduce(trace, run):
    before, after = run.get("metrics_before"), run.get("metrics_after")
    seconds = run.get("seconds") or 0.0
    if before is None or after is None or FAMILY not in after \
            or seconds <= 0:
        return None
    return 100.0 * prom.delta(before, after, FAMILY) / seconds
