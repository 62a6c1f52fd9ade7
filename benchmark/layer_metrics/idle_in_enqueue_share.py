"""Scheduler: device-idle time while the scheduler thread was inside
``sched:dispatch`` — building a payload, or in its children
``sched:enqueue:<kind>`` (payload -> device arrays -> launch) and a
``load:<kind>`` they stood still for — over the traced span, in
percent. What the device waits for the host to hand it work."""
from benchmark.lib import host_trace as H


def reduce(trace, run):
    got = H.idle_under(trace, run, by="root")
    if got is None:
        return None
    by, _idle_ns, span = got
    return 100.0 * by.get("sched:dispatch", 0) / span
