"""Scheduler: device-idle time while the scheduler thread was inside
``sched:harvest`` (completing ready flights; its child ``sched:emit``
is token emission, detokenising and the queue puts) over the traced
span, in percent. What the device waits for the host to take results."""
from benchmark.lib import host_trace as H


def reduce(trace, run):
    got = H.idle_under(trace, run, by="root")
    if got is None:
        return None
    by, _idle_ns, span = got
    return 100.0 * by.get("sched:harvest", 0) / span
