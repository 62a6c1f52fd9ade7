"""Scheduler: the share of the capture's device-idle time that falls
under ANY of the program's own spans (``sched:*`` phases of the
scheduler thread, ``load:*`` program loads; telemetry/flightrec.py), in
percent. The spans are TraceAnnotations in the capture's host plane, on
the device lines' clock; what no span covers is idle time the program
cannot name."""
from benchmark.lib import host_trace as H


def reduce(trace, run):
    got = H.idle_under(trace, run)
    if got is None:
        return None
    by, idle_ns, _span = got
    if idle_ns <= 0:
        return 100.0  # a device that never idled left nothing unnamed
    return 100.0 * (idle_ns - by.get(H.UNNAMED, 0)) / idle_ns
