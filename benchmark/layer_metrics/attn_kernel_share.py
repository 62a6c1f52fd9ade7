"""Attention kernel: device time of the ``ragged_paged_attention``
Pallas calls over device busy time, in percent."""
from benchmark.lib import trace as T


def reduce(trace, run):
    if trace is None:
        return None
    kern = T.kernel_events(trace)
    if not kern:
        return None
    busy, _ = T.busy_and_window(trace)
    return 100.0 * sum(e[2] for e in kern) / 1e9 / busy
