"""Attention kernel: device time of the attention calls (the ops the
configuration's model file names: ``ragged_paged_attention``, the
Pallas call, for the types here today) over device busy time, in
percent."""
from benchmark.lib import trace as T


def reduce(trace, run):
    if trace is None:
        return None
    kern = T.kernel_events(trace, run["config"])
    if not kern:
        return None
    busy, _ = T.busy_and_window(trace)
    return 100.0 * sum(e[2] for e in kern) / 1e9 / busy
