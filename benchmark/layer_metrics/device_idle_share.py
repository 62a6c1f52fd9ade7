"""Device: 1 - (union of device-op intervals / traced span), in percent,
averaged over the chips used."""
from benchmark.lib import trace as T


def reduce(trace, run):
    if trace is None:
        return None
    busy, window = T.busy_and_window(trace)
    return 100.0 * (1.0 - busy / window)
