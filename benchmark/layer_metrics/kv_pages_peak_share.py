"""KV pool: the most pages in use at any 1 Hz poll of the window, as a
share of the pool (pool size from the configuration's ``assumed``)."""
from benchmark.lib import prom


def reduce(trace, run):
    vals = prom.polled(run.get("polls"), "engine_kv_pages_in_use_count")
    if not vals:
        return None
    return 100.0 * max(vals) / run["config"]["assumed"]["kv_pool_pages"]
