"""Dispatch kinds: the least time a decode step could take on this chip
— (weights one step has to read at its mean row count + KV bytes of the
pages in use) / peak HBM bytes per second — over the device time a step
took, in percent. Bound named: memory (a decode step at these batch
sizes is far under the chip's compute roof). Pages in use: the mean of
the window's 1 Hz polls; rows: the ``decode_rows_mean`` reader's."""
import os

from benchmark.lib import layer_metrics, prom, roofline
from benchmark.lib import trace as T


def reduce(trace, run):
    if trace is None:
        return None
    steps, seconds = T.decode_steps(trace, run["config"])
    pages = prom.polled(run.get("polls"), "engine_kv_pages_in_use_count")
    if not steps or not pages:
        return None
    cfg = run["config"]
    rows = layer_metrics.evaluate(os.path.dirname(os.path.abspath(__file__)),
                                  "decode_rows_mean", trace, run)
    kv = (sum(pages) / len(pages) * cfg["assumed"]["kv_page_tokens"]
          * roofline.kv_bytes_per_token(cfg))
    floor_s = (roofline.decode_weight_bytes(cfg, rows or 1.0) + kv) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (seconds / steps)
