"""Attention kernel: the KV bytes its decode-step calls had to read,
over peak HBM bytes per second, over the device time those calls took,
in percent — with the context COUNTED by the scheduler at the dispatch
site and not inferred from the harness's request log
(``attn_kernel_roofline``). Bytes per call: the mean context tokens a
decode step's rows had to read, Δengine_attn_context_tokens_total of
the decode-only kinds ÷ Δengine_decode_steps_total between the two
scrapes that bracket the capture most tightly, x K and V bytes per
token of ONE layer (data only: no scales, no rounding up to pages, so
the share reads low rather than high). Only calls inside decode-only
programs count, on both sides of the ratio. Bound named: memory.

The bracket: the scrape taken as the capture is asked for, and the
window's own closing scrape when the capture ends just before the
window does (the harness's placement: 0.5 s). The capture's second
scrape comes only once the profiler has written the capture — seconds
during which the server, no longer held by the stop, goes on serving:
another part of the admission cycle, with other contexts."""
from benchmark.lib import prom, roofline
from benchmark.lib import trace as T

CONTEXT = "engine_attn_context_tokens_total"
STEPS = "engine_decode_steps_total"


def reduce(trace, run):
    prof = run.get("profile")
    if trace is None or not prof or not run.get("peaks"):
        return None
    before, after = prof.get("before"), prof.get("after")
    ends = prof.get("t_before", 0.0) + prof.get("duration", 0.0)
    if run.get("metrics_after") is not None \
            and 0.0 <= run.get("seconds", -1.0) - ends <= 2.0:
        after = run["metrics_after"]
    if before is None or after is None or CONTEXT not in after:
        return None
    steps = prom.delta(before, after, STEPS)
    calls = T.kernel_events(trace, run["config"],
                            T.module_events(trace, T.DECODE))
    if steps <= 0 or not calls:
        return None
    ctx = prom.delta(before, after, CONTEXT,
                     {"kind": ["decodek", "decode1"]}) / steps
    nbytes = ctx * roofline.kv_bytes_per_token(run["config"], layers=1)
    floor_s = len(calls) * nbytes / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (sum(e[2] for e in calls) / 1e9)
