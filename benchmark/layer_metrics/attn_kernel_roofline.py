"""Attention kernel: the KV bytes the decode-step calls had to read,
over peak HBM bytes per second, over the device time those calls took,
in percent. Bytes per call: the mean, over the traced span, of the
context tokens of the requests then decoding (from the harness's own
request log: prompt tokens served + tokens streamed so far) x K and V
bytes per token of ONE layer. Only calls inside decode-only programs
count, on both sides of the ratio. Bound named: memory."""
from benchmark.lib import roofline
from benchmark.lib import trace as T


def _context_tokens(log, t):
    """Tokens in the cache of every request decoding at time t."""
    total = 0
    for r in log:
        ch = r.get("chunk_t") or []
        if not ch or not (ch[0] <= t <= ch[-1]):
            continue
        done = sum(1 for x in ch if x <= t)
        n = r.get("completion_tokens") or len(ch)
        total += (r.get("prompt_tokens_served") or r["prompt_tokens"]) \
            + n * done / len(ch)
    return total


def reduce(trace, run):
    prof = run.get("profile")
    if trace is None or not prof:
        return None
    calls = T.kernel_events(trace, run["config"],
                            T.module_events(trace, T.DECODE))
    if not calls:
        return None
    _, window = T.busy_and_window(trace)
    t0, t1 = prof["t_before"], prof["t_before"] + window
    ts = [t0 + (t1 - t0) * (i + 0.5) / 20 for i in range(20)]
    ctx = sum(_context_tokens(run["log"], t) for t in ts) / len(ts)
    nbytes = ctx * roofline.kv_bytes_per_token(run["config"], layers=1)
    floor_s = len(calls) * nbytes / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (sum(e[2] for e in calls) / 1e9)
