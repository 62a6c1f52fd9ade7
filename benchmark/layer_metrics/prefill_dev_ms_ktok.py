"""Dispatch kinds: device time of the programs that carry prompt chunks
(jit_dispatch_prefill*, jit_dispatch_mixed — a mixed program's decode
rows ride along and are counted here) per 1000 prompt tokens. The prompt
tokens are those of the requests whose first content chunk arrived
inside the traced span (their last prompt chunk ran just before), by the
harness's own request log: the capture starts when the profile call is
sent and lasts as long as the trace's own span. With a handful of
prefills in a capture, one request at either edge moves this by its
share — read it beside the count of programs in ``breakdown``."""
from benchmark.lib import trace as T


def reduce(trace, run):
    prof = run.get("profile")
    if trace is None or not prof:
        return None
    _, window = T.busy_and_window(trace)
    t0, t1 = prof["t_before"], prof["t_before"] + window
    toks = sum(r.get("prompt_tokens_served") or r["prompt_tokens"]
               for r in run["log"]
               if r.get("chunk_t") and t0 <= r["chunk_t"][0] < t1)
    if toks <= 0:
        return None
    return T.module_seconds(trace, T.PREFILL) * 1e3 / (toks / 1000.0)
