"""From the request log to the end-to-end metrics.

The arithmetic every PR is measured by: which requests count, what a
time to first token, a gap and a time per output token are, and how a
percentile is taken. An end-to-end metric is a file
``benchmark/end_to_end/<name>.json`` naming a series and a reduction:

  {"series": "ttft_ms", "reduce": "percentile", "q": 95}
  {"series": "window_tokens", "reduce": "per_second"}
  {"reduce": "pooled", "num": "decode_span_ms", "den": "decode_gaps"}
  {"reduce": "setup"}

Series (over the requests DUE inside the window, pre-roll excluded):
  ttft_ms   first content chunk - the time the request was due
  itl_ms    every gap between consecutive content chunks of a request
            (a burst of k tokens in one write is k-1 zero gaps and one
            long one: what the user sees)
  tpot_ms   (last chunk - first chunk) / (completion tokens - 1)
  decode_span_ms, decode_gaps   per request: last chunk - first chunk,
            and completion tokens - 1. Pooled (sum over sum) they give
            the time per output token over ALL tokens of the window's
            requests, where the median over requests hangs on which
            requests happened to share their life with a long prefill
  late_ms   sent - due: how late the generator ran
  window_tokens  completion tokens streamed inside the window, by ALL
            requests (pre-roll ones still streaming included): each
            request's usage count, shared over its chunks by arrival
"""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def in_window(log: list, seconds: float) -> list:
    return [r for r in log if r["due"] is not None
            and 0.0 <= r["due"] < seconds]


def series(log: list, seconds: float, name: str) -> list:
    recs = in_window(log, seconds)
    if name == "ttft_ms":
        return [(r["chunk_t"][0] - r["due"]) * 1e3
                for r in recs if r.get("chunk_t")]
    if name == "itl_ms":
        out = []
        for r in recs:
            t = r.get("chunk_t") or []
            out += [(b - a) * 1e3 for a, b in zip(t, t[1:])]
        return out
    if name == "tpot_ms":
        out = []
        for r in recs:
            t, n = r.get("chunk_t") or [], r.get("completion_tokens") or 0
            if len(t) >= 2 and n >= 2:
                out.append((t[-1] - t[0]) * 1e3 / (n - 1))
        return out
    if name in ("decode_span_ms", "decode_gaps"):
        out = []
        for r in recs:
            t, n = r.get("chunk_t") or [], r.get("completion_tokens") or 0
            if len(t) >= 2 and n >= 2:
                out.append((t[-1] - t[0]) * 1e3
                           if name == "decode_span_ms" else n - 1)
        return out
    if name == "late_ms":
        return [(r["sent"] - r["due"]) * 1e3 for r in recs
                if r.get("sent") is not None]
    if name == "window_tokens":
        total = 0.0
        for r in log:
            t = r.get("chunk_t") or []
            if not t and r.get("completion_tokens") and r.get("end"):
                t = [r["end"]]  # no visible text: counted when it ended
            if not t:
                continue
            n = r.get("completion_tokens") or len(t)
            inside = sum(1 for x in t if 0.0 <= x < seconds)
            total += n * inside / len(t)
        return [total]
    raise ValueError(f"unknown series {name!r}")


def end_to_end(spec: dict, log: list, seconds: float,
               setup_s: float) -> "tuple[float | None, int]":
    """-> (value, sample count). None when the series is empty."""
    kind = spec["reduce"]
    if kind == "setup":
        return setup_s, 1
    if kind == "pooled":
        num = series(log, seconds, spec["num"])
        den = series(log, seconds, spec["den"])
        return (sum(num) / sum(den), len(num)) if den else (None, 0)
    vals = series(log, seconds, spec["series"])
    if not vals:
        return None, 0
    if kind == "percentile":
        return percentile(vals, float(spec["q"])), len(vals)
    if kind == "mean":
        return sum(vals) / len(vals), len(vals)
    if kind == "per_second":
        return sum(vals) / float(seconds), len(vals)
    raise ValueError(f"unknown reduction {kind!r}")


def histogram(values: list, edges: list) -> dict:
    out = {}
    for lo, hi in zip([None] + edges, edges + [None]):
        key = f"<={hi}" if hi is not None else f">{lo}"
        out[key] = sum(1 for v in values
                       if (lo is None or v > lo) and (hi is None or v <= hi))
    return out
