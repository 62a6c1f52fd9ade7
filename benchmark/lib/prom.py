"""Reading the program's /metrics text (Prometheus exposition format):
samples by family and labels, deltas between two scrapes, quantiles of a
histogram's delta."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    """-> {family: [(labels dict, value)]}; comments skipped."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if not m:
            continue
        name, labels, value = m.groups()
        try:
            v = float(value)
        except ValueError:
            continue
        out.setdefault(name, []).append(
            (dict(_LABEL.findall(labels or "")), v))
    return out


def total(scrape: dict, family: str, match: "dict | None" = None) -> float:
    """Sum of a family's samples whose labels contain ``match`` (a label
    value may be a list of allowed values)."""
    s = 0.0
    for labels, v in scrape.get(family, []):
        ok = True
        for k, want in (match or {}).items():
            got = labels.get(k)
            if got != want and not (isinstance(want, list) and got in want):
                ok = False
        if ok:
            s += v
    return s


def polled(polls: "list | None", family: str,
           match: "dict | None" = None) -> list:
    """A family's total at each poll that carried it."""
    return [total(p, family, match) for p in polls or [] if family in p]


def delta(before: dict, after: dict, family: str,
          match: "dict | None" = None) -> float:
    return total(after, family, match) - total(before, family, match)


def hist_quantile(before: dict, after: dict, family: str, q: float,
                  match: "dict | None" = None) -> "float | None":
    """Quantile q (0..1) of the observations between two scrapes, by
    linear interpolation inside the bucket (Prometheus' rule). None when
    nothing was observed."""
    buckets: dict = {}
    for scrape, sign in ((after, 1.0), (before, -1.0)):
        for labels, v in scrape.get(family + "_bucket", []):
            if any(labels.get(k) != want for k, want in (match or {}).items()):
                continue
            le = labels.get("le", "")
            edge = float("inf") if le in ("+Inf", "inf") else float(le)
            buckets[edge] = buckets.get(edge, 0.0) + sign * v
    edges = sorted(buckets)
    if not edges or buckets[edges[-1]] <= 0:
        return None
    n = buckets[edges[-1]]
    rank = q * n
    prev_edge, prev_count = 0.0, 0.0
    for e in edges:
        c = buckets[e]
        if c >= rank:
            if e == float("inf"):
                return prev_edge
            if c == prev_count:
                return e
            return prev_edge + (e - prev_edge) * (rank - prev_count) / (
                c - prev_count)
        prev_edge, prev_count = e, c
    return edges[-1]
