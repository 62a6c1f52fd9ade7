"""Per-layer metrics are files of their own, found by name:

``benchmark/layer_metrics/<name>.json`` — a counter, gauge or histogram
of the program's /metrics, or a path in /backend/monitor, plus a
reduction:
  {"source": "monitor", "path": "engine.hbm.peak_bytes_in_use",
   "scale": 1e-9}
  {"source": "metrics", "family": f, "match": {...}, "reduce": "delta"}
  {"source": "metrics", "family": f, "reduce": "hist_quantile", "q": 0.95,
   "scale": 1000}
  {"source": "metrics", "family": f, "reduce": "max_poll", "per": 256,
   "scale": 100}
  {"source": "metrics", "reduce": "ratio", "num": {family, match},
   "den": {family, match}, "scale": 1}

  {"source": "requests", "series": "ttft_ms", "reduce": "percentile",
   "q": 95}       a statistic of the harness's own request log, as
                  lib/reduce.py defines the end-to-end ones

``benchmark/layer_metrics/<name>.py`` — ``reduce(trace, run) -> float |
None``: ``trace`` is the dumped capture (lib/trace.py) or None, ``run``
what the harness collected (see run.py ``collect``). A reader that
finds nothing to read returns None and the metric is left out.
"""

from __future__ import annotations

import importlib.util
import json
import os

from . import prom
from . import reduce as R


def _dig(obj, path: str):
    for part in path.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return obj


def _json_metric(spec: dict, run: dict):
    scale = float(spec.get("scale", 1.0))
    if spec["source"] == "requests":
        v, _n = R.end_to_end(spec, run.get("log") or [],
                             run.get("seconds", 0.0), 0.0)
        return None if v is None else v * scale
    if spec["source"] == "monitor":
        v = _dig(run.get("monitor") or {}, spec["path"])
        return None if v is None else float(v) * scale
    before, after = run.get("metrics_before"), run.get("metrics_after")
    kind = spec["reduce"]
    if kind == "max_poll":
        vals = prom.polled(run.get("polls"), spec["family"],
                           spec.get("match"))
        if not vals:
            return None
        return max(vals) / float(spec.get("per", 1.0)) * scale
    if before is None or after is None:
        return None
    if kind == "delta":
        if spec["family"] not in after:
            return None
        return prom.delta(before, after, spec["family"],
                          spec.get("match")) * scale
    if kind == "hist_quantile":
        v = prom.hist_quantile(before, after, spec["family"],
                               float(spec["q"]), spec.get("match"))
        return None if v is None else v * scale
    if kind == "ratio":
        # a program without the counter (a parent that cannot run the
        # configuration yet) has nothing to read: that is not a share of 0
        if any(spec[k]["family"] not in after for k in ("num", "den")):
            return None
        num = prom.delta(before, after, spec["num"]["family"],
                         spec["num"].get("match"))
        den = prom.delta(before, after, spec["den"]["family"],
                         spec["den"].get("match"))
        return None if den <= 0 else num / den * scale
    raise ValueError(f"unknown reduction {kind!r}")


def find(metrics_dir: str, name: str) -> "str | None":
    for ext in (".json", ".py"):
        p = os.path.join(metrics_dir, name + ext)
        if os.path.exists(p):
            return p
    return None


def evaluate(metrics_dir: str, name: str, trace, run: dict):
    """-> float or None (nothing to read)."""
    path = find(metrics_dir, name)
    if path is None:
        raise FileNotFoundError(
            f"no reader for per-layer metric {name!r} in {metrics_dir}")
    if path.endswith(".json"):
        with open(path) as f:
            spec = json.load(f)
        return _json_metric(spec, run)
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    v = mod.reduce(trace, run)
    return None if v is None else float(v)
