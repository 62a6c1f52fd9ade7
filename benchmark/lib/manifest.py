"""BENCHMARK.json and the files it names.

The harness is driven by data: a cell names a configuration and a
traffic mix, a metric names its reader, and each is a file found by
that name. A later PR adds a cell, a configuration, a mix or a metric by
adding files and appending entries here; it edits nothing that exists.

  benchmark/configs/<config>.json          sizes as run + serving YAML
  benchmark/models/<model_type>.py         what the harness knows of that
                                           configuration's model type
  benchmark/traffic/<traffic>.json         parameters of the generator
  benchmark/cells/<cell>.json              optional: that cell's own
                                           overrides of mix fields (the
                                           rate its sweep fixed)
  benchmark/end_to_end/<metric>.json       series + reduction
  benchmark/layer_metrics/<metric>.json|py reader of one layer's metric
"""

from __future__ import annotations

import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def bench_dir(root: str = ROOT) -> str:
    return os.path.join(root, "benchmark")


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def config_path(manifest: dict, root: str, name: str) -> str:
    return os.path.join(root, config_entry(manifest, name)["file"])


def traffic_path(root: str, name: str) -> str:
    return os.path.join(bench_dir(root), "traffic", name + ".json")


def cell_overrides(root: str, cell_name: str) -> dict:
    p = os.path.join(bench_dir(root), "cells", cell_name + ".json")
    if not os.path.exists(p):
        return {}
    with open(p) as f:
        return json.load(f)


def metrics_of(manifest: dict, kind: str, cell_name: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    with no ``workloads`` key, or with the cell in it."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def end_to_end_spec(root: str, name: str) -> dict:
    with open(os.path.join(bench_dir(root), "end_to_end",
                           name + ".json")) as f:
        return json.load(f)


def problems(manifest: dict, root: str = ROOT) -> list:
    """Everything wrong with the manifest and the files it names, as
    readable lines; empty when sound."""
    from . import layer_metrics, models

    out = []

    def name_ok(what, n):
        if not isinstance(n, str) or not NAME.match(n):
            out.append(f"{what}: bad name {n!r}")

    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        if key not in manifest:
            out.append(f"missing key {key}")
    if out:
        return out
    extra = set(manifest) - {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    if extra:
        out.append(f"unknown keys {sorted(extra)}")
    if not (isinstance(manifest["run_seconds"], int)
            and 1 <= manifest["run_seconds"] <= 51):
        out.append("run_seconds must be a whole number from 1 to 51")
    paths = manifest["paths"]
    under = lambda p: any(  # noqa: E731
        p == d or p.startswith(d.rstrip("/") + "/") for d in paths)
    cfg_names, files = set(), set()
    for c in manifest["configs"]:
        name_ok("config", c.get("name"))
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"config {c.get('name')}: keys {sorted(c)}")
        if c["name"] in cfg_names:
            out.append(f"config {c['name']} twice")
        cfg_names.add(c["name"])
        if c["file"] in files or not under(c["file"]):
            out.append(f"config {c['name']}: file {c['file']} shared or "
                       "outside paths")
        files.add(c["file"])
        if not os.path.exists(os.path.join(root, c["file"])):
            out.append(f"config {c['name']}: no file {c['file']}")
        else:
            with open(os.path.join(root, c["file"])) as f:
                mt = json.load(f).get("model_type")
            mdir = os.path.join(bench_dir(root), "models")
            if not mt or models.find(mt, mdir) is None:
                out.append(f"config {c['name']}: no model file "
                           f"benchmark/models/{mt}.py for model_type {mt!r} "
                           f"(known: {models.known(mdir)})")
        for k in c["reduced"]:
            name_ok(f"config {c['name']} reduced", k)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("end_to_end lacks setup_s")
    cells, pairs = set(), set()
    used_cfg = set()
    for w in manifest["workloads"]:
        name_ok("workload", w.get("name"))
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        if w["name"] in cells:
            out.append(f"workload {w['name']} twice")
        cells.add(w["name"])
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"pair {w['config']} x {w['traffic']} twice")
        pairs.add((w["config"], w["traffic"]))
        name_ok("traffic", w["traffic"])
        if w["config"] not in cfg_names:
            out.append(f"workload {w['name']}: unknown config")
        used_cfg.add(w["config"])
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips {w['chips']}")
        if not (1 <= len(w["why"]) <= 200) or "\n" in w["why"]:
            out.append(f"workload {w['name']}: why of {len(w['why'])} chars")
        if not os.path.exists(traffic_path(root, w["traffic"])):
            out.append(f"workload {w['name']}: no traffic file")
    for c in cfg_names - used_cfg:
        out.append(f"config {c} used by no cell")
    seen = set()
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            name_ok(kind, m.get("name"))
            if m["name"] in seen:
                out.append(f"metric {m['name']} twice")
            seen.add(m["name"])
            allowed = {"name", "unit", "better", "source", "workloads"} | (
                {"bound"} if kind == "end_to_end" else {"layer", "moves"})
            if not set(m) <= allowed or not (allowed - {"workloads"}) <= set(m):
                out.append(f"metric {m['name']}: keys {sorted(m)}")
                continue
            if not UNIT.match(m["unit"]):
                out.append(f"metric {m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                out.append(f"metric {m['name']}: source {m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    out.append(f"metric {m['name']}: unknown cell {w}")
            if kind == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    out.append(f"metric {m['name']}: end-to-end source")
                if not (0 < m["bound"] <= 0.1):
                    out.append(f"metric {m['name']}: bound {m['bound']}")
                p = os.path.join(bench_dir(root), "end_to_end",
                                 m["name"] + ".json")
                if not os.path.exists(p):
                    out.append(f"metric {m['name']}: no {p}")
            else:
                if layer_metrics.find(os.path.join(
                        bench_dir(root), "layer_metrics"), m["name"]) is None:
                    out.append(f"metric {m['name']}: no reader file")
                if m["moves"] not in e2e:
                    out.append(f"metric {m['name']}: moves {m['moves']!r} "
                               "is no end-to-end metric")
                    continue
                target = e2e[m["moves"]]
                for w in m.get("workloads", sorted(cells)):
                    if "workloads" in target and w not in target["workloads"]:
                        out.append(
                            f"metric {m['name']}: cell {w} does not report "
                            f"{m['moves']}")
    for w in cells:
        e = [m["name"] for m in metrics_of(manifest, "end_to_end", w)]
        if "setup_s" not in e or len(e) < 2:
            out.append(f"cell {w}: needs setup_s and one more end-to-end")
        if not metrics_of(manifest, "per_layer", w):
            out.append(f"cell {w}: no per-layer metric")
    four = sum(1 for w in manifest["workloads"] if w.get("chips") == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        out.append(f"{four} four-chip cells of {len(manifest['workloads'])}")
    return out
