"""From a profiler capture (.xplane.pb) to device events, and from those
to busy time, per-module time, per-op self time and idle gaps.

Two halves. ``dump`` needs JAX (``jax.profiler.ProfileData``) and runs
in a child with JAX_PLATFORMS=cpu after the server has exited:

    python benchmark/lib/trace.py dump <capture.xplane.pb> <out.json>

It keeps the device planes only: per plane, per line, events as
``[name, start_ns, dur_ns]`` (the capture's events carry no op metadata
on this stack: no ``tf_op``, no ``hlo_module``). Everything else here is
plain arithmetic on that dump, so the tests run it on synthetic events.

Layout of a TPU capture (seen on the v5e, jax 0.9.0): one plane per chip
named ``/device:TPU:<n>``; line ``XLA Modules`` has one event per
executed program, named ``<module>(<program id>)`` — the engine's
dispatches are ``jit_dispatch_<kind>``; line ``XLA Ops`` has the HLO
instructions, each named by its WHOLE HLO line (``%fusion.12 = bf16[..]
fusion(..)``), nested (a ``while`` spans its body's ops), the Pallas
call among them under the name it was given (``ragged_paged_attention``).
"""

from __future__ import annotations

import json
import re
import sys

MODULES = "XLA Modules"
OPS = "XLA Ops"


# ----------------------------------------------------------------- dump


def dump(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    out = {"planes": [], "other_planes": []}
    for pl in pd.planes:
        if not pl.name.startswith("/device:"):
            out["other_planes"].append(pl.name)
            continue
        lines = []
        for ln in pl.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in ln.events]
            lines.append({"name": ln.name, "events": evs})
        out["planes"].append({"name": pl.name, "lines": lines})
    return out


def summary(tr: dict, top: int = 25) -> dict:
    """What a capture holds, for a first look by hand."""
    out = {"other_planes": tr.get("other_planes"), "planes": []}
    for pl in tr["planes"]:
        lines = []
        for ln in pl["lines"]:
            by: dict = {}
            for name, _s, d in ln["events"]:
                k = _strip_id(name)
                by[k] = by.get(k, 0) + d
            lines.append({
                "line": ln["name"], "events": len(ln["events"]),
                "top": sorted(by.items(), key=lambda kv: -kv[1])[:top],
                "sample": ln["events"][:3]})
        out["planes"].append({"plane": pl["name"], "lines": lines})
    return out


# ----------------------------------------------------------- arithmetic


def _strip_id(name: str) -> str:
    """``jit_dispatch_decodek(123)`` -> ``jit_dispatch_decodek``."""
    return re.sub(r"\(\d+\)$", "", name)


def chip_planes(tr: dict) -> list:
    """The per-chip planes that carry XLA lines (a chip may also have
    planes for other cores, which hold none of the program's ops)."""
    out = []
    for pl in tr["planes"]:
        names = {ln["name"] for ln in pl["lines"]}
        if MODULES in names or OPS in names:
            out.append(pl)
    return out


def events(plane: dict, line: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == line:
            return sorted(ln["events"], key=lambda e: (e[1], -e[2]))
    return []


def union(intervals: list) -> list:
    """Merged [start, end) intervals, ascending."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(plane: dict) -> list:
    evs = events(plane, OPS) or events(plane, MODULES)
    return union([(e[1], e[1] + e[2]) for e in evs if e[2] > 0])


def span_ns(tr: dict) -> "tuple[int, int]":
    """First start and last end over every chip's ops and modules."""
    lo, hi = None, None
    for pl in chip_planes(tr):
        for line in (MODULES, OPS):
            for e in events(pl, line):
                lo = e[1] if lo is None else min(lo, e[1])
                hi = e[1] + e[2] if hi is None else max(hi, e[1] + e[2])
    if lo is None:
        raise ValueError("no device event in the capture")
    return lo, hi


def busy_and_window(tr: dict) -> "tuple[float, float]":
    """(busy seconds averaged over the chips, window seconds)."""
    lo, hi = span_ns(tr)
    planes = chip_planes(tr)
    busy = [sum(e - s for s, e in busy_intervals(pl)) for pl in planes]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def module_events(tr: dict, prefixes: tuple) -> list:
    """Module events whose name (id stripped) starts with any prefix,
    over all chips' first plane (one program runs on all chips of a
    mesh at once, so the first chip stands for the step)."""
    pl = chip_planes(tr)[0]
    return [e for e in events(pl, MODULES)
            if _strip_id(e[0]).startswith(tuple(prefixes))]


def module_seconds(tr: dict, prefixes: tuple) -> float:
    return sum(e[2] for e in module_events(tr, prefixes)) / 1e9


def ops_inside(tr: dict, modules: list, match) -> list:
    """Ops (first chip) that start inside any of ``modules`` and whose
    name satisfies ``match(name)``."""
    pl = chip_planes(tr)[0]
    ivs = union([(m[1], m[1] + m[2]) for m in modules])
    out, i = [], 0
    for e in events(pl, OPS):
        while i < len(ivs) and ivs[i][1] <= e[1]:
            i += 1
        if i < len(ivs) and ivs[i][0] <= e[1] and match(e[0]):
            out.append(e)
    return out


DECODE = ("jit_dispatch_decodek", "jit_dispatch_decode1")
PREFILL = ("jit_dispatch_prefill", "jit_dispatch_mixed")


def own_name(event_name: str) -> str:
    """An op event is named by its whole HLO line, ``%fusion.12 = bf16[..]
    fusion(...)``: -> ``fusion.12`` (operands mention other ops' names)."""
    return event_name.lstrip("%").split(" ", 1)[0]


def kernel_events(tr: dict, config: dict,
                  modules: "list | None" = None) -> list:
    """The attention kernel's calls (first chip), the kernel being what
    the model file of ``config`` names (``ATTENTION_KERNELS``: op-name
    prefixes); inside ``modules`` only when given."""
    from benchmark.lib import models  # not at the top: ``dump`` runs
    # this file as a script, outside the package

    kernels = tuple(models.of(config).ATTENTION_KERNELS)

    def match(name):
        return own_name(name).startswith(kernels)
    if modules is None:
        modules = events(chip_planes(tr)[0], MODULES)
    # a call may be reported with children of its own: keep outermost
    out, end = [], -1
    for e in ops_inside(tr, modules, match):
        if e[1] >= end:
            out.append(e)
            end = e[1] + e[2]
    return out


def decode_steps(tr: dict, config: dict) -> "tuple[float, float]":
    """(token-steps the decode-only programs ran, their device seconds).
    Steps = attention-kernel calls inside them / layers: one call per
    layer per step, whatever k a decodek program was built with."""
    mods = module_events(tr, DECODE)
    calls = kernel_events(tr, config, mods)
    steps = len(calls) / float(config["num_hidden_layers"])
    return steps, sum(m[2] for m in mods) / 1e9


def self_times(evs: list) -> list:
    """Nested events -> [(event, self_ns)]: an event's duration minus
    the part its direct children cover."""
    out, stack = [], []  # stack of [event, end, child_ns]
    for e in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= e[1]:
            ev, _end, child = stack.pop()
            out.append((ev, max(0, ev[2] - child)))
        if stack:
            stack[-1][2] += e[2]
        stack.append([e, e[1] + e[2], 0])
    while stack:
        ev, _end, child = stack.pop()
        out.append((ev, max(0, ev[2] - child)))
    return out


def module_of(tr: dict):
    """-> f(start_ns) = stripped name of the module running then."""
    pl = chip_planes(tr)[0]
    mods = events(pl, MODULES)
    starts = [m[1] for m in mods]
    import bisect

    def f(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < mods[i][1] + mods[i][2]:
            return _strip_id(mods[i][0])
        return ""
    return f


def top_ops(tr: dict, n: int = 10) -> list:
    """The device operations with most self time, as [name, seconds];
    an op is named ``<module>/<op>`` with the ``jit_dispatch_`` prefix
    and trailing instruction numbers dropped, so the fusions of one
    kind in one module add up."""
    pl = chip_planes(tr)[0]
    mod = module_of(tr)
    by: dict = {}
    for e, self_ns in self_times(events(pl, OPS)):
        m = mod(e[1]).replace("jit_dispatch_", "").replace("jit_", "")
        op = re.sub(r"[.\d]+$", "", own_name(e[0]))
        key = f"{m}/{op}" if m else op
        by[key] = by.get(key, 0) + self_ns
    rows = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def _labelled_gaps(tr: dict) -> list:
    """Every idle gap of the first chip as (label, ns), labelled by the
    module that ran before it and the one after."""
    busy = busy_intervals(chip_planes(tr)[0])
    mod = module_of(tr)

    def short(t: int) -> str:
        return mod(t).replace("jit_dispatch_", "") or "?"
    return [(f"after_{short(e0 - 1)}_before_{short(s1)}", s1 - e0)
            for (_s0, e0), (s1, _e1) in zip(busy, busy[1:])]


def idle_gaps(tr: dict, n: int = 5) -> list:
    """The longest idle gaps as [label, seconds]."""
    gaps = sorted(_labelled_gaps(tr), key=lambda g: -g[1])[:n]
    return [[k, v / 1e9] for k, v in gaps]


def idle_by_neighbours(tr: dict, n: int = 10) -> list:
    """All idle time grouped by label: [label, seconds], largest first."""
    by: dict = {}
    for k, v in _labelled_gaps(tr):
        by[k] = by.get(k, 0) + v
    rows = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


if __name__ == "__main__":
    if len(sys.argv) != 4 or sys.argv[1] != "dump":
        sys.exit("usage: trace.py dump <capture.xplane.pb> <out.json>")
    tr = dump(sys.argv[2])
    with open(sys.argv[3], "w") as f:
        json.dump(tr, f)
    with open(sys.argv[3] + ".summary.json", "w") as f:
        json.dump(summary(tr), f, indent=1)
