"""The program's own spans in a profiler capture, and device idle time
attributed to them.

While a ``/debug/profile`` capture runs, the scheduler writes its phase
spans (``sched:guards``, ``sched:admit``, ``sched:harvest`` >
``sched:emit``, ``sched:dispatch`` > ``sched:enqueue:<kind>``,
``sched:gauges``, ``sched:wait``) and its program loads
(``load:<kind>``) as ``jax.profiler.TraceAnnotation``s
(localai_tfp_tpu/telemetry/flightrec.py), so they land in the host
plane of the SAME ``.xplane.pb`` as the device's ``XLA Modules`` /
``XLA Ops`` lines, on one clock. ``lib/trace.py dump`` keeps the device
planes only; this is the other half:

    python benchmark/lib/host_trace.py dump <capture.xplane.pb> <out.json>

needs JAX (``jax.profiler.ProfileData``) and runs in a child with
JAX_PLATFORMS=cpu, like ``lib/trace.py dump``. It keeps, per line of a
non-device plane, the events whose name starts with ``sched:`` or
``load:`` as ``[name, start_ns, dur_ns]``. A program without the spans
(a parent commit) gives no line, every reader here then returns None.
Everything below ``dump`` is plain arithmetic, tested on synthetic
intervals.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

PREFIXES = ("sched:", "load:")
UNNAMED = ""  # attribute()'s key for idle time no span covers


# ----------------------------------------------------------------- dump


def dump(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    out: dict = {"lines": []}
    for pl in pd.planes:
        if pl.name.startswith("/device:"):
            continue
        for ln in pl.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in ln.events if e.name.startswith(PREFIXES)]
            if evs:
                out["lines"].append({"plane": pl.name, "line": ln.name,
                                     "events": evs})
    return out


def _cache_path(profile_dir: str) -> str:
    """Beside the run's ``trace.json`` when the capture sits under a run
    directory (``<run>/state/profiles/<stamp>``), else in the capture's
    own directory."""
    d = profile_dir
    for _ in range(4):
        d = os.path.dirname(d)
        if os.path.exists(os.path.join(d, "trace.json")):
            return os.path.join(d, "host_trace.json")
    return os.path.join(profile_dir, "host_trace.json")


def load(run: dict) -> "dict | None":
    """The host dump of the capture at ``run["profile"]["path"]``: made
    once, in a child off the chip, and cached. None when there is no
    capture or the dump fails."""
    prof = run.get("profile") or {}
    pdir = prof.get("path")
    if not pdir:
        return None
    out = _cache_path(pdir)
    if not os.path.exists(out):
        found = glob.glob(os.path.join(pdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            return None
        from .children import CHILDREN, child_env

        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        proc = CHILDREN.spawn(
            [sys.executable, os.path.abspath(__file__), "dump", found[0],
             out],
            cwd=root, env=child_env(root, {"JAX_PLATFORMS": "cpu"}),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        text, _ = proc.communicate(timeout=300)
        if proc.returncode != 0 or not os.path.exists(out):
            print(f"host_trace: dump failed rc={proc.returncode}: "
                  f"{text[-600:]}", file=sys.stderr, flush=True)
            return None
    with open(out) as f:
        return json.load(f)


# ----------------------------------------------------------- arithmetic


def scheduler_spans(host: "dict | None") -> list:
    """The spans of the scheduler's line: the line holding most
    ``sched:`` events (one engine serves a cell; another thread's stray
    ``load:`` is not scheduler time). -> [[name, start_ns, dur_ns]]."""
    best, n_best = [], 0
    for ln in (host or {}).get("lines", []):
        n = sum(1 for e in ln["events"] if e[0].startswith("sched:"))
        if n > n_best:
            best, n_best = ln["events"], n
    return sorted(best, key=lambda e: (e[1], -e[2]))


def phase_of(name: str) -> str:
    """``sched:enqueue:mixed`` -> ``enqueue``; ``load:mixed`` -> ``load``."""
    parts = name.split(":")
    return parts[1] if parts[0] == "sched" and len(parts) > 1 else parts[0]


def flatten(spans: list) -> list:
    """Nested spans of one thread -> disjoint segments
    ``[start, end, innermost name, root name]``, ascending: every
    instant belongs to the innermost span that covers it."""
    out: list = []
    stack: list = []  # [name, end]
    cur = None  # where the open segment starts

    def emit(upto: int) -> None:
        if stack and cur is not None and upto > cur:
            out.append([cur, upto, stack[-1][0], stack[0][0]])

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            cur = stack.pop()[1]
        emit(start)
        stack.append([name, start + dur])
        cur = start
    while stack:
        emit(stack[-1][1])
        cur = stack.pop()[1]
    return out


def attribute(idle: list, spans: list, by: str = "innermost") -> dict:
    """Each instant of the ``idle`` intervals ``[start, end)`` goes to
    the innermost span covering it (``by="root"``: to that span's
    outermost ancestor). -> {span name: ns}, with what no span covers
    under ``UNNAMED``."""
    col = 2 if by == "innermost" else 3
    segs = flatten(spans)
    out: dict = {}
    i = 0
    for s, e in sorted(idle):
        covered = 0
        while i < len(segs) and segs[i][1] <= s:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < e:
            lo, hi = max(s, segs[j][0]), min(e, segs[j][1])
            if hi > lo:
                out[segs[j][col]] = out.get(segs[j][col], 0) + hi - lo
                covered += hi - lo
            j += 1
        if e - s > covered:
            out[UNNAMED] = out.get(UNNAMED, 0) + (e - s) - covered
    return out


def device_idle(trace: dict) -> "tuple[list, int]":
    """(the first chip's idle intervals inside the traced span, the
    span in ns) — the device side of ``device_idle_share``."""
    from . import trace as T

    busy = T.busy_intervals(T.chip_planes(trace)[0])
    lo, hi = T.span_ns(trace)
    idle = [(e0, s1) for (_s0, e0), (s1, _e1) in zip(busy, busy[1:])
            if s1 > e0]
    return idle, hi - lo


def idle_under(trace, run: dict, by: str = "innermost"):
    """-> ({span name: idle ns}, idle ns in all, traced span ns), or
    None when the capture holds no scheduler span or no device plane."""
    if trace is None:
        return None
    spans = scheduler_spans(load(run))
    if not spans:
        return None
    idle, span = device_idle(trace)
    return (attribute(idle, spans, by), sum(e - s for s, e in idle), span)


if __name__ == "__main__":
    if len(sys.argv) != 4 or sys.argv[1] != "dump":
        sys.exit("usage: host_trace.py dump <capture.xplane.pb> <out.json>")
    with open(sys.argv[3], "w") as f:
        json.dump(dump(sys.argv[2]), f)
