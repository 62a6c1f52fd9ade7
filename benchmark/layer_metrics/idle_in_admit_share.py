"""Scheduler: device-idle time while the scheduler thread was inside
``sched:admit`` — the admission pass: the KV tier's tick, the prefix
index's sync, slot choice, the tier's capture (with the
``load:kv_gather`` it may stand still for), ``_assign`` — over the
traced span, in percent. Beside it, among the run's lines: that idle
time by innermost span, and where the idle time NO span covers lies —
before the scheduler line's first span (a span already open when the
capture began is not in it), between two root spans (the code of
``step()`` and of the loop around it that no span covers; under and
over 1 ms apart), or after the line's last span."""
from benchmark.lib import host_trace as H

ROOT = "sched:admit"
MS = 1_000_000


def unnamed_split(idle, spans):
    """Where the idle ns NO span covers lie, against the line's root
    spans (those no earlier span contains): -> ns under
    ``before_first``, ``between_roots_under_1ms`` (the statements of
    ``step()`` between two spans), ``between_roots_1ms_or_more`` (the
    loop around it: a step that raised, the engine out of work) and
    ``after_last``; a key with nothing under it is left out."""
    roots: list = []
    for _name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        if roots and start < roots[-1][1]:
            roots[-1][1] = max(roots[-1][1], start + dur)
        else:
            roots.append([start, start + dur])
    if not roots or not idle:
        return {}
    lo, hi = min(s for s, _e in idle), max(e for _s, e in idle)
    holes = []
    if lo < roots[0][0]:
        holes.append(["before_first", lo, roots[0][0] - lo])
    for (_a0, a1), (b0, _b1) in zip(roots, roots[1:]):
        if b0 > a1:
            holes.append(["between_roots_under_1ms" if b0 - a1 < MS
                          else "between_roots_1ms_or_more", a1, b0 - a1])
    if hi > roots[-1][1]:
        holes.append(["after_last", roots[-1][1], hi - roots[-1][1]])
    out = H.attribute(idle, holes)
    out.pop(H.UNNAMED, None)
    return out


def reduce(trace, run):
    if trace is None:
        return None
    spans = H.scheduler_spans(H.load(run))
    if not spans:
        return None
    idle, span = H.device_idle(trace)
    by_root = H.attribute(idle, spans, by="root")
    # the admission pass's idle time by innermost span
    inner = H.attribute(idle, [[name, s, e - s] for s, e, name, root
                               in H.flatten(spans) if root == ROOT])
    inner.pop(H.UNNAMED, None)
    print(f"idle_in_admit_share: {by_root.get(ROOT, 0)} of "
          f"{sum(e - s for s, e in idle)} idle ns under {ROOT}, by "
          f"innermost span {inner}; unnamed idle ns "
          f"{by_root.get(H.UNNAMED, 0)}: {unnamed_split(idle, spans)}",
          flush=True)
    return 100.0 * by_root.get(ROOT, 0) / span
