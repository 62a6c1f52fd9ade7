"""Scheduler/device flight recorder: a bounded, lock-cheap timeline ring.

Samples what the serving stack actually DID over time — per-dispatch
device-flight spans (kind, composition, token counts, and — when the
cost-model predictor priced the dispatch — ``predicted_ms`` /
``measured_ms``, so per-dispatch calibration error reads directly off
the Perfetto args pane), scheduler-state counters (queue depth, busy
slots, KV pool occupancy), follower replay spans, and point events —
and exports them as Chrome-trace JSON
(``GET /debug/timeline``) that loads directly into Perfetto
(https://ui.perfetto.dev) or chrome://tracing. Offline rendering:
tools/trace_viewer.py.

Cost discipline (the reason this is NOT just more Prometheus series):
every recorded value is a host-held scalar the caller already owns —
flight durations are measured at harvest, when ``ready()`` is already
true, so a sample never forces a device sync (graftlint's
hot-path-sync rule keeps this honest). A record() is one short lock
around a list-slot store; the ring never grows, never allocates past
warm-up, and drops the oldest event on overflow by construction.

The recorder is process-global (``FLIGHT``): engine scheduler threads,
the follower replay loop and the federated proxy all write to one
timeline, each under its own track, so the exported view interleaves
them on a shared clock (perf_counter, microseconds since process
start). ``LOCALAI_TIMELINE=off`` disables recording wholesale;
``LOCALAI_TIMELINE_EVENTS`` sizes the ring (default 8192).

Two further pieces live here because they write to the same ring and
share its cost discipline:

- ``PhaseClock`` — the scheduler's phase spans (``sched:guards``,
  ``sched:admit`` > ``sched:admit:<part>`` (``ADMIT_PARTS``: ``tier``,
  ``prefix``, ``place``, ``spill``, ``assign``), ``sched:harvest`` >
  ``sched:emit``, ``sched:dispatch`` > ``sched:enqueue:<kind>``,
  ``sched:state``, ``sched:gauges``, ``sched:wait``; a name's SECOND
  field is its phase, one of ``PHASES``). A span always adds its SELF
  time to a plain float under its name (published by the engine as
  ``engine_sched_span_seconds_total`` and, summed by phase, as
  ``engine_sched_phase_seconds_total``); it enters the ring only
  when it lasted >= 1 ms; and only while a ``/debug/profile`` capture
  runs (``set_capturing``) is it also a ``jax.profiler.TraceAnnotation``
  — which puts it in the host plane of the capture's own ``.xplane.pb``,
  on the clock of the device's ``XLA Modules``/``XLA Ops`` lines.
  ``PhaseClock.snapshot`` is what a decode stall is named from
  (``stall_cause``): the per-span seconds between two dispatches.
- ``LoadWatch`` — program loads. The first execution in this process of
  a (program, input signature) traces, lowers, and compiles or fetches
  the executable from the persistent cache while the calling thread
  stands still. ``jax.monitoring`` listeners, registered once,
  attribute what fires to the dispatch bound on that thread; each load
  is counted, timed, logged with its FULL variant key, spanned as
  ``load:<kind>`` on the ``device`` track, and kept (last 32) for
  ``/backend/monitor``.
"""

from __future__ import annotations

import collections
import gc
import logging
import threading
import time
from typing import Any, Optional

from ..config import knobs
from . import metrics as tm
from .metrics import TIMELINE_RING_EVENTS

log = logging.getLogger(__name__)

# shared clock origin: every event's ts is perf_counter relative to this
_T0 = time.perf_counter()



def origin() -> float:
    """perf_counter at the timeline's zero (``ts`` of every exported
    event is microseconds since then)."""
    return _T0


# dedicated timeline thread for KV tier DMA lanes (spill/fetch spans
# interleave against the "device" track's step spans in Perfetto — the
# visual proof that a spill never blocks a device step)
KV_TIER_TRACK = "kv_tier"

# dedicated timeline thread for disaggregated-serving KV migration lanes
# (engine/kv_migrate.py capture/stage spans interleave against BOTH
# engines' "device" tracks — the visual proof that a migration never
# blocks either engine's device step)
MIGRATE_TRACK = "migrate"

# dedicated timeline thread for weight-paging DMA lanes
# (engine/weight_pager.py demote/fetch spans interleave against the
# "device" track — the visual proof that paging a model's weights in or
# out never blocks a device step)
WEIGHTS_TRACK = "weights"


def _env_capacity() -> int:
    return max(64, knobs.int_("LOCALAI_TIMELINE_EVENTS"))


class FlightRecorder:
    """Fixed-capacity ring of timeline events.

    Events are stored as compact tuples ``(ph, name, track, ts, dur,
    args)`` with perf_counter timestamps and formatted only at export —
    the recording path does no string formatting, no dict merging and
    no allocation beyond the tuple itself."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        self.capacity = capacity or _env_capacity()
        self.enabled = knobs.flag("LOCALAI_TIMELINE")
        self._lock = threading.Lock()
        self._buf: list = [None] * self.capacity
        self._n = 0  # events ever recorded (ring head = _n % capacity)
        # (track, name) -> the last value a counter series recorded
        self._last: dict = {}

    # ------------------------------------------------------- recording

    def record(self, ph: str, name: str, track: str, ts: float,
               dur: float = 0.0, args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._buf[self._n % self.capacity] = (
                ph, name, track, ts, dur, args)
            self._n += 1

    def span(self, name: str, track: str, t0: float, dur_s: float,
             args: Optional[dict] = None) -> None:
        """A complete interval (Chrome-trace "X"): host-measured start
        and duration, e.g. a device flight from enqueue to ready.
        ``args`` is caller-owned scalars only — the harvest path adds
        ``predicted_ms``/``measured_ms`` to step spans it has a
        prediction for, never anything requiring device work."""
        self.record("X", name, track, t0, dur_s, args)

    def instant(self, name: str, track: str,
                args: Optional[dict] = None) -> None:
        self.record("i", name, track, time.perf_counter(), 0.0, args)

    def sample(self, name: str, track: str, value: float) -> None:
        """A sampled counter series (Chrome-trace "C" phase): queue
        depth, busy slots, KV pool pages — Perfetto renders these as
        stacked area charts above the track. Recorded on CHANGE only:
        a series holds its value until the next sample, so repeating it
        every scheduler iteration only pushed the spans out of the
        ring."""
        key = (track, name)
        if self._last.get(key) == value:
            return
        self._last[key] = value
        self.record("C", name, track, time.perf_counter(), 0.0,
                    {"value": value})

    def transfer(self, direction: str, t0: float, dur_s: float,
                 pages: int, nbytes: int, blocking: bool = False,
                 track: str = KV_TIER_TRACK, prefix: str = "kv") -> None:
        """A tier DMA lane span (KV spill/fetch/save/load,
        engine/kv_tier.py; weight demote/fetch with ``prefix="w"``,
        engine/weight_pager.py): enqueue-to-observed-ready window
        stamped at harvest like device flights — recording one never
        forces a sync. ``blocking`` marks a transfer the scheduler
        WAITED on; the tier's contract (tests/test_kv_tier.py,
        tests/test_weight_paging.py) is that no device-step span ever
        overlaps a blocking=True transfer, because the tier never
        records one."""
        self.record("X", prefix + ":" + direction, track, t0, dur_s,
                    {"pages": pages, "bytes": nbytes,
                     "blocking": blocking})

    # ------------------------------------------------------ inspection

    def occupancy(self) -> int:
        with self._lock:
            return min(self._n, self.capacity)

    def total_recorded(self) -> int:
        with self._lock:
            return self._n

    def dropped(self) -> int:
        with self._lock:
            return max(0, self._n - self.capacity)

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self.capacity
            self._n = 0
            self._last = {}

    def update_gauge(self) -> None:
        """Refresh timeline_ring_events_count (called from the engine's
        ~1 Hz tick and at export — never per event or per iteration)."""
        TIMELINE_RING_EVENTS.set(self.occupancy())

    # ---------------------------------------------------------- export

    def export_chrome_trace(self) -> dict:
        """The ring as a Chrome-trace JSON object (Perfetto-loadable):
        ``{"traceEvents": [...], "displayTimeUnit": "ms"}`` with one
        pid for the process and one tid per track. Timestamps are
        microseconds since process start, oldest event first."""
        with self._lock:
            n = min(self._n, self.capacity)
            start = self._n - n
            rows = [self._buf[(start + i) % self.capacity]
                    for i in range(n)]
        self.update_gauge()
        tids: dict[str, int] = {}
        events: list[dict] = []
        for ph, name, track, ts, dur, args in rows:
            tid = tids.setdefault(track, len(tids) + 1)
            ev: dict = {
                "name": name, "ph": ph, "pid": 1, "tid": tid,
                "ts": round((ts - _T0) * 1e6, 1),
            }
            if ph == "X":
                ev["dur"] = round(dur * 1e6, 1)
            if ph == "i":
                ev["s"] = "t"  # thread-scoped instant marker
            if args:
                ev["args"] = args
            events.append(ev)
        meta: list[dict] = [{
            "name": "process_name", "ph": "M", "pid": 1,
            "args": {"name": "localai-tfp-tpu"},
        }]
        for track, tid in sorted(tids.items(), key=lambda kv: kv[1]):
            meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                         "tid": tid, "args": {"name": track}})
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "recorded_total": self.total_recorded(),
                "ring_capacity": self.capacity,
                "dropped": self.dropped(),
            },
        }


FLIGHT = FlightRecorder()


# ------------------------------------------------------ capture flag

# True between /debug/profile's start_trace and stop_trace (the handler
# sets it): the one condition under which spans are ALSO written as
# jax.profiler.TraceAnnotation — off, no annotation object is built
_CAPTURING = False


def set_capturing(on: bool) -> None:
    global _CAPTURING
    _CAPTURING = bool(on)


def capturing() -> bool:
    return _CAPTURING


def _annotation(name: str, args: Optional[dict]) -> Any:
    """A TraceAnnotation (entered by the caller). Its kwargs land as the
    event's stats in the capture, so values are flattened to strings."""
    import jax

    return jax.profiler.TraceAnnotation(
        name, **{k: str(v) for k, v in (args or {}).items()})


def name_os_thread(name: str) -> None:
    """Give the calling thread an OS-level name (Linux ``prctl``,
    15 bytes): the profiler names a host line after it, so the
    scheduler's spans sit on a line called ``llm-engine`` and not on
    one of several called ``python``. Best effort — elsewhere the line
    keeps the default name."""
    try:
        import ctypes

        ctypes.CDLL(None).prctl(15, name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):
        pass


# ----------------------------------------------------- phase spans

SCHED_TRACK = "scheduler"
# the label values of engine_sched_phase_seconds_total: the second
# field of a span's name (``sched:enqueue:mixed`` -> ``enqueue``)
PHASES = ("guards", "admit", "harvest", "emit", "dispatch", "enqueue",
          "state",
          "gauges", "wait")
# the parts of an admission pass, ``sched:admit:<part>``: a closed set
# too. Each adds to phase ``admit`` and to its own name
ADMIT_PARTS = ("tier", "prefix", "place", "spill", "assign")
# the span names every scheduler publishes from its start (a dispatch
# kind's ``sched:enqueue:<kind>`` joins on first use)
SPAN_NAMES = tuple("sched:" + ph for ph in PHASES) + tuple(
    "sched:admit:" + part for part in ADMIT_PARTS)
# what a decode stall can be blamed on (the ``cause`` label of
# engine_sched_stalls_total): a phase, a part of the admission pass,
# program loads, or nothing the clock covers
STALL_CAUSES = PHASES + tuple("admit:" + p for p in ADMIT_PARTS) + (
    "load", "unnamed")
# a span shorter than this stays out of the ring: /debug/timeline shows
# a stall by name, not microsecond noise
RING_MIN_S = 1e-3


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullSpan()


class _Span:
    """One named phase of one thread. Reused across entries (a phase is
    never open twice on its thread), so the hot path allocates
    nothing."""

    __slots__ = ("clock", "stack", "name", "phase", "args", "t0", "child",
                 "ann")

    def __init__(self, clock: "PhaseClock", stack: list,
                 name: str) -> None:
        self.clock, self.stack, self.name = clock, stack, name
        parts = name.split(":")
        self.phase = parts[1]
        if self.phase not in PHASES or (
                self.phase == "admit" and len(parts) > 2
                and parts[2] not in ADMIT_PARTS):
            # the counters' label values are closed sets
            raise ValueError(f"unknown scheduler phase in {name!r}")
        clock.phase_of[name] = self.phase
        clock.by_name.setdefault(name, 0.0)
        self.args: Optional[dict] = None
        self.t0 = 0.0
        self.child = 0.0  # seconds covered by child spans
        self.ann = None

    def __enter__(self) -> "_Span":
        self.child = 0.0
        self.stack.append(self)
        if _CAPTURING:
            self.ann = _annotation(self.name, self.args)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self.t0
        ann, self.ann = self.ann, None
        if ann is not None:
            ann.__exit__(*exc)
        stack = self.stack
        stack.pop()
        if stack:
            stack[-1].child += dur
        self.clock.by_name[self.name] += dur - self.child
        if dur >= RING_MIN_S:
            FLIGHT.span(self.name, self.clock.track, self.t0, dur,
                        self.args)
        return False


class PhaseClock:
    """Scheduler phase spans, balanced by construction: the only way in
    is ``with clock.span(name):``. Spans nest by the ``with`` nesting on
    their thread (stacks are per thread); a span's SELF time — its
    duration minus its children's — goes to ``by_name[name]``, so the
    names, and the phases they sum to (``totals``), tile the thread's
    wall time. A span asked for on a thread with no open root returns a
    no-op unless ``root=True``: dispatches from other threads
    (``embed``, ``warmup``) are not scheduler time.
    """

    def __init__(self, track: str = SCHED_TRACK) -> None:
        self.track = track
        self.by_name: dict[str, float] = dict.fromkeys(SPAN_NAMES, 0.0)
        self.phase_of: dict[str, str] = {
            name: name.split(":")[1] for name in SPAN_NAMES}
        self._tls = threading.local()

    @property
    def totals(self) -> dict[str, float]:
        """Self seconds by phase: ``by_name`` summed over each phase's
        names."""
        out = dict.fromkeys(PHASES, 0.0)
        # (a copy: /backend/monitor reads this from another thread
        # while the scheduler may be adding a dispatch kind's name)
        for name, sec in list(self.by_name.items()):
            out[self.phase_of[name]] += sec
        return out

    def snapshot(self, now: float) -> dict[str, float]:
        """``by_name`` as it would read if every span open on the
        calling thread closed at ``now``: a copy, plus each open span's
        self time so far — so the difference of two snapshots tiles the
        time between them, whatever was open at either end."""
        out = dict(self.by_name)
        below = now
        for sp in reversed(getattr(self._tls, "stack", ())):
            # the span above this one opened at ``below``: what it has
            # covered since is not yet in this one's ``child``
            out[sp.name] += below - sp.t0 - sp.child
            below = sp.t0
        return out

    def span(self, name: str, args: Optional[dict] = None,
             root: bool = False):
        tls = self._tls
        try:
            stack, spans = tls.stack, tls.spans
        except AttributeError:
            stack, spans = tls.stack, tls.spans = [], {}
        if not stack and not root:
            return _NULL
        sp = spans.get(name)
        if sp is None:
            sp = spans[name] = _Span(self, stack, name)
        sp.args = args
        return sp


# ---------------------------------------------------- decode stalls

# ONE fixed rule (engine.py ``_note_decode_advance``): the gap between
# two decode-advancing dispatches is a stall when it lasted at least
# STALL_MIN_S AND at least STALL_RATIO x the running mean of the gaps
# before it, once those have covered STALL_HORIZON_S. The mean is
# weighted by TIME: a model whose admission enqueues a chain of prompt
# steps milliseconds apart and then waits 0.3 s for the device to run
# them has many short gaps and one long one a cycle, and its ordinary
# long gap is no stall
STALL_MIN_S = 0.25
STALL_RATIO = 3.0
STALL_HORIZON_S = 2.0


class GapMean:
    """The running mean gap, each gap counted by the time it covered:
    a gap moves the mean ``gap / STALL_HORIZON_S`` of the way to itself
    (all the way for a longer one) — the mean of the gap a moment of
    the last seconds fell in. Stalls count into it too, so a regime
    whose ordinary gap has grown stops reading as stalls."""

    __slots__ = ("mean", "seen")

    def __init__(self) -> None:
        self.mean = 0.0
        self.seen = 0.0  # seconds of gaps the mean has taken in

    def note(self, gap: float) -> bool:
        """Whether ``gap`` is a stall against the gaps before it; then
        it is one of them."""
        stall = (self.seen >= STALL_HORIZON_S and gap >= STALL_MIN_S
                 and gap >= STALL_RATIO * self.mean)
        if self.seen:
            self.mean += min(1.0, gap / STALL_HORIZON_S) * (gap - self.mean)
        else:
            self.mean = gap
        self.seen += gap
        return stall


def cause_of(name: str) -> str:
    """``sched:admit:tier`` -> ``admit:tier``; any other span -> its
    phase (``sched:enqueue:mixed`` -> ``enqueue``)."""
    parts = name.split(":")
    if parts[1] == "admit" and len(parts) > 2:
        return "admit:" + parts[2]
    return parts[1]


def stall_cause(split: dict[str, float], gap: float,
                load_s: float) -> str:
    """Name a stall of ``gap`` seconds from the self seconds each span
    added over it (the difference of two ``PhaseClock.snapshot``s):
    ``load`` when program loads that closed inside the gap took half of
    it or more (a load hides inside the span that stood still for it),
    else the cause with the largest share, ``unnamed`` when what no
    span covers is larger than any."""
    if load_s >= 0.5 * gap:
        return "load"
    by: dict[str, float] = {}
    for name, sec in split.items():
        c = cause_of(name)
        by[c] = by.get(c, 0.0) + sec
    best = max(by, key=by.__getitem__, default="unnamed")
    if gap - sum(by.values()) > by.get(best, 0.0):
        return "unnamed"
    return best


# seconds this PROCESS has spent in garbage collections (a collection
# holds the GIL whichever thread set it off, so every thread stands
# still for it) and when the one under way began; written by the
# callback below, two clock reads a collection
_GC = [0.0, 0.0]
_gc_watched = False


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _GC[1] = time.perf_counter()
    elif _GC[1]:
        _GC[0] += time.perf_counter() - _GC[1]
        _GC[1] = 0.0


def watch_gc() -> None:
    """Start counting ``gc_seconds`` (idempotent)."""
    global _gc_watched
    with _LISTENERS_LOCK:
        if not _gc_watched:
            gc.callbacks.append(_on_gc)
            _gc_watched = True


def gc_seconds() -> float:
    return _GC[0]


# ---------------------------------------------------- program loads

# jax.monitoring event names (jax 0.9.0: jax/_src/dispatch.py,
# jax/_src/compiler.py; pinned by tests/test_sched_spans.py). Each of
# the first three is recorded as a scalar when its phase STARTS and as
# a duration when it ends; backend_compile wraps the persistent-cache
# lookup, which records cache_hits on a hit.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_BOUND = threading.local()  # .rec: the _Load this thread is inside
_LISTENERS_LOCK = threading.Lock()
_listening = False


class _Load:
    """One watched dispatch: what the listeners saw while it was bound
    to its thread, and the context manager that binds it."""

    __slots__ = ("owner", "kind", "key", "in_warmup", "t0", "trace_s",
                 "lower_s", "compile_s", "retrieval_s", "traces",
                 "compiles", "hits", "programs", "fn", "size0", "call",
                 "ann", "prev")

    def __init__(self, owner: "LoadWatch", kind: str, key: tuple,
                 in_warmup: bool) -> None:
        self.owner, self.kind, self.key = owner, kind, key
        self.in_warmup = in_warmup
        self.t0 = 0.0
        self.trace_s = self.lower_s = self.compile_s = 0.0
        self.retrieval_s = 0.0
        self.traces = self.compiles = self.hits = 0
        self.programs: list = []
        self.fn = None
        self.size0 = 0
        self.call = None  # (args, kwargs) of the jit call, by reference
        self.ann = None
        self.prev = None

    def __enter__(self) -> "_Load":
        self.prev = getattr(_BOUND, "rec", None)
        _BOUND.rec = self
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self.t0
        _BOUND.rec = self.prev
        if self.ann is not None:
            self.ann.__exit__(*exc)
        seen = bool(self.traces or self.compiles or self.programs)
        if not seen and self.fn is not None:
            seen = self.fn._cache_size() > self.size0
        if seen:
            self.owner.record(self, dur)
        return False


def _on_scalar(event: str, value: float, **kw) -> None:
    """A trace/lower/compile phase STARTS: while capturing, this is
    where the ``load:<kind>`` annotation opens (a load is only known to
    be one once JAX begins it)."""
    rec = getattr(_BOUND, "rec", None)
    if rec is None or rec.ann is not None or not _CAPTURING:
        return
    if event in (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT):
        rec.ann = _annotation("load:" + rec.kind, {"key": rec.key})
        rec.ann.__enter__()


def _on_duration(event: str, duration: float, **kw) -> None:
    rec = getattr(_BOUND, "rec", None)
    if rec is None:
        return
    if event == TRACE_EVENT:
        rec.traces += 1
        rec.trace_s += duration
    elif event == LOWER_EVENT:
        rec.lower_s += duration
        name = kw.get("fun_name")
        if name and name not in rec.programs:
            rec.programs.append(name)
    elif event == COMPILE_EVENT:
        rec.compiles += 1
        rec.compile_s += duration
    elif event == CACHE_RETRIEVAL_EVENT:
        rec.retrieval_s += duration


def _on_event(event: str, **kw) -> None:
    rec = getattr(_BOUND, "rec", None)
    if rec is not None and event == CACHE_HIT_EVENT:
        rec.hits += 1


def _listen() -> None:
    global _listening
    with _LISTENERS_LOCK:
        if _listening:
            return
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(_on_duration)
        mon.register_event_listener(_on_event)
        mon.register_scalar_listener(_on_scalar)
        _listening = True


def note_jit(fn: Any, args: tuple = (), kw: Optional[dict] = None) -> None:
    """Tell the bound load watch which jitted function the dispatch is
    about to call, and with what: the function's cache size before and
    after is the fallback evidence of a load where the listeners say
    nothing, and the arguments (references only — nothing is computed
    unless the dispatch turns out to be a load) are what a load's
    ``arg_sig`` is made from."""
    rec = getattr(_BOUND, "rec", None)
    if rec is not None and rec.fn is None:
        try:
            rec.size0 = fn._cache_size()
            rec.fn = fn
            rec.call = (args, kw or {})
        except AttributeError:
            pass


def _arg_signature(call: tuple) -> tuple:
    """(digest, lines) of a jit call's abstract signature — per array
    leaf its shape, dtype, weak type and committed-ness, everything the
    jit cache keys an executable on beyond the program. The digest
    covers every leaf; the lines list those after the first positional
    argument (the parameters: hundreds of leaves that never change).
    Two loads of one variant key differ in a line."""
    import hashlib

    from jax.tree_util import keystr, tree_flatten_with_path

    h = hashlib.blake2s(digest_size=4)
    lines = []
    for path, leaf in tree_flatten_with_path(call)[0]:
        desc = "%s%s%s%s" % (
            getattr(leaf, "dtype", type(leaf).__name__),
            list(getattr(leaf, "shape", ())),
            "w" if getattr(leaf, "weak_type", False) else "",
            "" if getattr(leaf, "committed", True) else "u")
        where = keystr(path)
        h.update((where + desc).encode())
        if not where.startswith("[0][0]"):
            lines.append(where + ":" + desc)
    return h.hexdigest(), lines


class LoadWatch:
    """Per-engine program-load accounting. ``watch(kind, key)`` binds
    the dispatch to the calling thread for the listeners; a dispatch
    during which JAX traced, lowered or compiled anything is a load."""

    KEEP = 32

    def __init__(self, model: str) -> None:
        _listen()
        self.model = model
        self._lock = threading.Lock()
        self._recent: collections.deque = collections.deque(
            maxlen=self.KEEP)  # lint: guarded-by self._lock
        self._total = 0  # lint: guarded-by self._lock
        # seconds of every load recorded so far: a plain float the
        # scheduler reads with no lock to see whether a stall's gap
        # held a load (it stood still for its own loads itself)
        self.blocked_s = 0.0

    def watch(self, kind: str, key: tuple,
              in_warmup: bool = False) -> _Load:
        return _Load(self, kind, key, in_warmup)

    def call(self, fn: Any, kind: str, key: tuple, *args) -> Any:
        """``fn(*args)`` under a watch — for jitted helpers outside the
        engine's dispatch funnel (the KV tier's page gather/scatter)."""
        with self.watch(kind, key):
            note_jit(fn, args)
            return fn(*args)

    def record(self, rec: _Load, dur: float) -> None:
        # compile = XLA compiled it; cache = every backend compile was
        # answered by the persistent cache; trace = JAX re-traced (or
        # only the jit cache grew) without reaching the backend
        source = ("trace" if not rec.compiles else
                  "cache" if rec.hits >= rec.compiles else "compile")
        n = max(1, len(rec.programs))
        sig, sig_lines = (_arg_signature(rec.call) if rec.call is not None
                          else ("", []))
        rec.call = None  # the references have served
        entry = {
            "kind": rec.kind, "key": repr(rec.key), "arg_sig": sig,
            "source": source,
            "seconds": round(dur, 4), "in_warmup": bool(rec.in_warmup),
            "programs": list(rec.programs),
            "trace_s": round(rec.trace_s, 4),
            "lower_s": round(rec.lower_s, 4),
            "compile_s": round(rec.compile_s, 4),
            "cache_retrieval_s": round(rec.retrieval_s, 4),
            "t": round(rec.t0 - _T0, 4),
        }
        tm.ENGINE_PROGRAM_LOADS.labels(
            model=self.model, kind=rec.kind, source=source).inc(n)
        tm.ENGINE_PROGRAM_LOAD_SECONDS.labels(
            model=self.model, kind=rec.kind).observe(dur)
        FLIGHT.span("load:" + rec.kind, "device", rec.t0, dur, entry)
        log.info("program load (%s): %s key=%s source=%s %.3fs "
                 "in_warmup=%s programs=%s arg_sig=%s args=%s",
                 self.model, rec.kind, entry["key"], source, dur,
                 rec.in_warmup, rec.programs, sig, " ".join(sig_lines))
        with self._lock:
            self._recent.append(entry)
            self._total += n
            self.blocked_s += dur

    def stats(self) -> dict:
        with self._lock:
            return {"total": self._total, "recent": list(self._recent)}
