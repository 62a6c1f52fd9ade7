"""The scheduler says what it was doing (telemetry/flightrec.py
PhaseClock / LoadWatch, the dispatch-site counters in engine.py, the
/debug/profile repairs):

- phase spans nest and balance, their self times tile the scheduler's
  wall time and are published as engine_sched_phase_seconds_total;
- a program load is counted, timed, spanned and listed ONCE, with the
  full variant key, and a repeat of the same variant yields none;
- token positions, attention context and decode steps counted at the
  dispatch boundary match hand-computed values;
- FLIGHT.sample records on change only, so the ring keeps its spans;
- no TraceAnnotation is built unless a capture runs, and a real short
  capture's host plane holds ``sched:*`` on the ``llm-engine`` line;
- ``sched:admit`` is split by part, every span keeps its seconds by
  name, a stall is counted and named where it happens, the time the
  device had no step queued is counted, and ``sched:wait`` is entered
  again when a capture starts under it;
- /debug/profile's stop does not hold the event loop.
"""

import asyncio
import glob
import json
import logging
import threading
import time
import types

import jax
import jax.numpy as jnp
import pytest

from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import init_params
from localai_tfp_tpu.telemetry import costmodel, flightrec
from localai_tfp_tpu.telemetry.flightrec import (
    FLIGHT, FlightRecorder, LoadWatch, PhaseClock,
)
from localai_tfp_tpu.telemetry.registry import REGISTRY


@pytest.fixture(scope="module")
def model():
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512)
    params = init_params(jax.random.PRNGKey(1), spec, dtype=jnp.float32)
    return spec, params, tk


def _engine(model, **kw):
    spec, params, tk = model
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_seq", 256)
    kw.setdefault("prefill_buckets", (8, 32, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("autostart", False)
    eng = LLMEngine(spec, params, tk, **kw)
    # prefix reuse would add kvcopy dispatches whose presence hangs on
    # which donor is resident: orthogonal to everything counted here
    eng._prefix_enabled = False
    return eng


def _drain(q, timeout=120):
    while True:
        ev = q.get(timeout=timeout)
        if ev.done:
            return ev


def _step_until(eng, cond, limit=5000):
    for _ in range(limit):
        if cond():
            return
        eng.step()
    raise AssertionError("condition not reached")


def _value(family, **labels):
    """One sample of the global registry, 0.0 when absent."""
    want = ",".join(f'{k}="{v}"' for k, v in labels.items())
    for ln in REGISTRY.render().splitlines():
        if ln.startswith(family + "{" + want + "}"):
            return float(ln.rsplit(" ", 1)[1])
    return 0.0


def _ring(name_prefix):
    return [e for e in FLIGHT.export_chrome_trace()["traceEvents"]
            if e["name"].startswith(name_prefix)]


# ------------------------------------------------------- phase spans


def test_phase_spans_nest_balance_and_keep_self_time():
    clock = PhaseClock("test-sched")
    # a child asked for with no open root is a no-op (a dispatch from
    # a thread that is not the scheduler)
    with clock.span("sched:enqueue:embed"):
        pass
    assert clock.totals["enqueue"] == 0.0
    t0 = time.perf_counter()
    with clock.span("sched:dispatch", root=True):
        time.sleep(0.004)
        with clock.span("sched:enqueue:mixed", {"key": ("mixed", 1)}):
            time.sleep(0.006)
    wall = time.perf_counter() - t0
    t = clock.totals
    assert t["enqueue"] >= 0.006 and t["dispatch"] >= 0.004
    # self time only: parent and child tile the root's duration (the
    # sleeps may overshoot on a loaded machine; the tiling may not)
    assert wall - 1e-3 <= t["dispatch"] + t["enqueue"] <= wall
    # balanced through an exception: the stack is empty again, so the
    # next child is a no-op, not a child of a leaked root
    with pytest.raises(RuntimeError):
        with clock.span("sched:harvest", root=True):
            with clock.span("sched:emit"):
                raise RuntimeError("boom")
    before = dict(clock.totals)
    with clock.span("sched:emit"):
        time.sleep(0.002)
    assert clock.totals == before
    # spans of >= 1 ms reached the ring under their own names, with
    # the parent containing the child on the timeline's clock
    evs = {e["name"]: e for e in FLIGHT.export_chrome_trace()["traceEvents"]
           if e.get("tid") and e["name"].startswith("sched:")}
    d, q = evs["sched:dispatch"], evs["sched:enqueue:mixed"]
    assert d["ts"] <= q["ts"] and q["ts"] + q["dur"] <= d["ts"] + d["dur"] + 1
    assert q["args"]["key"] == ("mixed", 1)


def test_short_spans_stay_out_of_the_ring():
    rec_before = FLIGHT.total_recorded()
    clock = PhaseClock("test-sched")
    for _ in range(50):
        with clock.span("sched:guards", root=True):
            pass
    assert FLIGHT.total_recorded() == rec_before
    assert clock.totals["guards"] > 0.0


def test_phase_counters_tile_the_loop_wall_time(model):
    eng = _engine(model, tag="phases")
    try:
        q = eng.submit(GenRequest(prompt_ids=eng.tokenize("warm the jits"),
                                  max_tokens=12, ignore_eos=True))
        _step_until(eng, lambda: not eng._has_work())
        _drain(q)
        base = sum(_value("engine_sched_phase_seconds_total",
                          model="phases", phase=ph)
                   for ph in flightrec.PHASES)
        q = eng.submit(GenRequest(prompt_ids=eng.tokenize("now measured"),
                                  max_tokens=48, ignore_eos=True))
        t0 = time.perf_counter()
        _step_until(eng, lambda: not eng._has_work())
        wall = time.perf_counter() - t0
        eng._update_gauges()  # publish the last iteration's deltas
        _drain(q)
        by = {ph: _value("engine_sched_phase_seconds_total",
                         model="phases", phase=ph)
              for ph in flightrec.PHASES}
        total = sum(by.values()) - base
        # every phase is a label value; what the loop does between
        # spans (a handful of Python statements an iteration) is all
        # that may be missing
        assert 0.85 * wall <= total <= 1.001 * wall, (total, wall, by)
        assert by["wait"] > 0.0 and by["enqueue"] > 0.0
        assert by["emit"] > 0.0 and by["gauges"] > 0.0
    finally:
        eng.close()


# ---------------------------------- admission by part, spans by name


def test_admit_parts_add_to_the_phase_and_to_their_own_name():
    clock = PhaseClock("test-sched")
    t0 = time.perf_counter()
    with clock.span("sched:admit", root=True):
        time.sleep(0.002)
        with clock.span("sched:admit:tier"):
            time.sleep(0.004)
        with clock.span("sched:admit:assign"):
            time.sleep(0.003)
            with clock.span("sched:enqueue:kvcopy"):
                time.sleep(0.002)
    wall = time.perf_counter() - t0
    n = clock.by_name
    assert n["sched:admit:tier"] >= 0.004
    assert n["sched:admit:assign"] >= 0.003 and n["sched:admit"] >= 0.002
    # a part's seconds are the phase's too, a child's are its own
    assert clock.totals["admit"] == pytest.approx(
        n["sched:admit"] + n["sched:admit:tier"] + n["sched:admit:assign"])
    assert clock.totals["enqueue"] == n["sched:enqueue:kvcopy"] >= 0.002
    # by name as by phase, the self times tile the root's duration
    assert sum(n.values()) == pytest.approx(sum(clock.totals.values()))
    assert wall - 1e-3 <= sum(n.values()) <= wall
    # every name of the closed sets is there from the start, at 0
    assert set(flightrec.SPAN_NAMES) <= set(n)
    assert n["sched:admit:spill"] == 0.0
    # the parts are a closed set, like the phases
    with pytest.raises(ValueError):
        clock.span("sched:admit:nonsense", root=True)
    with pytest.raises(ValueError):
        clock.span("sched:nonsense", root=True)


def test_a_snapshot_counts_open_spans_up_to_now():
    clock = PhaseClock("test-sched")
    assert clock.snapshot(time.perf_counter()) == clock.by_name
    with clock.span("sched:dispatch", root=True):
        time.sleep(0.003)
        with clock.span("sched:enqueue:mixed"):
            time.sleep(0.002)
        t_mid = time.perf_counter()
        a = clock.snapshot(t_mid)
        with clock.span("sched:enqueue:decodek"):
            time.sleep(0.004)
            t_in = time.perf_counter()
            b = clock.snapshot(t_in)
    # nothing was written by looking
    assert clock.by_name["sched:dispatch"] > 0.0
    assert a["sched:enqueue:mixed"] >= 0.002
    # (a dispatch kind's name joins on first use)
    assert a["sched:dispatch"] >= 0.003 and "sched:enqueue:decodek" not in a
    # between the two snapshots only the open child ran: the
    # difference tiles the time between them
    d = {k: b[k] - a.get(k, 0.0) for k in b}
    assert d["sched:enqueue:decodek"] >= 0.004
    assert sum(d.values()) == pytest.approx(t_in - t_mid, abs=2e-4)
    # and closed, the totals agree with the last snapshot's view
    assert clock.by_name["sched:enqueue:decodek"] >= b[
        "sched:enqueue:decodek"]


def test_span_counters_tile_the_loop_and_sum_to_their_phase(model):
    eng = _engine(model, tag="spans")
    try:
        def by_span():
            out = {}
            for ln in REGISTRY.render().splitlines():
                if ln.startswith('engine_sched_span_seconds_total{'
                                 'model="spans"'):
                    out[ln.split('span="')[1].split('"')[0]] = float(
                        ln.rsplit(" ", 1)[1])
            return out

        # every closed-set name is scraped from the start, at 0
        assert set(flightrec.SPAN_NAMES) <= set(by_span())
        q = eng.submit(GenRequest(prompt_ids=eng.tokenize("warm the jits"),
                                  max_tokens=12, ignore_eos=True))
        _step_until(eng, lambda: not eng._has_work())
        _drain(q)
        eng._update_gauges()
        base = by_span()
        q = eng.submit(GenRequest(prompt_ids=eng.tokenize("now measured"),
                                  max_tokens=48, ignore_eos=True))
        t0 = time.perf_counter()
        _step_until(eng, lambda: not eng._has_work())
        wall = time.perf_counter() - t0
        eng._update_gauges()
        _drain(q)
        got = by_span()
        d = {k: v - base.get(k, 0.0) for k, v in got.items()}
        assert 0.85 * wall <= sum(d.values()) <= 1.001 * wall, (d, wall)
        # the two enqueue kinds come apart, the admission's parts too
        assert d["sched:enqueue:mixed"] > 0 and d["sched:enqueue:decodek"] > 0
        assert d["sched:admit:assign"] > 0 and d["sched:admit:place"] > 0
        assert d["sched:admit:tier"] > 0 and d["sched:admit:prefix"] > 0
        # summed over a phase's names the span counter IS the phase
        for ph in flightrec.PHASES:
            names = [k for k in got if k.split(":")[1] == ph]
            assert sum(got[k] for k in names) == pytest.approx(
                _value("engine_sched_phase_seconds_total", model="spans",
                       phase=ph), abs=1e-9), ph
    finally:
        eng.close()


# ------------------------------------------------------ decode stalls


@pytest.mark.parametrize("split,gap,load_s,want", [
    ({"sched:admit:tier": 0.4, "sched:wait": 0.05}, 0.46, 0.0,
     "admit:tier"),
    # the enqueue kinds are one cause; the bare admit span is its own
    ({"sched:enqueue:mixed": 0.2, "sched:enqueue:decodek": 0.2,
      "sched:wait": 0.3}, 0.7, 0.0, "enqueue"),
    ({"sched:admit": 0.3, "sched:admit:place": 0.1}, 0.4, 0.0, "admit"),
    # loads of half the gap or more: the span they hid in is not blamed
    ({"sched:enqueue:mixed": 1.0}, 1.0, 0.6, "load"),
    ({"sched:enqueue:mixed": 1.0}, 1.0, 0.4, "enqueue"),
    # what no span covers outweighs every span
    ({"sched:wait": 0.1}, 0.5, 0.0, "unnamed"),
    ({}, 0.3, 0.0, "unnamed"),
])
def test_stall_cause(split, gap, load_s, want):
    assert flightrec.stall_cause(split, gap, load_s) == want
    assert want in flightrec.STALL_CAUSES


def _after(cycle, seconds, gaps=None):
    """(stalls seen, the GapMean) after ``seconds`` of a cycle of gaps."""
    gaps = gaps or flightrec.GapMean()
    stalls, t = [], 0.0
    while t < seconds:
        for gap in cycle:
            if gaps.note(gap):
                stalls.append(gap)
            t += gap
    return stalls, gaps


@pytest.mark.parametrize("cycle,extra,want", [
    # a dense model's k-scans 0.1 s apart: 0.26 s is no stall (< 3 x
    # the mean), 0.45 s is
    ([0.1], 0.26, False),
    ([0.1], 0.45, True),
    # found on the chip (olmohybrid_docs_closed, PERF §5): an admission
    # enqueues five prompt steps 3 ms apart, the device runs them for
    # 0.3 s, two k-scans follow 0.13 s apart. Counted gap by gap the
    # mean is 0.07 s and every admission read as a stall; weighted by
    # time it is 0.2 s and the ordinary 0.32 s gap is none, 0.9 s is
    ([0.003] * 5 + [0.32, 0.13, 0.13], 0.32, False),
    ([0.003] * 5 + [0.32, 0.13, 0.13], 0.9, True),
    # under a quarter of a second nothing is a stall, whatever the mean
    ([0.002], 0.2, False),
])
def test_the_stall_rule_weights_the_mean_gap_by_time(cycle, extra, want):
    stalls, gaps = _after(cycle, 30.0)
    assert stalls == []  # the ordinary cycle counts none, from the start
    mean = gaps.mean
    assert gaps.note(extra) is want
    # nothing is a stall before the mean has taken in a horizon of gaps
    fresh = flightrec.GapMean()
    assert not fresh.note(5.0) and fresh.mean == 5.0
    fresh = flightrec.GapMean()
    fresh.note(0.01)
    assert not fresh.note(1.9) and fresh.note(0.09) is False
    assert fresh.note(6.0)  # ... and after it, it is
    # a stall counts into the mean too (one longer than the horizon IS
    # the mean) and is forgotten in a few horizons of ordinary gaps
    gaps.note(3.0)
    assert gaps.mean == 3.0
    _stalls, gaps = _after(cycle, 8 * flightrec.STALL_HORIZON_S, gaps)
    assert gaps.mean == pytest.approx(mean, rel=0.05, abs=0.005)


@pytest.fixture()
def tiered(model, monkeypatch):
    """An engine with the KV tier on, stepped by hand."""
    for knob, v in (("LOCALAI_KV_PAGE", "16"), ("LOCALAI_KV_TIER", "on"),
                    ("LOCALAI_KV_TIER_IDLE_S", "0")):
        monkeypatch.setenv(knob, v)
    eng = _engine(model, tag="stalls")
    assert eng._tier is not None
    yield eng
    eng.close()


def _stalls(model_label):
    return {c: _value("engine_sched_stalls_total", model=model_label,
                      cause=c) for c in flightrec.STALL_CAUSES}


def test_a_sleep_in_the_tiers_tick_is_one_stall_named_admit_tier(
        tiered, monkeypatch, caplog):
    eng = tiered
    # every cause is scraped from the start, at 0
    text = REGISTRY.render()
    for c in flightrec.STALL_CAUSES:
        assert ('engine_sched_stalls_total{model="stalls",cause="%s"}'
                % c) in text
        assert ('engine_sched_stall_seconds_total{model="stalls",'
                'cause="%s"}' % c) in text

    def serve(n_tokens, during=None):
        q = eng.submit(GenRequest(prompt_ids=eng.tokenize("a stream"),
                                  max_tokens=n_tokens, ignore_eos=True))
        if during is not None:
            during()
        _step_until(eng, lambda: not eng._has_work())
        _drain(q)

    # first uses load programs for seconds on the CPU (cause "load"):
    # the same traffic first, until it has reached every variant
    for _ in range(3):
        serve(64)
    # ... and nothing is a stall before the running mean has taken in
    # a horizon's worth of gaps
    while eng._gaps.seen < flightrec.STALL_HORIZON_S:
        serve(64)
    # an ordinary run counts none (once more if the machine hiccuped)
    for attempt in range(2):
        before = _stalls("stalls")
        serve(64)
        if _stalls("stalls") == before:
            break
    else:
        raise AssertionError((before, _stalls("stalls")))

    tick = eng._tier.tick
    fired = []

    def slow_tick():
        if fired == ["armed"]:
            fired.append("slept")
            time.sleep(0.4)
        return tick()

    def arm_while_decoding():
        # between two decode dispatches: the stream decodes and a few
        # gaps have set the running mean
        _step_until(eng, lambda: eng._last_decode_adv > 0
                    and eng._gaps.seen > 0)
        fired.append("armed")

    monkeypatch.setattr(eng._tier, "tick", slow_tick)
    ring0 = len(_ring("stall:"))
    sec0 = _value("engine_sched_stall_seconds_total", model="stalls",
                  cause="admit:tier")
    with caplog.at_level(logging.WARNING,
                         logger="localai_tfp_tpu.engine.engine"):
        serve(200, during=arm_while_decoding)
    assert fired == ["armed", "slept"]
    after = _stalls("stalls")
    assert after["admit:tier"] - before["admit:tier"] == 1
    # (on a shared CPU the "device" may be late once more right after:
    # a gap spent waiting for it is its own cause, and no host stall)
    others = {c: after[c] - before[c] for c in after
              if c not in ("admit:tier", "wait") and after[c] != before[c]}
    assert others == {}, (before, after)
    gap = _value("engine_sched_stall_seconds_total", model="stalls",
                 cause="admit:tier") - sec0
    assert 0.4 <= gap < 2.0
    # ONE log line with the whole split, wall against CPU seconds
    lines = [r.getMessage() for r in caplog.records
             if "decode stall" in r.getMessage()
             and "cause=wait" not in r.getMessage()]
    assert len(lines) == 1, lines
    assert "cause=admit:tier" in lines[0] and "'sched:admit:tier'" in lines[0]
    for field in ("wall_s=", "cpu_s=", "gc_s=", "load_s=", "queue_depth=",
                  "slots_busy="):
        assert field in lines[0]
    # ONE instant on the scheduler track, between the step: flights
    (ev,) = [e for e in _ring("stall:")[ring0:]
             if e["name"] != "stall:wait"]
    assert ev["name"] == "stall:admit:tier" and ev["ph"] == "i"
    assert ev["args"]["gap_s"] == pytest.approx(gap, abs=1e-3)
    # a sleep computes nothing: CPU seconds far under the wall's
    assert ev["args"]["cpu_s"] < 0.5 * ev["args"]["gap_s"]
    assert ev["args"]["split"]["sched:admit:tier"] >= 0.4
    assert ev["args"]["slots_busy"] == 1
    tracks = {e["tid"]: e["args"]["name"]
              for e in FLIGHT.export_chrome_trace()["traceEvents"]
              if e["name"] == "thread_name"}
    assert tracks[ev["tid"]] == "scheduler"
    # the histogram beside it is fed as before
    assert _value("engine_decode_stall_seconds_count", model="stalls") > 0


def test_the_offline_viewer_draws_a_stall_instant_on_its_track():
    """tools/trace_viewer.py needs no line for it: an instant is a lane
    of its own on its track, between the spans."""
    import io

    from tools import trace_viewer

    fr = FlightRecorder(capacity=16)
    t = time.perf_counter()
    fr.span("step:decodek", "device", t, 0.010)
    fr.span("sched:admit:tier", flightrec.SCHED_TRACK, t + 0.010, 0.400)
    fr.record("i", "stall:admit:tier", flightrec.SCHED_TRACK, t + 0.411,
              0.0, {"gap_s": 0.41})
    fr.span("step:decodek", "device", t + 0.412, 0.010)
    out = io.StringIO()
    assert trace_viewer.render(fr.export_chrome_trace(), out) == 0
    text = out.getvalue()
    sched = text.split("track scheduler")[1].split("track ")[0]
    assert "1 spans, 1 instants" in sched
    (lane,) = [ln for ln in sched.splitlines() if "stall:admit:tier" in ln]
    assert lane.count("█") == 1


# -------------------------------------- the device with no step queued


def test_starved_seconds_are_the_time_with_no_step_queued(model):
    eng = _engine(model, tag="starved", decode_steps=4)

    def starved():
        return _value("engine_device_starved_seconds_total",
                      model="starved")

    try:
        for _ in range(2):  # load every variant first
            q = eng.submit(GenRequest(prompt_ids=eng.tokenize("abcd"),
                                      max_tokens=40, ignore_eos=True))
            _step_until(eng, lambda: not eng._has_work())
            _drain(q)
        a = GenRequest(prompt_ids=eng.tokenize("abcd"), max_tokens=400,
                       ignore_eos=True)
        qa = eng.submit(a)
        _step_until(eng, lambda: len(eng._flights) >= 2 and all(
            f.kind == "decodek" for f in eng._flights))
        # a flight is always queued: the newest stays in the air while
        # the one before it is harvested, and nothing is starved
        for fl in eng._flights:
            jax.block_until_ready(fl.arrays)
        s0 = starved()
        newest = eng._flights[-1]
        ready = type(newest).ready
        type(newest).ready = lambda self: self is not newest and ready(self)
        try:
            assert eng._harvest() is True
            assert list(eng._flights) == [newest]
            assert eng._starved_t0 == 0.0
            assert eng._dispatch() is True
        finally:
            type(newest).ready = ready
        assert starved() == s0
        # the queue drains with work pending: starved from that harvest
        # to the next enqueue
        for fl in eng._flights:
            jax.block_until_ready(fl.arrays)
        assert eng._harvest() is True and not eng._flights
        assert eng._starved_t0 > 0.0 and eng._has_work()
        time.sleep(0.2)
        assert eng._dispatch() is True and eng._starved_t0 == 0.0
        assert 0.2 <= starved() - s0 < 0.6
        # with no work left nothing is starved: a drained queue whose
        # request was cancelled opens no interval that the next
        # request would close hours later
        for fl in eng._flights:
            jax.block_until_ready(fl.arrays)
        eng._harvest()
        assert eng._starved_t0 > 0.0
        s1 = starved()
        eng.cancel(a.id)
        eng.step()
        _step_until(eng, lambda: not eng._has_work())
        _drain(qa)
        assert eng._starved_t0 == 0.0
        assert starved() - s1 < 0.05
    finally:
        eng.close()


# ----------------------------------------- sched:wait across a capture


@pytest.mark.parametrize("flip", [True, False])
def test_wait_reenters_its_span_when_the_capture_flag_flips(
        model, monkeypatch, flip):
    built = []
    real = flightrec._annotation
    monkeypatch.setattr(flightrec, "_annotation", lambda name, args: (
        built.append(name), real(name, args))[1])
    eng = _engine(model, tag="rewait")
    polled, landed = threading.Event(), threading.Event()
    eng._flights.append(types.SimpleNamespace(
        ready=lambda: (polled.set(), landed.is_set())[1]))
    t0 = (time.perf_counter() - flightrec.origin()) * 1e6
    th = threading.Thread(target=eng._wait_for_event)
    try:
        assert not flightrec.capturing()
        th.start()
        assert polled.wait(10)  # the loop is inside its span
        time.sleep(0.02)
        if flip:
            flightrec.set_capturing(True)
            deadline = time.perf_counter() + 10
            while not built and time.perf_counter() < deadline:
                time.sleep(0.002)
            time.sleep(0.02)
        landed.set()
        th.join(timeout=10)
        assert not th.is_alive()
    finally:
        flightrec.set_capturing(False)
        landed.set()
        eng._flights.clear()
        eng.close()
    waits = [e for e in _ring("sched:wait") if e["ts"] >= t0]
    if flip:
        # left when the flag flipped, entered again under the capture:
        # the second entry is the one the capture can hold
        assert len(waits) == 2 and built == ["sched:wait"]
        assert waits[0]["ts"] + waits[0]["dur"] <= waits[1]["ts"] + 1
        assert eng._phases.by_name["sched:wait"] >= 0.03
    else:
        assert len(waits) == 1 and built == []


# ------------------------------------------------------ program loads


def test_jax_monitoring_event_names_are_the_pinned_ones():
    """The listeners key on these strings: a JAX upgrade that renames
    one must fail here, not silently stop counting loads."""
    from jax._src import dispatch

    assert flightrec.TRACE_EVENT == dispatch.JAXPR_TRACE_EVENT
    assert flightrec.LOWER_EVENT == dispatch.JAXPR_TO_MLIR_MODULE_EVENT
    assert flightrec.COMPILE_EVENT == dispatch.BACKEND_COMPILE_EVENT
    import inspect

    from jax._src import compiler

    src = inspect.getsource(compiler)
    assert f"'{flightrec.CACHE_HIT_EVENT}'" in src
    assert f'"{flightrec.CACHE_RETRIEVAL_EVENT}"' in src


def test_a_new_variant_is_one_load_and_a_repeat_is_none():
    watch = LoadWatch("loads-unit")

    @jax.jit
    def prog(x):
        return x * 2 + 1

    def loads():
        return sum(_value("engine_program_loads_total", model="loads-unit",
                          kind="unit", source=s)
                   for s in ("compile", "cache", "trace"))

    key = ("unit", 8, ("carry", False))
    out = watch.call(prog, "unit", key, jnp.ones((8,)))
    assert float(out[0]) == 3.0
    st = watch.stats()
    assert st["total"] == 1 and loads() == 1
    (entry,) = st["recent"]
    assert entry["kind"] == "unit" and entry["key"] == repr(key)
    assert entry["source"] == "compile"  # the suite runs cache-off
    assert entry["in_warmup"] is False and entry["seconds"] > 0
    assert entry["programs"] == ["jit(prog)"] or "prog" in entry["programs"][0]
    assert entry["trace_s"] > 0 and entry["compile_s"] > 0
    assert _value("engine_program_load_seconds_count", model="loads-unit",
                  kind="unit") == 1
    span = [e for e in _ring("load:unit") if e["args"]["key"] == repr(key)]
    assert len(span) == 1 and span[0]["args"]["source"] == "compile"
    # the same variant again: nothing fires, nothing is counted
    watch.call(prog, "unit", key, jnp.ones((8,)))
    assert watch.stats()["total"] == 1 and loads() == 1
    # another input signature is another program
    watch.call(prog, "unit", ("unit", 16, ("carry", False)), jnp.ones((16,)))
    assert watch.stats()["total"] == 2 and loads() == 2
    # arg_sig digests the call's abstract signature (shape, dtype, weak
    # type, committed-ness of every leaf): what tells two loads of ONE
    # key apart, should the key ever lack the input that made them two
    sigs = [e["arg_sig"] for e in watch.stats()["recent"]]
    assert all(len(x) == 8 for x in sigs) and sigs[0] != sigs[1]
    weak = flightrec._arg_signature(((jnp.asarray(1.0),), {}))
    strong = flightrec._arg_signature(((jnp.ones(()),), {}))
    assert weak[0] != strong[0]


def test_load_found_by_the_jit_cache_when_the_listeners_say_nothing(
        monkeypatch):
    """The fallback: with the monitoring events deaf (as after a JAX
    rename), a grown jit cache is still a load, under source=trace."""
    from jax._src import monitoring

    watch = LoadWatch("loads-deaf")  # registers the listeners, if new
    for lst in ("_event_duration_secs_listeners", "_event_listeners",
                "_scalar_listeners"):
        monkeypatch.setattr(monitoring, lst, [])

    @jax.jit
    def prog2(x):
        return x - 1

    watch.call(prog2, "unit", ("unit", 4), jnp.ones((4,)))
    st = watch.stats()
    assert st["total"] == 1 and st["recent"][0]["source"] == "trace"
    assert st["recent"][0]["programs"] == []
    watch.call(prog2, "unit", ("unit", 4), jnp.ones((4,)))
    assert watch.stats()["total"] == 1


def test_engine_loads_name_the_full_variant_key(model):
    eng = _engine(model, tag="loads-eng")
    try:
        # which k a scan gets hangs on arrival timing, so a repeat may
        # reach a variant the first pass did not — but never one that
        # is already loaded: no key appears twice in the load log
        for _ in range(3):
            q = eng.submit(GenRequest(prompt_ids=eng.tokenize("load me"),
                                      max_tokens=40, ignore_eos=True))
            _step_until(eng, lambda: not eng._has_work())
            _drain(q)
        st = eng._loads.stats()
        recent = st["recent"]
        assert st["total"] == len(recent) <= LoadWatch.KEEP
        kinds = [e["kind"] for e in recent]
        assert "mixed" in kinds and "decodek" in kinds
        # the key says what dispatch_key lacks: host inputs or the carry
        assert all("('carry', " in e["key"] for e in recent)
        assert len({e["key"] for e in recent}) == len(recent)
        assert all(not e["in_warmup"] and e["seconds"] > 0 for e in recent)
    finally:
        eng.close()


def test_variant_key_extends_dispatch_key_without_changing_it():
    import numpy as np

    p = {"k": 8, "window": 256, "depth": 1, "carry": True}
    assert costmodel.dispatch_key("decodek", p) == ("decodek", 8, 256, 1)
    assert costmodel.variant_key("decodek", p) == (
        "decodek", 8, 256, 1, ("carry", True))
    assert costmodel.variant_key("decodek", dict(p, carry=False)) != \
        costmodel.variant_key("decodek", p)
    mp = {"toks": np.zeros((4, 8), np.int32), "window": 256,
          "carry": False, "masks": None, "soft": None}
    assert costmodel.variant_key("mixed", mp) == (
        "mixed", (4, 8), 256, ("carry", False), ("masks", False),
        ("soft", False))
    assert costmodel.variant_key(
        "mixed", dict(mp, masks=np.ones((8, 3), bool)))[4] == ("masks", True)


def test_serving_loads_no_program_that_warmup_compiled(model):
    """Found on the chip: one key loaded twice, the two loads' arg_sig
    differing in an argument's committed-ness alone (the sampler state
    as constructed; a scan's tokens fed by the host or chained on the
    device carry) — a program lowers again for it. The engine commits
    what it makes on the host, so warmup's executables are serving's:
    a stream decoding through chained scans while a burst is admitted
    loads nothing."""
    spec, params, tk = model
    params = jax.device_put(params, jax.devices()[0])  # as a loader's
    eng = _engine((spec, params, tk), tag="committed", autostart=True)
    try:
        assert eng.cache.k.committed and eng.sampling.rng.committed
        eng.warmup()
        sizes = {k: f._cache_size() for k, f in eng._decode_k_fns.items()}
        q0 = eng.submit(GenRequest(prompt_ids=tk.encode("a live stream"),
                                   max_tokens=60, ignore_eos=True))
        while q0.get(timeout=60).token_id is None:
            pass
        qs = eng.submit_many([
            GenRequest(prompt_ids=tk.encode("burst one " * 5),
                       max_tokens=6, ignore_eos=True),
            GenRequest(prompt_ids=tk.encode("burst two"), max_tokens=6,
                       ignore_eos=True)])
        for q in (*qs, q0):
            _drain(q)
        assert sizes == {
            k: f._cache_size() for k, f in eng._decode_k_fns.items()}
        late = [e for e in eng._loads.stats()["recent"]
                if not e["in_warmup"]]
        assert not late, late
    finally:
        eng.close()


def test_warmup_loads_are_marked_in_warmup(model):
    eng = _engine(model, tag="loads-warm", n_slots=2, max_seq=64,
                  prefill_buckets=(8,), decode_steps=2)
    try:
        eng.warmup()
        st = eng._loads.stats()
        assert st["total"] >= eng.warmup_variants > 0
        assert st["recent"] and all(e["in_warmup"] for e in st["recent"])
        assert eng._in_warmup is False
    finally:
        eng.close()


# ------------------------------------------- counts at the dispatch site


def _tok(kind, part, model="counts"):
    return _value("engine_dispatch_tokens_total", model=model, kind=kind,
                  part=part)


def _ctx(kind, model="counts"):
    return _value("engine_attn_context_tokens_total", model=model, kind=kind)


def test_mixed_and_kscan_counts_match_hand_computed_values(model):
    """Stepped by hand, so every dispatch's composition is known. Decode
    rows advance at HARVEST: while a flight is still in the air, a
    slot's ``n_past`` is what it was when the flight was enqueued."""
    eng = _engine(model, tag="counts", decode_steps=8)
    try:
        a = GenRequest(prompt_ids=eng.tokenize("abcd"), max_tokens=200,
                       ignore_eos=True)
        qa = eng.submit(a)
        _step_until(eng, lambda: any(
            s.state.name == "DECODE" for s in eng.slots))
        (sa,) = [s for s in eng.slots if s.active]
        # a lone prompt of n tokens rode a mixed step with no row
        # decoding: real n, padded 4 decode rows + a prompt group of
        # [4, 8] (under a rung's worth of tokens the row counts merge
        # into the slot count), causal context n(n-1)/2
        n = len(a.prompt_ids)
        assert (_tok("mixed", "real"), _tok("mixed", "padded")) == (
            n, 4 + 4 * 8)
        assert _ctx("mixed") == n * (n - 1) // 2

        # B arrives while A decodes: ONE mixed step, behind A's scans
        b = GenRequest(prompt_ids=eng.tokenize("hello!"), max_tokens=200,
                       ignore_eos=True)
        nb = len(b.prompt_ids)
        assert nb <= 8
        qb = eng.submit(b)
        _step_until(eng, lambda: any(f.kind == "mixed"
                                     for f in eng._flights))

        def before(fl):  # positions A's row is ahead by at ``fl``
            fls = list(eng._flights)
            at = [f is fl for f in fls].index(True)
            return sum(f.meta["k"] if f.kind == "decodek" else 1
                       for f in fls[:at])

        mx = eng._flights[-1]
        assert mx.kind == "mixed"
        ca = sa.n_past + before(mx)  # A's cache as the step runs
        assert _tok("mixed", "real") - n == 1 + nb   # A's row + B's chunk
        assert _tok("mixed", "padded") == 2 * (4 + 4 * 8)
        assert _ctx("mixed") - n * (n - 1) // 2 == ca + nb * (nb - 1) // 2
        # so far only A's lone scans: one row a step
        steps0 = _value("engine_decode_steps_total", model="counts")
        real0, pad0, ctx0 = (_tok("decodek", "real"),
                             _tok("decodek", "padded"), _ctx("decodek"))
        assert steps0 == real0 > 0 and pad0 == 4 * real0

        # both decode: the next k-scan, chained behind the step, has
        # two rows at known contexts — B joined the carry on the device
        _step_until(eng, lambda: any(
            f.kind == "decodek" and len(f.meta["pairs"]) == 2
            for f in eng._flights))
        fl = eng._flights[-1]
        # (behind the step if it is still in the air: a chained scan
        # reads its rows' first tokens from the carry)
        assert (fl.meta["prev_last"] is None) == (len(eng._flights) > 1)
        k = fl.meta["k"]
        ctxs = [sa.n_past + before(fl), nb]
        assert ctxs[0] == ca + 1
        assert _value("engine_decode_steps_total",
                      model="counts") - steps0 == k
        assert _tok("decodek", "real") - real0 == 2 * k
        assert _tok("decodek", "padded") - pad0 == 4 * k
        assert _ctx("decodek") - ctx0 == sum(
            sum(c + j for j in range(k)) for c in ctxs)
        eng.cancel(a.id)
        eng.cancel(b.id)
        _step_until(eng, lambda: not eng._has_work())
        _drain(qa), _drain(qb)
    finally:
        eng.close()


def test_a_long_prompts_chain_streams_between_its_chunks(model):
    """A prompt of many chunks is enqueued as ONE chain, and what lands
    meanwhile is harvested between two chunks: the rows that decode
    beside it are not held to the chain's end (on the chip a 20-chunk
    chain takes 0.35 s to enqueue)."""
    eng = _engine(model, tag="chain", decode_steps=2,
                  prefill_buckets=(8, 32))
    try:
        a = GenRequest(prompt_ids=eng.tokenize("abcd"), max_tokens=200,
                       ignore_eos=True)
        qa = eng.submit(a)
        _step_until(eng, lambda: any(
            s.state.name == "DECODE" for s in eng.slots))
        order = []
        enq, harvest, dispatch = (eng._enqueue_mixed, eng._harvest,
                                  eng._dispatch)

        def wrap(tag, fn):
            def inner(*args):
                out = fn(*args)
                order.append(tag if out is not False else tag.lower())
                return out
            return inner

        eng._enqueue_mixed = wrap("E", enq)
        eng._harvest = wrap("H", harvest)
        eng._dispatch = wrap("|", dispatch)
        b = GenRequest(prompt_ids=[1 + i % 200 for i in range(5 * 32)],
                       max_tokens=4, ignore_eos=True)
        qb = eng.submit(b)
        _step_until(eng, lambda: order.count("E") == 5)
        # the five chunks rode chains, one chain a _dispatch call; a
        # chain breaks only where a step had to wait for a harvest ("e")
        chains = [c for c in "".join(order).upper().split("|") if "E" in c]
        assert sum(c.count("E") for c in chains) == 5
        assert any(c.count("E") > 1 for c in chains)
        for c in chains:
            # step()'s own harvest, then E (H E)*: never two chunks
            # enqueued with no harvest between them
            assert "EE" not in c and not c.endswith("EH"), order
        eng.cancel(a.id)
        _step_until(eng, lambda: not eng._has_work())
        assert _drain(qb).completion_tokens == 4
        _drain(qa)
    finally:
        eng.close()


# ------------------------------------------------------- FLIGHT repairs


def test_sample_records_a_series_only_when_its_value_changes():
    fr = FlightRecorder(capacity=64)
    for _ in range(100):
        fr.sample("queue_depth", "scheduler", 0)
    assert fr.total_recorded() == 1
    fr.sample("queue_depth", "scheduler", 3)
    fr.sample("queue_depth", "scheduler", 3)
    fr.sample("slots_busy", "scheduler", 3)  # another series
    fr.sample("queue_depth", "other-track", 3)  # another track
    assert fr.total_recorded() == 4
    fr.clear()
    fr.sample("queue_depth", "scheduler", 3)  # clear() forgets the last
    assert fr.total_recorded() == 1


def test_ring_keeps_its_first_step_span_through_steady_serving(model):
    """What the ring takes is bounded per DISPATCH (its step: span and
    the phases around it that lasted >= 1 ms), with nothing per
    scheduler iteration: at a serving dispatch rate (a 7B k-scan is
    ~0.2 s, so < 10 dispatches a second) 60 s of steady serving is
    < 3000 of the ring's 8192 events and its first ``step:`` span is
    still there. The toy model's scans take milliseconds, so the
    bound is checked per dispatch, not per second."""
    eng = _engine(model, tag="ring", autostart=True)
    iterations = []
    orig = eng._update_gauges
    eng._update_gauges = lambda: (iterations.append(1), orig())[1]
    try:
        _drain(eng.submit(GenRequest(
            prompt_ids=eng.tokenize("steady serving"),
            max_tokens=16, ignore_eos=True)))
        first = min(e["ts"] for e in _ring("step:"))
        dropped0 = FLIGHT.dropped()

        def dispatches():
            return sum(_value("engine_mixed_dispatch_total", model="ring",
                              composition=c)
                       for c in ("mixed", "prefill_only", "decode_only"))

        n0, d0, i0 = FLIGHT.total_recorded(), dispatches(), len(iterations)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 2.0:
            _drain(eng.submit(GenRequest(
                prompt_ids=eng.tokenize("steady serving"),
                max_tokens=64, ignore_eos=True)))
        recorded = FLIGHT.total_recorded() - n0
        n_disp, n_iter = dispatches() - d0, len(iterations) - i0
        assert n_disp > 5 and n_iter > n_disp
        assert recorded <= 5 * n_disp + 20, (recorded, n_disp, n_iter)
        assert 10 * 60 * 5 < FLIGHT.capacity
        if FLIGHT.dropped() == dropped0 == 0:
            assert min(e["ts"] for e in _ring("step:")) == first
    finally:
        eng.close()


# ------------------------------------------------ capture on and off


def test_no_annotation_is_built_unless_a_capture_runs(model, monkeypatch):
    built = []
    real = flightrec._annotation

    def counting(name, args):
        built.append(name)
        return real(name, args)

    monkeypatch.setattr(flightrec, "_annotation", counting)
    eng = _engine(model, tag="ann")
    try:
        assert not flightrec.capturing()
        q = eng.submit(GenRequest(prompt_ids=eng.tokenize("quiet"),
                                  max_tokens=8, ignore_eos=True))
        _step_until(eng, lambda: not eng._has_work())
        _drain(q)
        assert built == []
        flightrec.set_capturing(True)
        try:
            q = eng.submit(GenRequest(prompt_ids=eng.tokenize("quiet"),
                                      max_tokens=8, ignore_eos=True))
            _step_until(eng, lambda: not eng._has_work())
            _drain(q)
        finally:
            flightrec.set_capturing(False)
        assert {"sched:admit", "sched:dispatch", "sched:harvest",
                "sched:emit", "sched:wait", "sched:admit:tier",
                "sched:admit:prefix", "sched:admit:place",
                "sched:admit:assign"} <= set(built)
        assert any(n.startswith("sched:enqueue:") for n in built)
    finally:
        eng.close()


def _profile_app(tmp_path, monkeypatch, max_s="0.5"):
    from aiohttp.test_utils import TestClient, TestServer

    from localai_tfp_tpu.config.app_config import ApplicationConfig
    from localai_tfp_tpu.server.app import build_app
    from localai_tfp_tpu.server.state import Application

    monkeypatch.setenv("LOCALAI_PROFILER", "on")
    monkeypatch.setenv("LOCALAI_PROFILER_MAX_S", max_s)
    (tmp_path / "models").mkdir()
    cfg = ApplicationConfig(
        models_path=str(tmp_path / "models"),
        generated_content_dir=str(tmp_path / "generated"),
        upload_dir=str(tmp_path / "uploads"),
        config_dir=str(tmp_path / "configuration"),
        state_dir=str(tmp_path / "state"),
    )
    loop = asyncio.new_event_loop()
    tc = TestClient(TestServer(build_app(Application(cfg))), loop=loop)
    loop.run_until_complete(tc.start_server())
    return loop, tc


def test_a_real_capture_holds_sched_spans_on_the_engine_line(
        model, tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    loop, tc = _profile_app(tmp_path, monkeypatch)
    eng = _engine(model, tag="capture", autostart=True)
    try:
        # the capture must not fall inside a program load (seconds of
        # one open sched:dispatch on the CPU): the same traffic first
        _drain(eng.submit(GenRequest(
            prompt_ids=eng.tokenize("captured traffic"),
            max_tokens=24, ignore_eos=True)))
        stop = threading.Event()

        def traffic():
            while not stop.is_set():
                _drain(eng.submit(GenRequest(
                    prompt_ids=eng.tokenize("captured traffic"),
                    max_tokens=24, ignore_eos=True)))

        th = threading.Thread(target=traffic)
        th.start()

        def quiet_spell():
            # ... until it has reached every variant it will
            seen, since = -1, time.perf_counter()
            while time.perf_counter() - since < 0.8:
                n = eng._loads.stats()["total"]
                if n != seen:
                    seen, since = n, time.perf_counter()
                time.sleep(0.05)

        async def capture():
            r = await tc.request("GET", "/debug/profile",
                                 params={"duration": "0.5"})
            return r.status, await r.json()

        def host_lines(info):
            (pb,) = glob.glob(info["path"] + "/**/*.xplane.pb",
                              recursive=True)
            lines = {}
            for pl in ProfileData.from_file(pb).planes:
                if pl.name.startswith("/device:"):
                    continue
                for ln in pl.lines:
                    names = {e.name for e in ln.events
                             if e.name.startswith(("sched:", "load:"))}
                    if names:
                        lines.setdefault(ln.name, set()).update(names)
            return lines

        want = {"sched:admit", "sched:harvest", "sched:dispatch",
                "sched:gauges", "sched:wait", "sched:emit"}
        try:
            # on a loaded machine one scheduler phase can outlast a
            # short capture (a span that opened before it is not in
            # it): a few attempts, each a capture of its own
            for _ in range(6):
                quiet_spell()
                status, info = loop.run_until_complete(capture())
                lines = host_lines(info) if status == 200 else {}
                if status != 200 or want <= lines.get("llm-engine", set()):
                    break
                time.sleep(1.1)  # capture dirs are named by the second
        finally:
            stop.set()
            th.join()
        assert status == 200
        assert not flightrec.capturing()
        # the reply lays /debug/timeline beside the capture by hand
        assert info["perf_counter_stop"] - info["perf_counter_start"] \
            >= info["duration_s"]
        assert info["timeline_t0"] == flightrec.origin()
        # every sched: span sits on the scheduler thread's own line
        assert [ln for ln, names in lines.items()
                if any(n.startswith("sched:") for n in names)] \
            == ["llm-engine"], lines
        got = lines["llm-engine"]
        assert {"sched:dispatch", "sched:harvest", "sched:wait"} <= got, got
        assert any(n.startswith("sched:enqueue:") for n in got)
    finally:
        eng.close()
        loop.run_until_complete(tc.close())
        loop.close()


def test_stopping_a_capture_does_not_hold_the_event_loop(
        tmp_path, monkeypatch):
    """A stubbed stop_trace that takes 0.6 s: a request answered by the
    same event loop during the stop returns in a fraction of that (a
    streamed reply's chunks are written by that loop)."""
    loop, tc = _profile_app(tmp_path, monkeypatch, max_s="0.2")
    stopping = threading.Event()

    def slow_stop():
        stopping.set()
        time.sleep(0.6)

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)

    async def go():
        prof = asyncio.ensure_future(tc.request(
            "GET", "/debug/profile", params={"duration": "0.1"}))
        while not stopping.is_set():
            await asyncio.sleep(0.005)
        gaps = []
        for _ in range(5):
            t0 = time.perf_counter()
            r = await tc.request("GET", "/healthz")
            await r.read()
            gaps.append(time.perf_counter() - t0)
        still_stopping = not prof.done()
        r = await prof
        return gaps, still_stopping, r.status, await r.json()

    try:
        gaps, still_stopping, status, info = loop.run_until_complete(go())
    finally:
        loop.run_until_complete(tc.close())
        loop.close()
    assert status == 200 and still_stopping
    assert max(gaps) < 0.3, gaps
    assert json.dumps(info)  # the reply is plain JSON
    assert not flightrec.capturing()
