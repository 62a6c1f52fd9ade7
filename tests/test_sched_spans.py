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
- /debug/profile's stop does not hold the event loop.
"""

import asyncio
import glob
import json
import threading
import time

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
                "sched:emit", "sched:wait"} <= set(built)
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
