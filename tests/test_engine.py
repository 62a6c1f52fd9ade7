"""Continuous-batching engine behavior (ref semantics: grpc-server.cpp
update_slots/process_token; SURVEY.md §3.2 hot path)."""

import queue
import time

import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.engine.engine import (
    GenRequest,
    LLMEngine,
    SlotState,
    _scan_stops,
)
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import (
    KVCache,
    forward,
    init_params,
)

import jax


@pytest.fixture(scope="module")
def model():
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    return spec, params, tk


def _engine(model, **kw):
    spec, params, tk = model
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_seq", 128)
    kw.setdefault("prefill_buckets", (8, 32, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    return LLMEngine(spec, params, tk, **kw)


def _reference_logits_for_prefix(spec, params, ids):
    """Full-prefill logits at the last position for a given token prefix."""
    cache = KVCache.create(spec, 1, 256, jnp.float32)
    logits, _ = forward(
        spec, params, jnp.asarray([ids], jnp.int32),
        jnp.zeros((1,), jnp.int32), cache, jnp.zeros((1,), jnp.int32),
    )
    return np.asarray(logits[0, -1])


def _collect_tokens(q):
    toks, final = [], None
    while final is None:
        ev = q.get(timeout=60)
        if ev.done:
            final = ev
        elif ev.token_id is not None:
            toks.append(ev.token_id)
    return toks, final


def test_greedy_tracks_reference_argmax(model):
    """Every engine token must be (near-)argmax of reference logits given
    the engine's own prefix. Tolerance absorbs fp32 reduction-order
    differences between bucketed/batched engine shapes and the naive
    full-prefill reference (exact numerics are covered by test_model.py)."""
    spec, params, tk = model
    eng = _engine(model)
    prompt = tk.encode("hello world")
    q = eng.submit(GenRequest(prompt_ids=prompt, max_tokens=8,
                              ignore_eos=True))
    toks, ev = _collect_tokens(q)
    eng.close()
    assert ev.finish_reason == "length"
    assert ev.completion_tokens == 8
    prefix = list(prompt)
    for tok in toks:
        ref = _reference_logits_for_prefix(spec, params, prefix)
        assert ref[tok] >= ref.max() - 1e-3, (
            f"token {tok} not near-argmax (ref top {ref.argmax()})"
        )
        prefix.append(tok)


def test_streaming_events_concat_to_full_text(model):
    eng = _engine(model)
    q = eng.submit(GenRequest(prompt_ids=eng.tokenize("abc"), max_tokens=6,
                              ignore_eos=True))
    parts, final = [], None
    while final is None:
        ev = q.get(timeout=30)
        if ev.done:
            final = ev
        elif ev.text:
            parts.append(ev.text)
    eng.close()
    assert final.finish_reason in ("length", "stop")
    assert "".join(parts) == final.full_text


def test_timings_populated(model):
    eng = _engine(model)
    ev = eng.generate(GenRequest(prompt_ids=eng.tokenize("timing test"),
                                 max_tokens=4, ignore_eos=True))
    eng.close()
    assert ev.prompt_tokens == len("timing test")
    assert ev.timing_prompt_processing_ms > 0
    assert ev.timing_token_generation_ms > 0


# slow tier: concurrency storms live in test_engine_stress (same
# tier); tier-1 keeps test_more_requests_than_slots for multi-wave
# serving
@pytest.mark.slow
def test_concurrent_requests_isolated(model):
    """Concurrent slot-batched decode must produce exactly what each request
    produces when it runs alone (slot isolation, ref: llama.cpp slots)."""
    spec, params, tk = model
    prompts = ["aaaa", "bbbb", "cccc"]
    want = []
    for p in prompts:
        eng = _engine(model)
        ev = eng.generate(GenRequest(prompt_ids=tk.encode(p), max_tokens=5,
                                     ignore_eos=True))
        want.append(ev.full_text)
        eng.close()
    eng = _engine(model)
    qs = [
        eng.submit(GenRequest(prompt_ids=tk.encode(p), max_tokens=5,
                              ignore_eos=True))
        for p in prompts
    ]
    got = []
    for q in qs:
        while True:
            ev = q.get(timeout=60)
            if ev.done:
                got.append(ev.full_text)
                break
    eng.close()
    assert got == want


def test_more_requests_than_slots(model):
    eng = _engine(model, n_slots=2)
    qs = [
        eng.submit(GenRequest(prompt_ids=eng.tokenize(f"req{i}"),
                              max_tokens=3, ignore_eos=True))
        for i in range(5)
    ]
    done = 0
    for q in qs:
        while True:
            ev = q.get(timeout=60)
            if ev.done:
                assert ev.finish_reason == "length"
                done += 1
                break
    eng.close()
    assert done == 5


def test_prompt_too_long_errors(model):
    eng = _engine(model, max_seq=16)
    ev = eng.generate(GenRequest(prompt_ids=list(range(20))))
    eng.close()
    assert ev.finish_reason == "error" and "exceeds" in ev.error


def test_context_exhaustion_finishes_with_length(model):
    eng = _engine(model, max_seq=16, prefill_buckets=(8, 16))
    ev = eng.generate(GenRequest(prompt_ids=eng.tokenize("0123456789"),
                                 max_tokens=100, ignore_eos=True))
    eng.close()
    assert ev.finish_reason == "length"
    # 10 prompt + k generated <= 16
    assert ev.completion_tokens <= 6


def test_prefix_reuse_skips_recompute(model):
    eng = _engine(model, autostart=False)
    prompt = eng.tokenize("shared prefix 123")
    q1 = eng.submit(GenRequest(prompt_ids=prompt, max_tokens=2,
                               ignore_eos=True))
    while q1.empty() or not q1.get_nowait().done:
        eng.step()
    # slot 0 now caches the prompt; a second identical request should reuse it
    eng.submit(GenRequest(prompt_ids=prompt, max_tokens=2, ignore_eos=True))
    eng._admit()
    slot = next(s for s in eng.slots if s.active)
    assert slot.n_past == len(prompt) - 1  # all but reprocessed last token
    eng.close()


def test_stop_string_truncates(model):
    spec, params, tk = model
    eng = _engine(model)
    prompt = tk.encode("stop test")
    base = eng.generate(GenRequest(prompt_ids=prompt, max_tokens=8,
                                   ignore_eos=True))
    text = base.full_text
    if len(text) < 3:
        pytest.skip("generated text too short to carve a stop string")
    stop = text[2:4]
    ev = eng.generate(GenRequest(prompt_ids=prompt, max_tokens=8,
                                 ignore_eos=True, stop=[stop]))
    eng.close()
    assert ev.finish_reason == "stop"
    assert stop not in ev.full_text
    assert ev.full_text == text[: text.find(stop)]


def test_scan_stops_partial_withholding():
    emit, hit = _scan_stops("hello wor", ["world"])
    assert not hit and emit == "hello "  # "wor" withheld
    emit, hit = _scan_stops("hello world!", ["world"])
    assert hit and emit == "hello "
    emit, hit = _scan_stops("plain", ["xyz"])
    assert not hit and emit == "plain"


def test_metrics_accumulate(model):
    eng = _engine(model)
    eng.generate(GenRequest(prompt_ids=eng.tokenize("metrics"),
                            max_tokens=4, ignore_eos=True))
    eng.close()
    assert eng.metrics.requests_completed == 1
    assert eng.metrics.tokens_generated >= 3
    assert eng.metrics.prompt_tokens_processed == len("metrics")


def test_sampled_generation_terminates(model):
    eng = _engine(model)
    ev = eng.generate(GenRequest(
        prompt_ids=eng.tokenize("sample"), max_tokens=10, temperature=0.8,
        top_k=40, top_p=0.95, seed=7, ignore_eos=True,
    ))
    eng.close()
    assert ev.finish_reason == "length"
    assert ev.completion_tokens == 10


def test_submit_many_single_wave(model):
    eng = _engine(model)
    eng.start()
    try:
        good = [GenRequest(prompt_ids=[2, 5, 9], max_tokens=4,
                           ignore_eos=True) for _ in range(3)]
        bad = [GenRequest(prompt_ids=[], max_tokens=4),
               GenRequest(prompt_ids=list(range(500)), max_tokens=4)]
        qs = eng.submit_many(good + bad)
        assert len(qs) == 5
        outs = []
        for q in qs:
            while True:
                ev = q.get(timeout=60)
                if ev.done:
                    outs.append(ev)
                    break
        assert all(o.finish_reason == "length" for o in outs[:3])
        assert all(o.finish_reason == "error" for o in outs[3:])
        # identical prompts in one wave must produce identical greedy text
        assert outs[0].full_text == outs[1].full_text == outs[2].full_text
    finally:
        eng.close()


def test_kernel_engine_matches_xla_engine(monkeypatch):
    """The fused Pallas decode path (forced interpret on CPU) must
    reproduce the XLA path's greedy output exactly (same model, same
    prompts, kernel-eligible shapes: kv_dim % 128 == 0, max_seq % 256)."""
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, n_kv_heads=2, d_head=64,
                     n_heads=4, max_position=256)
    assert spec.kv_dim % 128 == 0
    params = init_params(jax.random.PRNGKey(3), spec, dtype=jnp.float32)

    def run(env):
        monkeypatch.setenv("LOCALAI_DECODE_KERNEL", env)
        eng = LLMEngine(spec, params, tk, n_slots=2, max_seq=256,
                        prefill_buckets=(8, 32), cache_dtype=jnp.float32,
                        autostart=False)
        used = eng._use_kernel
        eng.start()
        try:
            evs = []
            qs = eng.submit_many([
                GenRequest(prompt_ids=tk.encode(p, add_bos=True),
                           max_tokens=8, temperature=0.0, ignore_eos=True)
                for p in ("hello", "the quick brown fox")
            ])
            for q in qs:
                while True:
                    ev = q.get(timeout=120)
                    if ev.done:
                        evs.append(ev)
                        break
            return used, [e.full_text for e in evs]
        finally:
            eng.close()

    used_k, kernel_out = run("1")
    used_x, xla_out = run("0")
    assert used_k and not used_x  # both paths actually exercised
    assert kernel_out == xla_out
    assert all(len(t) > 0 for t in kernel_out)


def test_warmup_variant_count_drops_with_ragged(model, monkeypatch):
    """Full-width page tables collapse the warmup-precompiled jit
    variant set: the dense cache compiles a bucket x window ladder
    (pruned of never-dispatchable rungs, but still a ladder), the pool
    exactly one variant per token-budget shape. The count is also
    exported as engine_dispatch_compile_variants_count. The dispatch
    layer is stubbed: the assertion is about the variant PLAN (which
    shapes warmup would compile), and every planned dispatch kind is
    compiled-and-exercised by the rest of the suite — paying ~25 real
    jit compiles here would test nothing more."""
    from localai_tfp_tpu.telemetry import metrics as tm

    spec, params, tk = model

    def warm(paged):
        # max_seq ABOVE the 256 window floor so the dense cache has a
        # real bucket x window ladder to collapse; the 512 bucket makes
        # the dead-rung prune observable (an identity bucket-512 final
        # can only ever dispatch at window 1024)
        monkeypatch.setenv("LOCALAI_PAGED_KV", "on" if paged else "off")
        eng = LLMEngine(spec, params, tk, n_slots=2, max_seq=1024,
                        prefill_buckets=(8, 512), decode_steps=4,
                        cache_dtype=jnp.float32, autostart=False)
        assert eng._paged == paged
        planned = []

        def record(kind, payload):
            rec = {"kind": kind}
            if isinstance(payload, dict):
                rec["window"] = payload.get("window")
                toks = payload.get("toks")
                if toks is not None:
                    rec["rows"], rec["bucket"] = toks.shape
            planned.append(rec)

        eng._run = record
        try:
            eng.warmup()
            n = eng.warmup_variants
            # warmup-populated gauge (point-in-time; overwritten by the
            # next engine warming under the same model label, so it is
            # read here, between runs)
            gauge = tm.ENGINE_DISPATCH_VARIANTS.labels(
                model=eng._mlabel).value
        finally:
            eng.close()
        return n, gauge, planned

    n_on, g_on, plan_on = warm(True)
    n_off, g_off, plan_off = warm(False)
    assert 0 < n_on < n_off, (n_on, n_off)
    assert g_on == n_on and g_off == n_off
    assert n_on == len(plan_on) and n_off == len(plan_off)
    # the pool: every windowed dispatch is planned at FULL width — one
    # variant per token-budget shape
    assert all(r["window"] in (None, 1024) for r in plan_on), plan_on
    # the dense ladder's dead-rung prune: a step picks bucket 512 only
    # for a chunk over 8 tokens, so its window covers at least 8 + 2
    # positions — and a bucket-8 step's at least 2: both ladders stay
    # fully warmed here, each rung once per row count
    b512 = [r for r in plan_off if r["kind"] == "mixed"
            and r.get("bucket") == 512]
    assert {r["window"] for r in b512} == {256, 512, 1024}, b512
    assert {r["rows"] for r in b512} == {1, 2}, b512
    # under a rung's worth of tokens the small row counts merge into
    # the slot count
    b8 = [r for r in plan_off if r["kind"] == "mixed"
          and r.get("bucket") == 8]
    assert {r["window"] for r in b8} == {256, 512, 1024}, b8
    assert {r["rows"] for r in b8} == {2}, b8
    assert ({r["kind"] for r in plan_on}
            == {r["kind"] for r in plan_off})


def test_mirostat_and_typical_flow_through_engine(model):
    """PredictOptions-surface mirostat/typical_p fields must actually
    change engine output (VERDICT r3 missing #1): same seed, same
    prompt, mirostat v2 with tight tau vs plain sampling."""
    spec, params, tk = model
    eng = _engine(model)
    prompt = tk.encode("sampling modes")

    def gen(**kw):
        ev = eng.generate(GenRequest(
            prompt_ids=prompt, max_tokens=12, temperature=1.4, seed=7,
            ignore_eos=True, **kw))
        assert ev.finish_reason == "length", ev.error
        return ev.full_text

    base = gen()
    base2 = gen()
    assert base == base2  # seeded determinism baseline
    miro = gen(mirostat=2, mirostat_tau=0.05, mirostat_eta=0.1)
    typ = gen(typical_p=0.05)
    eng.close()
    # a near-zero surprise target / typical mass truncates the sampled
    # distribution hard; with temp 1.4 over a byte vocab the plain draw
    # virtually surely differs
    assert miro != base or typ != base


def test_latency_k_policy(model):
    """_latency_k: balanced mode picks the smallest warmed k covering
    the dispatch RTT; latency mode (latency_target_ms) picks the
    largest warmed k under the budget — the open-capacity half of the
    BASELINE steady-TTFT knob."""
    eng = _engine(model, decode_steps=16, autostart=False)
    try:
        # no samples yet: never throttle
        assert eng._latency_k() == 16
        eng._step_ms = 32.0  # 8B-class step
        assert eng._latency_k() == 4  # 4*32 >= 90 (balanced)
        eng._step_ms = 9.0  # 1B-class step
        assert eng._latency_k() == 16  # 8*9=72 < 90 -> next rung
        eng.latency_target_ms = 70.0
        eng._step_ms = 32.0
        assert eng._latency_k(True) == 2  # 2*32=64 <= 70 < 4*32
        assert eng._latency_k(False) == 4  # drain tail: balanced rule
        eng._step_ms = 9.0
        assert eng._latency_k(True) == 4  # 4*9=36 <= 70 < 8*9=72
        eng._step_ms = 200.0  # giant steps: floor at the smallest k>1
        assert eng._latency_k(True) == 2
    finally:
        eng.close()


def test_latency_mode_serves_and_bounds_scans(model):
    """Latency mode end-to-end: once the 1 s arrival window ages out on
    a long-running stream with a free slot, decode scans go depth-1
    (never enqueued behind another decodek) and k fits the budget —
    the open-capacity state BASELINE's steady-TTFT target measures."""
    spec, params, tk = model
    prompt = tk.encode("hello")

    def run(**kw):
        eng = _engine(model, decode_steps=8, n_slots=2,
                      max_seq=256, **kw)
        # seed the step EWMA as a warmed engine would have it: 20 ms
        # steps make the 50 ms budget resolve to k=2 (2*20 <= 50 < 4*20)
        eng._step_ms = 20.0
        events: list = []  # (k, n_decodek_already_in_flight, t)
        orig = eng._run

        def spy(kind, payload):
            if kind == "decodek":
                events.append((
                    payload["k"],
                    sum(1 for f in eng._flights if f.kind == "decodek"),
                    time.perf_counter(),
                    # real harvests keep updating the EWMA during the
                    # run, so capture the budget k the engine believed
                    # in AT DISPATCH TIME for the assertion below
                    eng._latency_k(True)))
            return orig(kind, payload)

        eng._run = spy
        try:
            t_submit = time.perf_counter()
            q = eng.submit(GenRequest(prompt_ids=prompt, max_tokens=220,
                                      ignore_eos=True))
            while True:
                ev = q.get(timeout=300)
                assert not ev.error, ev.error
                if ev.done:
                    return ev.completion_tokens, events, t_submit
        finally:
            eng._run = orig
            eng.close()

    base_n, _, _ = run()
    lat_n, events, t_submit = run(latency_target_ms=50.0)
    assert lat_n == base_n == 220  # both runs complete the full budget
    # scans dispatched after the arrival window aged out, while the
    # stream still had > decode_steps tokens to go (not the drain tail):
    # generating 220 tokens at k<=8 keeps the engine busy well past
    # t_submit + 1 s unless CPU steps are sub-5ms — skip then, the
    # policy window never opened
    window = [e for e in events if e[2] - t_submit > 1.05][:-3]
    if not window:
        pytest.skip("model generated 220 tokens in under ~1 s on this "
                    "host; the open-capacity window never opened")
    assert all(k == want for k, _, _, want in window), window  # budget
    assert all(d == 0 for _, d, _, _ in window), window  # depth-1
