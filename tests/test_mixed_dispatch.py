"""The one admission step (engine._enqueue_mixed / _mixed_fn): ONE
device dispatch carries a wave's prompt rows [R, bucket] and one token
for every decoding row [n_slots, 1], whether or not a row decodes.

Invariants enforced here:
- the oracle: admissions interleaved with decoding are a pure
  scheduling matter, so every request of a schedule (greedy AND seeded
  sampling) yields exactly the tokens it yields with the engine to
  itself — for every composition the one program serves, on the three
  cache routes, f32 and int8 KV;
- under mixed load no stream starves or deadlocks, and every step that
  carries prompt tokens while a slot decodes also advances every
  decoding row (decode rows ride every step);
- host-interactive slots (grammar constraints, logit-bias bans) keep
  draining the pipeline correctly through mixed steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine, SlotState
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import init_params
from localai_tfp_tpu.telemetry.registry import REGISTRY


@pytest.fixture(scope="module")
def model():
    tk = ByteTokenizer()
    # kernel-eligible shapes (kv_dim % 128 == 0) so forcing the kernel
    # is the only thing between the gather route and the ragged one
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512,
                     n_heads=4, n_kv_heads=2, d_head=64)
    params = init_params(jax.random.PRNGKey(1), spec, dtype=jnp.float32)
    return spec, params, tk


def _engine(model, **kw):
    spec, params, tk = model
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_seq", 256)
    kw.setdefault("prefill_buckets", (8, 32, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("autostart", True)
    eng = LLMEngine(spec, params, tk, **kw)
    # prefix reuse is timing-dependent (WHICH donor is resident when a
    # request admits varies with scheduling interleave) and orthogonal
    # to what this file compares — disable it so token identity
    # isolates the admission step itself
    eng._prefix_enabled = False
    return eng


class DispatchSpy:
    """Wraps engine._run recording, per dispatch, its kind plus the
    decode-row/prompt-row composition of mixed payloads and the slot
    states at enqueue time — the scheduling ground truth."""

    def __init__(self, eng):
        self.eng = eng
        self.records = []
        self._orig = eng._run
        eng._run = self._run

    def _run(self, kind, payload):
        S = self.eng.n_slots
        rec = {"kind": kind,
               "decoding": sum(1 for s in self.eng.slots
                               if s.state.name == "DECODE")}
        if kind == "mixed":
            member = payload["slot_ids"] < S
            rec["shape"] = payload["toks"].shape
            rec["decode_rows"] = int(payload["active"].sum())
            rec["prompt_rows"] = int(member.sum())
            rec["chunks"] = int((member & ~payload["final"]).sum())
            rec["prefill_tokens"] = int(payload["n_chunk"][member].sum())
            rec["masked"] = payload["masks"] is not None
            rec["carry"] = payload["carry"]
        self.records.append(rec)
        return self._orig(kind, payload)

    def mixed(self):
        return [r for r in self.records if r["kind"] == "mixed"]


class FinishSpy:
    """Captures each request's EXACT generated token sequence at
    _finish time — stream events coalesce text spans per harvest, so
    their token_ids are not a per-token record."""

    def __init__(self, eng):
        self.generated = {}  # request id -> [token ids]
        self._orig = eng._finish
        eng._finish = self._finish

    def _finish(self, slot, reason):
        if slot.request is not None:
            self.generated[slot.request.id] = list(slot.generated)
        return self._orig(slot, reason)


def _drain(q, timeout=120):
    while True:
        ev = q.get(timeout=timeout)
        if ev.done:
            return ev


def _first_token(q, timeout=120):
    while True:
        ev = q.get(timeout=timeout)
        assert not ev.done, f"finished early: {ev.finish_reason} {ev.error}"
        if ev.token_id is not None:
            return ev


# ------------------------------------------------------------ the oracle

# Prompts diverge at their FIRST characters: shared leading tokens
# would legitimately engage slot-resident prefix reuse, whose donor
# choice is interleave-dependent — not what the oracle compares.
_REQUESTS = {
    # long-lived streams
    "d1": dict(prompt="alpha stream stays live", max_tokens=40,
               temperature=0.9, top_k=12, seed=7),
    "d2": dict(prompt="beta stream stays live too", max_tokens=40,
               temperature=0.7, top_p=0.9, seed=11),
    "d3": dict(prompt="gamma stream, the greedy one", max_tokens=40),
    # admissions: bucket 32, bucket 128, longer than the largest
    # bucket (a non-final chunk, then a final), bucket 32 again
    "p1": dict(prompt="one burst request", max_tokens=6),
    "p2": dict(prompt="two burst request " * 5, max_tokens=6,
               temperature=0.8, seed=3),
    "p3": dict(prompt="three burst request " * 10, max_tokens=6,
               temperature=0.6, seed=5),
    "p4": dict(prompt="four, the last of the burst", max_tokens=6),
}

# composition -> (rows decoding when the wave lands, the wave, the
# group-token budget or None for the default)
_COMPOSITIONS = {
    "no_row_decodes": ((), ("p1", "p2", "p4"), None),
    "one_prompt_beside_the_rest": (("d1", "d2", "d3"), ("p1",), None),
    "the_rest_beside_one": (("d1",), ("p1", "p2", "p4"), None),
    "two_buckets_one_wave": (("d1", "d2"), ("p1", "p2"), None),
    "chunks_then_a_final": (("d1",), ("p3", "p1"), None),
    "wave_over_the_token_cap": (("d1",), ("p1", "p2", "p4"), 64),
}

_ROUTES = {
    "ragged": {"LOCALAI_DECODE_KERNEL": "1"},
    "gather": {},
    "dense": {"LOCALAI_PAGED_KV": "off"},
}


def _request(tk, name):
    kw = dict(_REQUESTS[name])
    return GenRequest(prompt_ids=tk.encode(kw.pop("prompt")),
                      ignore_eos=True, **kw)


def _pump(eng, until):
    """Drive the scheduler from this thread until ``until()``."""
    for _ in range(200000):
        if until():
            return
        eng.step()
    raise AssertionError("engine stalled")


def _forget(eng):
    """Drop every slot's resident prefix, so a request served a second
    time on this engine is prefilled again in full."""
    assert not eng._has_work()
    for s in eng.slots:
        s.cache_tokens = []
        s.n_past = 0
        if eng._paged:
            eng._pool.drop(s.idx)


def _serve(eng, tk, decoders, wave):
    """Start ``decoders``, let each emit its first token, land ``wave``
    as one submit, run everything to its end. Returns {name: tokens}."""
    fin = FinishSpy(eng)
    try:
        reqs = {n: _request(tk, n) for n in (*decoders, *wave)}
        if decoders:
            eng.submit_many([reqs[n] for n in decoders])
            _pump(eng, lambda: sum(
                s.state is SlotState.DECODE for s in eng.slots)
                == len(decoders))
        eng.submit_many([reqs[n] for n in wave])
        _pump(eng, lambda: not eng._has_work())
    finally:
        eng._finish = fin._orig
    return {n: fin.generated[r.id] for n, r in reqs.items()}


@pytest.fixture(scope="module", params=[
    (r, d) for r in _ROUTES for d in ("f32", "int8")],
    ids=lambda p: f"{p[0]}-{p[1]}")
def routed(request, model):
    """One engine per (route, KV dtype), driven by the test's own
    thread, with each request's solo tokens: (engine, solo)."""
    route, dtype = request.param
    mp = pytest.MonkeyPatch()
    for k, v in _ROUTES[route].items():
        mp.setenv(k, v)
    try:
        eng = _engine(model, autostart=False, cache_dtype=(
            jnp.float32 if dtype == "f32" else jnp.int8))
    finally:
        mp.undo()
    assert eng.attention_path == {
        "ragged": "ragged_paged_kernel", "gather": "paged_xla_gather",
        "dense": "dense_xla"}[route]
    solo = {}
    for name in _REQUESTS:
        _forget(eng)
        solo.update(_serve(eng, model[2], (), (name,)))
    yield eng, solo
    eng.close()


@pytest.mark.parametrize("composition", list(_COMPOSITIONS))
def test_interleaved_schedule_yields_each_requests_solo_tokens(
        model, routed, composition):
    """The reference the one admission path is held to: every request
    of the schedule yields exactly the tokens it yields when it has the
    engine to itself, whatever rode the step beside it."""
    eng, solo = routed
    decoders, wave, cap = _COMPOSITIONS[composition]
    _forget(eng)
    spy = DispatchSpy(eng)
    budget = eng._prefill_group_tokens
    if cap is not None:
        eng._prefill_group_tokens = cap
    try:
        got = _serve(eng, model[2], decoders, wave)
    finally:
        eng._run = spy._orig
        eng._prefill_group_tokens = budget
    for name, toks in got.items():
        assert toks == solo[name], f"stream {name} diverged"
    # the composition really ran: ONE kind admits, beside the rows
    # that decode, at the shape the wave asks for
    kinds = {r["kind"] for r in spy.records}
    assert kinds <= {"mixed", "decodek"}, kinds
    landed = [r for r in spy.mixed() if r["prompt_rows"]
              and r["decode_rows"] >= len(decoders)]
    assert landed and landed[0]["decode_rows"] == len(decoders), landed
    if composition == "no_row_decodes":
        assert landed[0]["prompt_rows"] == 3  # one dispatch, the wave
        assert landed[0]["shape"] == (4, 128)
    elif composition == "one_prompt_beside_the_rest":
        assert landed[0]["shape"] == (4, 32)  # 17 tokens: under a rung
    elif composition == "two_buckets_one_wave":
        assert landed[0]["prompt_rows"] == 2  # no split by bucket
    elif composition == "chunks_then_a_final":
        assert any(r["chunks"] for r in landed)
    elif composition == "wave_over_the_token_cap":
        assert len(landed) >= 3 and all(
            r["shape"][0] * r["shape"][1] <= 128 for r in landed)
    if decoders:
        # new rows join the carry: the step rode behind the scans in
        # flight, and scans rode behind it
        assert any(r["carry"] for r in landed)


# ------------------------------------------------- scheduling under load

def _mixed_schedule(eng, tk):
    """Two streams decode, then a burst of three admissions lands
    mid-stream (threaded engine). Returns {name: (tokens, final event)}."""
    fin = FinishSpy(eng)
    reqs = {n: _request(tk, n) for n in ("d1", "d2", "p2", "p1", "p3")}
    out = {}
    qa, qb = eng.submit(reqs["d1"]), eng.submit(reqs["d2"])
    _first_token(qa)
    _first_token(qb)  # both rows are committed decoders
    burst = ("p2", "p1", "p3")
    qs = eng.submit_many([reqs[n] for n in burst])
    for name, q in zip(burst, qs):
        out[name] = _drain(q)
    out["d1"] = _drain(qa)
    out["d2"] = _drain(qb)
    return {n: (fin.generated[reqs[n].id], out[n]) for n in out}


def test_mixed_load_no_starvation_decode_priority(model):
    """Decoders active while a burst admits: everything completes (no
    deadlock), every mixed step carrying prompt tokens while >=1 slot
    decoded also advanced >=1 decode row (decode rows ride every
    step), and no prompt went out on any other kind."""
    spec, params, tk = model
    eng = _engine(model)
    snap = REGISTRY.snapshot()
    try:
        spy = DispatchSpy(eng)
        results = _mixed_schedule(eng, tk)
        m = eng._mlabel
    finally:
        eng.close()
    for name, (gen, ev) in results.items():
        assert ev.finish_reason == "length", (name, ev.error)
        assert len(gen) == ev.completion_tokens > 0
    carrying = [r for r in spy.mixed()
                if r["prefill_tokens"] and r["decoding"]]
    assert carrying, "no mixed step actually carried prompts and decode"
    for r in carrying:
        assert r["decode_rows"] >= 1, (
            "mixed step carried prompt tokens but advanced no decode "
            f"row: {r}")
    assert {r["kind"] for r in spy.records} <= {"mixed", "decodek"}
    delta = REGISTRY.delta(snap)
    assert delta.get(
        f'engine_mixed_dispatch_total{{model="{m}",'
        f'composition="mixed"}}', 0.0) >= len(carrying)
    assert delta.get(
        f'engine_decode_stall_seconds_count{{model="{m}"}}', 0.0) > 0
    # the gauge's parts at the dispatch site: a decode row is one real
    # token, the shape is both groups'
    real = delta[f'engine_dispatch_tokens_total{{model="{m}",'
                 f'kind="mixed",part="real"}}']
    padded = delta[f'engine_dispatch_tokens_total{{model="{m}",'
                   f'kind="mixed",part="padded"}}']
    assert real == sum(r["decode_rows"] + r["prefill_tokens"]
                       for r in spy.mixed())
    assert padded == sum(eng.n_slots + r["shape"][0] * r["shape"][1]
                         for r in spy.mixed())


def test_a_lone_request_is_not_held(model):
    """The alternating scheduler's burst hold parked a LONE request
    0.15 s before its first scan (CHANGES, PR 35); with one admission
    path nothing sleeps: first token and every scan follow at once."""
    import time

    spec, params, tk = model
    eng = _engine(model)
    try:
        for warm in ("one burst request", "zulu warms the same shapes"):
            # compile the shapes it rides (the first sampling dispatch
            # of a process loads a variant of its own)
            eng.generate(GenRequest(prompt_ids=tk.encode(warm),
                                    max_tokens=6, ignore_eos=True))
        t0 = time.perf_counter()
        ev = eng.generate(_request(tk, "p4"))
        dt = time.perf_counter() - t0
    finally:
        eng.close()
    assert ev.finish_reason == "length"
    assert dt < 0.15, f"a lone request took {dt:.3f} s"


# slow tier: grammar + logit-bias through batched rows is tier-1 on
# the current dispatch path in test_ragged_attention
@pytest.mark.slow
def test_grammar_and_logit_bias_ride_mixed_dispatches(model):
    """Host-interactive slots (grammar constraint, logit-bias ban) keep
    draining correctly while another stream decodes: their masks ride
    the mixed step per-row."""
    from localai_tfp_tpu.grammars.native import make_constraint

    spec, params, tk = model
    prompt = tk.encode("tool call now")
    solo = _engine(model)
    try:
        free = solo.generate(GenRequest(prompt_ids=prompt, max_tokens=12,
                                        ignore_eos=True))
        banned = free.full_text  # greedy continuation to ban below
    finally:
        solo.close()
    assert len(banned) >= 1

    eng = _engine(model)
    try:
        spy = DispatchSpy(eng)
        fin = FinishSpy(eng)
        qa = eng.submit(GenRequest(
            prompt_ids=tk.encode("background stream"), max_tokens=48,
            ignore_eos=True))
        _first_token(qa)
        # grammar-constrained: output must be exactly "ok" then EOS
        constraint = make_constraint('root ::= "ok"', tk)
        qg = eng.submit(GenRequest(prompt_ids=prompt, max_tokens=16,
                                   constraint=constraint))
        # logit-bias: ban the greedy first token; the stream must take
        # a different (still valid) continuation and never emit it
        ban_id = tk.encode(banned, add_bos=False)[0]
        rban = GenRequest(prompt_ids=prompt, max_tokens=8,
                          logit_bias={ban_id: -100.0}, ignore_eos=True)
        qb = eng.submit(rban)
        ev_g = _drain(qg)
        ev_b = _drain(qb)
        ev_a = _drain(qa)
    finally:
        eng.close()
    assert ev_g.full_text == "ok" and ev_g.finish_reason == "stop"
    gen_b = fin.generated[rban.id]
    assert ban_id not in gen_b and len(gen_b) == 8
    assert ev_a.finish_reason == "length"
    assert any(r.get("masked") for r in spy.mixed()), (
        "constrained slots never shipped a mask through a mixed "
        "dispatch")


def test_chunked_prompt_prefill_timing_attribution(model):
    """Chunked prompts must report real (device) prefill time: a
    non-final chunk only ENQUEUES, so device time is attributed at
    harvest of the covering final's flight, with the host enqueue cost
    split into its own field."""
    spec, params, tk = model
    eng = _engine(model)
    try:
        # > largest bucket (128) so the prompt takes the chunked path
        prompt = tk.encode("a long prompt that must chunk " * 8)
        assert len(prompt) > 128
        ev = eng.generate(GenRequest(prompt_ids=prompt, max_tokens=4,
                                     ignore_eos=True))
    finally:
        eng.close()
    assert ev.finish_reason == "length", ev.error
    # device prefill spans first-chunk enqueue -> covering harvest; on
    # any real backend this is orders of magnitude above the ~us-scale
    # enqueue cost the old attribution reported
    assert ev.timing_prompt_processing_ms > 1.0
    assert ev.timing_prefill_enqueue_ms >= 0.0
    assert ev.timing_prompt_processing_ms >= ev.timing_prefill_enqueue_ms


def test_chunked_prompt_prefill_timing_attribution_disagg(model):
    """Disaggregated extension of the attribution test above: when the
    chunked prompt runs on the PREFILL engine and the stream decodes on
    the other, timing_prompt_processing_ms must carry the prefill
    engine's device time PLUS the migration wall — not the decode
    engine's (zero) prompt work."""
    import os

    from localai_tfp_tpu.engine.kv_migrate import (DisaggRouter,
                                                   build_prefill_engine)
    spec, params, tk = model
    saved = os.environ.get("LOCALAI_DISAGG_MIN_PROMPT")
    os.environ["LOCALAI_DISAGG_MIN_PROMPT"] = "64"
    decode = _engine(model)
    prefill = build_prefill_engine(spec, params, tk, decode=decode,
                                   cache_dtype=jnp.float32)
    router = DisaggRouter(prefill, decode)
    router.start()
    try:
        prompt = tk.encode("a long prompt that must chunk " * 8)
        assert len(prompt) > 128
        mig0 = decode._migrator.counters["adoptions"]
        ev = router.generate(GenRequest(prompt_ids=prompt, max_tokens=4,
                                        ignore_eos=True))
        assert ev.finish_reason == "length", ev.error
        # the request really took the relay (not a fallback)
        assert decode._migrator.counters["adoptions"] == mig0 + 1
        assert ev.timing_prompt_processing_ms > 1.0
        assert ev.timing_prefill_enqueue_ms >= 0.0
        assert ev.timing_prompt_processing_ms >= \
            ev.timing_prefill_enqueue_ms
        # TTFT spans the whole relay: it can never undercut the prompt
        # processing it contains
        assert ev.timing_first_token_ms >= \
            ev.timing_prompt_processing_ms
    finally:
        if saved is None:
            os.environ.pop("LOCALAI_DISAGG_MIN_PROMPT", None)
        else:
            os.environ["LOCALAI_DISAGG_MIN_PROMPT"] = saved
        router.close()


def test_tokens_per_second_ewma_single_path(model):
    """metrics.tokens_per_second is ONE EWMA across every decode flavor
    instead of three stores stomping each other with instantaneous
    single-dispatch rates."""
    eng = _engine(model, autostart=False)
    try:
        assert eng.metrics.tokens_per_second == 0.0
        eng._note_tokens_per_second(10, 1.0)
        assert eng.metrics.tokens_per_second == pytest.approx(10.0)
        eng._note_tokens_per_second(30, 1.0)  # blended, not stomped
        assert eng.metrics.tokens_per_second == pytest.approx(
            0.7 * 10.0 + 0.3 * 30.0)
        before = eng.metrics.tokens_per_second
        eng._note_tokens_per_second(0, 1.0)  # degenerate: ignored
        eng._note_tokens_per_second(5, 0.0)
        assert eng.metrics.tokens_per_second == before
    finally:
        eng.close()


def test_mixed_dispatch_payload_is_scalar_only(model):
    """Multihost invariant: the mixed payload must contain only scalar
    host data (numpy arrays / python scalars), never device arrays —
    followers replay the record like any other dispatch — and only the
    fields the replay codec knows."""
    from localai_tfp_tpu.parallel.multihost import PAYLOAD_FIELDS

    spec, params, tk = model
    eng = _engine(model)
    try:
        captured = []
        orig = eng._run

        def run(kind, payload):
            if kind == "mixed":
                captured.append(payload)
            return orig(kind, payload)

        eng._run = run
        qa = eng.submit(GenRequest(prompt_ids=tk.encode("host a"),
                                   max_tokens=24, ignore_eos=True))
        _first_token(qa)
        qb = eng.submit(GenRequest(prompt_ids=tk.encode("host b"),
                                   max_tokens=4, ignore_eos=True))
        _drain(qb)
        _drain(qa)
    finally:
        eng.close()
    assert captured

    def leaves(x):
        if isinstance(x, dict):
            for v in x.values():
                yield from leaves(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                yield from leaves(v)
        else:
            yield x
    for p in captured:
        assert set(p) <= set(PAYLOAD_FIELDS["mixed"])
        for leaf in leaves(p):
            assert not isinstance(leaf, jax.Array), (
                "device array in mixed payload — not replayable")
            assert leaf is None or isinstance(
                leaf, (np.ndarray, np.generic, int, float, bool, str))
