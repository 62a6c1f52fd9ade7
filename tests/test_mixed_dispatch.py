"""Stall-free mixed prefill+decode dispatch (engine._enqueue_mixed /
_mixed_fn): one fused identity-batch device step advances prompt
chunks AND decode rows, replacing the legacy prefill/decode mutual
exclusion (sleep-hold loops).

Invariants enforced here:
- an identical request schedule produces BYTE-IDENTICAL outputs with
  the fused path on vs off (seeded sampling included — the mixed step
  carries the same reset/seed/sample math as the split paths);
- under mixed load (decoders active while a burst admits) no stream
  starves or deadlocks, and every dispatch that carries prefill
  tokens while a slot decodes also advances >=1 decode row
  (decode-priority budget);
- host-interactive slots (grammar constraints, logit-bias bans) keep
  draining the pipeline correctly through mixed dispatches.
"""

import jax
import jax.numpy as jnp
import pytest

from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import init_params
from localai_tfp_tpu.telemetry.registry import REGISTRY


@pytest.fixture(scope="module")
def model():
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512)
    params = init_params(jax.random.PRNGKey(1), spec, dtype=jnp.float32)
    return spec, params, tk


def _engine(model, mixed=True, **kw):
    spec, params, tk = model
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_seq", 256)
    kw.setdefault("prefill_buckets", (8, 32, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("autostart", True)
    eng = LLMEngine(spec, params, tk, **kw)
    eng._mixed = mixed  # pre-dispatch override of LOCALAI_MIXED_DISPATCH
    # prefix reuse is timing-dependent (WHICH donor is resident when a
    # request admits varies with scheduling interleave) and orthogonal
    # to the on/off comparison this file makes — disable it so byte-
    # identity isolates the dispatch fusion itself
    eng._prefix_enabled = False
    return eng


class DispatchSpy:
    """Wraps engine._run recording, per dispatch, its kind plus the
    decode-row/prefill-token composition of mixed payloads and the
    slot states at enqueue time — the scheduling ground truth."""

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
            sample = payload["sample_sids"]
            prefill = payload["prefill_sids"]
            rec["decode_rows"] = int(sum(
                1 for i in range(S)
                if int(sample[i]) < S and int(prefill[i]) >= S))
            rec["prefill_tokens"] = int(sum(
                int(c) for sid, c in zip(prefill, payload["n_chunk"])
                if int(sid) < S))
            rec["masked"] = payload["masks"] is not None
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


def _schedule_requests(tk):
    """The fixed request set: two long-lived streams and a burst of
    three (one prompt longer than the largest bucket, so it needs a
    non-final chunk). Prompts diverge at their FIRST characters:
    shared leading tokens would legitimately engage slot-resident
    prefix reuse, whose donor choice is interleave-dependent — not
    what on/off compares."""
    return {
        "a": GenRequest(
            prompt_ids=tk.encode("stream alpha stays live"), max_tokens=40,
            temperature=0.9, top_k=12, seed=7, ignore_eos=True),
        "b": GenRequest(
            prompt_ids=tk.encode("stream beta stays live too"),
            max_tokens=40, temperature=0.7, top_p=0.9, seed=11,
            ignore_eos=True),
        "c": GenRequest(prompt_ids=tk.encode("one burst request " * 9),
                        max_tokens=6, temperature=0.8, seed=3,
                        ignore_eos=True),
        "d": GenRequest(prompt_ids=tk.encode("two burst request"),
                        max_tokens=6, ignore_eos=True),
        # longer than the largest bucket (128): needs a non-final chunk
        "e": GenRequest(prompt_ids=tk.encode("three burst request " * 10),
                        max_tokens=6, temperature=0.6, seed=5,
                        ignore_eos=True),
    }


def _mixed_schedule(eng, tk):
    """One fixed request schedule: two streams decode, then a burst of
    three admissions lands mid-stream. Returns {name: (generated token
    ids, final event)}."""
    fin = FinishSpy(eng)
    reqs = _schedule_requests(tk)
    out = {}
    qa, qb = eng.submit(reqs["a"]), eng.submit(reqs["b"])
    _first_token(qa)
    _first_token(qb)  # both rows are committed decoders
    qs = eng.submit_many([reqs[n] for n in "cde"])
    for name, q in zip("cde", qs):
        out[name] = _drain(q)
    out["a"] = _drain(qa)
    out["b"] = _drain(qb)
    return {n: (fin.generated[reqs[n].id], out[n]) for n in out}


def _assert_same_streams(got, want):
    for name in want:
        assert got[name][0] == want[name][0], f"stream {name} diverged"
        assert got[name][1].full_text == want[name][1].full_text
        assert got[name][1].finish_reason == want[name][1].finish_reason


@pytest.fixture(scope="module")
def solo_streams(model):
    """Each request of the schedule served with the engine to itself:
    {name: (generated token ids, final event)}."""
    eng = _engine(model)
    try:
        fin = FinishSpy(eng)
        out = {}
        for name, req in _schedule_requests(model[2]).items():
            ev = eng.generate(req)
            out[name] = (fin.generated[req.id], ev)
        return out
    finally:
        eng.close()


@pytest.mark.parametrize("mixed", [True, False],
                         ids=["mixed", "alternating"])
def test_interleaved_schedule_yields_each_requests_solo_tokens(
        model, solo_streams, mixed):
    """The reference BOTH admission paths are held to, whatever the
    flag that chooses between them: admissions interleaved with
    decoding are a pure scheduling matter, so every request of the
    schedule (greedy AND seeded sampling) yields exactly the tokens it
    yields when it has the engine to itself. On/off identity alone
    would pass if both paths were wrong alike; PR 35 measured the two
    on the chip and kept both, so a later change to either answers to
    this."""
    eng = _engine(model, mixed=mixed)
    try:
        spy = DispatchSpy(eng)
        got = _mixed_schedule(eng, model[2])
    finally:
        eng.close()
    assert bool(spy.mixed()) == mixed
    _assert_same_streams(got, solo_streams)


def test_mixed_on_off_byte_identical(model):
    """The headline invariant: the fused path is a pure scheduling
    change — an identical request schedule (greedy AND seeded sampling)
    yields byte-identical streams with LOCALAI_MIXED_DISPATCH on/off."""
    spec, params, tk = model
    eng_off = _engine(model, mixed=False)
    try:
        want = _mixed_schedule(eng_off, tk)
    finally:
        eng_off.close()
    eng_on = _engine(model, mixed=True)
    try:
        spy = DispatchSpy(eng_on)
        got = _mixed_schedule(eng_on, tk)
    finally:
        eng_on.close()
    assert spy.mixed(), "fused path never dispatched a mixed step"
    _assert_same_streams(got, want)


def test_mixed_load_no_starvation_decode_priority(model):
    """Decoders active while a burst admits: everything completes (no
    deadlock), every mixed dispatch carrying prefill tokens while >=1
    slot decoded also advanced >=1 decode row (decode priority), and
    prefill NEVER went out on a prefill-only dispatch while a slot was
    decoding (the mutual exclusion this PR deletes)."""
    spec, params, tk = model
    eng = _engine(model, mixed=True)
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
    assert carrying, "no mixed dispatch actually fused prefill+decode"
    for r in carrying:
        assert r["decode_rows"] >= 1, (
            "mixed dispatch carried prefill tokens but advanced no "
            f"decode row: {r}")
    for r in spy.records:
        if r["kind"] in ("prefill", "prefill_final"):
            assert r["decoding"] == 0, (
                "prefill-only dispatch while a slot was decoding — the "
                f"legacy mutual exclusion is back: {r}")
    delta = REGISTRY.delta(snap)
    assert delta.get(
        f'engine_mixed_dispatch_total{{model="{m}",'
        f'composition="mixed"}}', 0.0) >= len(carrying)
    assert delta.get(
        f'engine_decode_stall_seconds_count{{model="{m}"}}', 0.0) > 0


# slow tier: grammar + logit-bias through batched rows is tier-1 on
# the current dispatch path in test_ragged_attention
@pytest.mark.slow
def test_grammar_and_logit_bias_ride_mixed_dispatches(model):
    """Host-interactive slots (grammar constraint, logit-bias ban) keep
    draining correctly while another stream decodes: their masks ride
    the fused dispatch per-row instead of forcing the blocking path."""
    from localai_tfp_tpu.grammars.native import make_constraint

    spec, params, tk = model
    prompt = tk.encode("tool call now")
    solo = _engine(model, mixed=True)
    try:
        free = solo.generate(GenRequest(prompt_ids=prompt, max_tokens=12,
                                        ignore_eos=True))
        banned = free.full_text  # greedy continuation to ban below
    finally:
        solo.close()
    assert len(banned) >= 1

    eng = _engine(model, mixed=True)
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
    """Satellite: chunked prompts must report real (device) prefill
    time. _prefill_step only ENQUEUES, so charging its wall time to
    t_prefill_ms made long prompts report near-zero prompt processing;
    device time is now attributed at harvest of the covering flight,
    with the host enqueue cost split into its own field."""
    spec, params, tk = model
    eng = _engine(model, mixed=True)
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
    decode = _engine(model, mixed=True)
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
    """Satellite: metrics.tokens_per_second is ONE EWMA across every
    decode flavor instead of three stores stomping each other with
    instantaneous single-dispatch rates."""
    eng = _engine(model, mixed=True, autostart=False)
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
    followers replay the record like any other dispatch."""
    spec, params, tk = model
    eng = _engine(model, mixed=True)
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
        for leaf in leaves(p):
            assert not isinstance(leaf, jax.Array), (
                "device array in mixed payload — not replayable")
