"""Cross-slot prefix KV cache: radix index (engine/prefix_index.py) +
on-device row-to-row KV copies (engine.py kvcopy dispatch).

An admitted request must be able to start from the best matching prefix
held by ANY slot — free or active — with byte-identical outputs to a
cache-off run, exactly one prefix prefill per same-prefix admission
wave, and no mutation of an active donor's row."""

import queue as _q

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
from localai_tfp_tpu.engine.prefix_index import (
    PrefixIndex,
    common_prefix_len,
)
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import init_params
from localai_tfp_tpu.telemetry.registry import REGISTRY


@pytest.fixture(scope="module")
def model():
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    return spec, params, tk


def _engine(model, **kw):
    spec, params, tk = model
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_seq", 256)
    kw.setdefault("prefill_buckets", (8, 32, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("autostart", True)
    return LLMEngine(spec, params, tk, **kw)


class RunSpy:
    """Wraps engine._run, counting REAL prefill tokens dispatched (pad
    rows excluded) and recording kvcopy payloads — the ground truth the
    telemetry counters are cross-checked against."""

    def __init__(self, eng):
        self.eng = eng
        self.prefill_tokens = 0
        self.copies = []
        self._orig = eng._run
        eng._run = self._run

    def _run(self, kind, payload):
        if kind == "mixed":
            # the prompt group's member rows carry the real prompt
            # chunk tokens (pad rows hold the slot-id sentinel)
            self.prefill_tokens += int(sum(
                int(c) for sid, c in zip(payload["slot_ids"],
                                         payload["n_chunk"])
                if int(sid) < self.eng.n_slots))
        elif kind == "kvcopy":
            self.copies.append(dict(payload))
        return self._orig(kind, payload)


def _drain(q, timeout=120):
    toks = []
    while True:
        ev = q.get(timeout=timeout)
        if ev.done:
            return toks, ev
        if ev.token_id is not None:
            toks.append(ev.token_id)


def _first_token(q, timeout=120):
    """Block until the request's first token event, return it."""
    while True:
        ev = q.get(timeout=timeout)
        assert not ev.done, f"finished early: {ev.finish_reason} {ev.error}"
        if ev.token_id is not None:
            return ev


# ------------------------------------------------------------- unit level


def test_common_prefix_len_matches_scalar_loop():
    rng = np.random.default_rng(0)
    for _ in range(50):
        n = int(rng.integers(0, 40))
        a = rng.integers(0, 5, n).tolist()
        b = rng.integers(0, 5, int(rng.integers(0, 40))).tolist()
        want = 0
        for x, y in zip(a, b):
            if x != y:
                break
            want += 1
        assert common_prefix_len(a, b) == want


def test_prefix_index_match_insert_remove():
    idx = PrefixIndex()
    idx.set_tokens(0, [1, 2, 3, 4, 5, 6])
    idx.set_tokens(1, [1, 2, 3, 9, 9])
    assert idx.match([1, 2, 3, 4, 5, 6, 7]) == (6, {0})
    assert idx.match([1, 2, 3, 9]) == (4, {1})
    n, slots = idx.match([1, 2, 3])
    assert n == 3 and slots == {0, 1}
    assert idx.match([5]) == (0, set())
    # exclusion: the destination slot must not donate to itself
    assert idx.match([1, 2, 3, 4], exclude=frozenset({0}))[0] == 3
    # extension keeps membership; truncating replace drops it
    idx.set_tokens(0, [1, 2, 3, 4, 5, 6, 7, 8])
    assert idx.match([1, 2, 3, 4, 5, 6, 7, 8])[0] == 8
    idx.set_tokens(0, [1, 2])
    assert idx.match([1, 2, 3, 4])[0] == 3  # slot 1 still covers 1,2,3
    idx.remove(1)
    assert idx.match([1, 2, 3, 4]) == (2, {0})
    assert idx.resident_tokens() == 2
    # sync removes unlisted slots and extends listed ones
    idx.sync([(0, [1, 2, 9, 9])])
    assert idx.match([1, 2, 9, 9, 1])[0] == 4


def test_prefix_index_value_prefers_long_recent():
    idx = PrefixIndex()
    idx.set_tokens(0, list(range(100)), now=1000.0)
    idx.set_tokens(1, list(range(4)), now=1000.0)
    assert idx.value(0, now=1000.0) > idx.value(1, now=1000.0)
    assert idx.value(2, now=1000.0) == 0.0  # unregistered: free-est


# ----------------------------------------------------------- engine level


def test_cross_slot_copy_from_active_donor_byte_identical(model):
    """(a)+(c): a request admitted to slot j reuses the >=k-token prefix
    resident in ACTIVE slot i via an on-device copy; its prefill shrinks
    to the tail, its output is byte-identical to a cache-off run, and
    the donor's own generation is untouched."""
    spec, params, tk = model
    prefix = tk.encode("shared system prompt: you are helpful. " * 3)
    tail_a = tk.encode("user alpha", add_bos=False)
    tail_b = tk.encode("user beta?", add_bos=False)
    assert len(prefix) >= 64

    solo = {}
    for name, ids, mt in (("a", prefix + tail_a, 48),
                          ("b", prefix + tail_b, 8)):
        off = _engine(model)
        off._prefix_enabled = False
        ev = off.generate(GenRequest(prompt_ids=ids, max_tokens=mt,
                                     ignore_eos=True))
        off.close()
        assert ev.finish_reason == "length", ev.error
        solo[name] = ev.full_text

    eng = _engine(model)
    spy = RunSpy(eng)
    try:
        qa = eng.submit(GenRequest(prompt_ids=prefix + tail_a,
                                   max_tokens=48, ignore_eos=True))
        _first_token(qa)  # donor's prompt KV is committed, still DECODE
        tok0 = spy.prefill_tokens
        qb = eng.submit(GenRequest(prompt_ids=prefix + tail_b,
                                   max_tokens=8, ignore_eos=True))
        toks_b, ev_b = _drain(qb)
        toks_a, ev_a = _drain(qa)
    finally:
        eng.close()
    assert spy.copies, "no cross-slot kvcopy was dispatched"
    assert spy.copies[0]["src"] != spy.copies[0]["dst"]
    # prefill for b covered only its divergent tail, not the prefix
    assert spy.prefill_tokens - tok0 <= len(tail_b) + 1
    assert ev_b.full_text == solo["b"]  # byte-identical to cache-off
    assert ev_a.full_text == solo["a"]  # donor row never mutated
    assert eng.metrics.prefix_copies >= 1
    assert eng.metrics.prefix_reused_tokens >= len(prefix)


def test_wave_of_same_prefix_requests_prefills_prefix_once(model):
    """(b): a submit_many wave of M same-prefix requests triggers
    exactly ONE prefix prefill — the rest admit as copy + tail — and
    the telemetry counters match the dispatch-level ground truth."""
    spec, params, tk = model
    prefix = tk.encode("common preamble for every request " * 3)
    # tails diverge at their FIRST token, so the shared prefix is
    # exactly `prefix` (a common leading tail char would legitimately
    # be reused too and shift the arithmetic below)
    tails = [tk.encode(t, add_bos=False) for t in ("A0", "B1", "C2", "D3")]
    prompts = [prefix + t for t in tails]

    off = _engine(model)
    off._prefix_enabled = False
    off_outs = off.submit_many(
        [GenRequest(prompt_ids=p, max_tokens=4, ignore_eos=True)
         for p in prompts])
    want_texts = [_drain(q)[1].full_text for q in off_outs]
    off.close()

    eng = _engine(model)
    spy = RunSpy(eng)
    snap = REGISTRY.snapshot()
    try:
        outs = eng.submit_many(
            [GenRequest(prompt_ids=p, max_tokens=4, ignore_eos=True)
             for p in prompts])
        finals = [_drain(q)[1] for q in outs]
    finally:
        eng.close()
    assert [f.full_text for f in finals] == want_texts
    # exactly one prefix prefill: req0 pays prefix+tail, the others
    # only their tails (every prompt fits one final chunk here)
    want_prefill = len(prompts[0]) + sum(len(t) for t in tails[1:])
    assert spy.prefill_tokens == want_prefill, (
        f"prefix prefilled more than once: {spy.prefill_tokens} "
        f"dispatched vs {want_prefill} expected")
    assert len(spy.copies) == 3
    delta = REGISTRY.delta(snap)
    m = eng._mlabel
    reused_copy = delta.get(
        f'engine_prefix_reused_tokens_total{{model="{m}",source="copy"}}',
        0.0)
    prefilled = delta.get(
        f'engine_prompt_tokens_total{{model="{m}"}}', 0.0)
    assert reused_copy == 3 * len(prefix)
    assert prefilled == want_prefill
    assert eng.metrics.prefill_tokens == want_prefill
    assert eng.metrics.prefix_reused_tokens == 3 * len(prefix)


def test_prefix_cache_off_escape_hatch(model, monkeypatch):
    monkeypatch.setenv("LOCALAI_PREFIX_CACHE", "off")
    eng = _engine(model)
    try:
        assert eng._prefix_enabled is False
        spy = RunSpy(eng)
        prompt = eng.tokenize("same prompt twice " * 4)
        for _ in range(2):
            ev = eng.generate(GenRequest(prompt_ids=prompt, max_tokens=2,
                                         ignore_eos=True))
            assert ev.finish_reason == "length"
        assert not spy.copies  # reuse still happens same-slot, no copies
    finally:
        eng.close()


def test_victim_selection_preserves_valuable_prefix(model):
    """Prefix-aware eviction: with several free slots and no own-slot
    match, the new request lands on the lowest-value resident (LRU x
    length) instead of clobbering the longest one."""
    spec, params, tk = model
    eng = _engine(model, n_slots=3)
    try:
        long_p = tk.encode("a long and valuable resident prefix " * 3)
        ev = eng.generate(GenRequest(prompt_ids=long_p, max_tokens=2,
                                     ignore_eos=True))
        assert ev.finish_reason == "length"
        donor_idx = next(s.idx for s in eng.slots
                         if len(s.cache_tokens) >= len(long_p))
        # unrelated prompt: must NOT evict the long resident
        ev2 = eng.generate(GenRequest(
            prompt_ids=tk.encode("zzz unrelated"), max_tokens=2,
            ignore_eos=True))
        assert ev2.finish_reason == "length"
        assert len(eng.slots[donor_idx].cache_tokens) >= len(long_p)
    finally:
        eng.close()


# slow tier: int8 serving identity is tier-1 in test_kv_quant and the
# fp cross-slot copy identity stays above; the scales-plane copy leg
# runs in the full suite
@pytest.mark.slow
def test_cross_slot_copy_quantized_kv(model):
    """(d) int8 KV: the copy moves k/v AND the per-row scales."""
    spec, params, tk = model
    prefix = tk.encode("quantized shared prefix " * 4)
    tail_a = tk.encode("one", add_bos=False)
    tail_b = tk.encode("two", add_bos=False)

    off = _engine(model, cache_dtype="int8")
    off._prefix_enabled = False
    want = off.generate(GenRequest(prompt_ids=prefix + tail_b,
                                   max_tokens=6, ignore_eos=True))
    off.close()
    assert want.finish_reason == "length", want.error

    eng = _engine(model, cache_dtype="int8")
    spy = RunSpy(eng)
    try:
        qa = eng.submit(GenRequest(prompt_ids=prefix + tail_a,
                                   max_tokens=40, ignore_eos=True))
        _first_token(qa)
        qb = eng.submit(GenRequest(prompt_ids=prefix + tail_b,
                                   max_tokens=6, ignore_eos=True))
        _, ev_b = _drain(qb)
        _drain(qa)
    finally:
        eng.close()
    assert spy.copies, "quantized path dispatched no kvcopy"
    assert ev_b.full_text == want.full_text


def test_cross_slot_copy_with_spec_decode(model):
    """(d) spec decode: the draft cache rows are copied alongside, and
    outputs still reproduce the main model's greedy sequence."""
    spec, params, tk = model
    dspec = tiny_spec(vocab_size=tk.vocab_size, d_model=32, n_layers=1,
                      d_ff=64, max_position=512)
    dparams = init_params(jax.random.PRNGKey(9), dspec,
                          dtype=jnp.float32)
    prefix = tk.encode("speculative shared prefix " * 4)
    tail_a = tk.encode("one", add_bos=False)
    tail_b = tk.encode("two", add_bos=False)

    plain = _engine(model)
    plain._prefix_enabled = False
    want = plain.generate(GenRequest(prompt_ids=prefix + tail_b,
                                     max_tokens=6, ignore_eos=True))
    plain.close()
    assert want.finish_reason == "length", want.error

    eng = _engine(model, draft=(dspec, dparams), n_draft=3,
                  decode_steps=16)
    spy = RunSpy(eng)
    try:
        qa = eng.submit(GenRequest(prompt_ids=prefix + tail_a,
                                   max_tokens=40, ignore_eos=True))
        _first_token(qa)
        qb = eng.submit(GenRequest(prompt_ids=prefix + tail_b,
                                   max_tokens=6, ignore_eos=True))
        _, ev_b = _drain(qb)
        _drain(qa)
    finally:
        eng.close()
    assert spy.copies, "spec-decode engine dispatched no kvcopy"
    assert ev_b.full_text == want.full_text


# slow tier: follower replay incl. prefix reuse + channel guards is
# tier-1 in test_multihost; the fp cross-slot copy identity stays above
@pytest.mark.slow
def test_cross_slot_copy_replays_on_multihost_follower(model):
    """kvcopy is a pure device op with a scalar payload: a follower
    replaying the leader's dispatch records (including the copy) must
    end bitwise-identical — the property that lets the cross-slot cache
    run under multihost where the on-disk restore cannot."""
    import threading

    from localai_tfp_tpu.parallel import multihost

    spec, params, tk = model
    kw = dict(n_slots=3, max_seq=256, prefill_buckets=(8, 32, 128),
              cache_dtype=jnp.float32, decode_steps=4)
    channel = multihost.LocalChannel()
    end = channel.follower_end()
    leader = LLMEngine(spec, params, tk, channel=channel, **kw)
    follower = LLMEngine(spec, params, tk, follower=True, **kw)
    t = threading.Thread(
        target=multihost.run_follower_engine, args=(follower, end),
        kwargs={"timeout": 60}, daemon=True)
    t.start()
    spy = RunSpy(leader)
    prefix = tk.encode("multihost shared prefix " * 4)
    qa = leader.submit(GenRequest(
        prompt_ids=prefix + tk.encode("one", add_bos=False),
        max_tokens=32, ignore_eos=True))
    _first_token(qa)  # donor active: forces the cross-slot copy path
    qb = leader.submit(GenRequest(
        prompt_ids=prefix + tk.encode("two", add_bos=False),
        max_tokens=4, ignore_eos=True))
    _drain(qb)
    _drain(qa)
    assert spy.copies, "scenario did not exercise a kvcopy record"
    leader.close()
    channel.publish("stop", None)
    t.join(timeout=60)
    assert not t.is_alive()
    np.testing.assert_array_equal(
        np.asarray(leader.cache.k), np.asarray(follower.cache.k))
    np.testing.assert_array_equal(
        np.asarray(leader.cache.v), np.asarray(follower.cache.v))


def test_resident_prefix_gauge_counts_idle_kv(model):
    spec, params, tk = model
    from localai_tfp_tpu.telemetry import metrics as tm

    eng = _engine(model)
    try:
        prompt = eng.tokenize("resident gauge prompt " * 3)
        ev = eng.generate(GenRequest(prompt_ids=prompt, max_tokens=2,
                                     ignore_eos=True))
        assert ev.finish_reason == "length"
        # poke the gauge refresh directly: the slot is idle but its
        # resident prefix must be visible
        eng._update_gauges()
        fam = tm.ENGINE_KV_RESIDENT_PREFIX
        val = {k: s for k, s in fam.collect()}
        key = next(k for k in val if eng._mlabel in str(k))
        assert val[key]["value"] >= len(prompt)
    finally:
        eng.close()
