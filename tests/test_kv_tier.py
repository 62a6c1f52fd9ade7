"""Tiered KV memory (engine/kv_tier.py): HBM -> host RAM -> disk.

The contract under test: slot churn DEMOTES sessions instead of
erasing them (capture-on-reuse spills before prepare_write discards),
a returning session PROMOTES with zero re-prefilled prompt tokens
(staged H2D scatter adopted by reference), shared prefixes spill once
(content-addressed dedup), the cold tier round-trips through the
prompt-cache file format, accounting survives churn (tier + pool
leak_check), and no device-step span ever overlaps a blocking tier
transfer — the async-DMA guarantee the whole design rests on.

``LOCALAI_KV_TIER=off`` must remove every hook: the off-engine has no
tier object at all, so today's byte-for-byte behavior is structural,
not a runtime branch."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import init_params
from localai_tfp_tpu.telemetry.flightrec import FLIGHT

_KNOBS = ("LOCALAI_KV_PAGE", "LOCALAI_KV_TIER",
          "LOCALAI_KV_TIER_IDLE_S", "LOCALAI_KV_TIER_WATERMARK",
          "LOCALAI_KV_TIER_HOST_MB", "LOCALAI_KV_TIER_COLD_S",
          "LOCALAI_KV_TIER_DIR")


@pytest.fixture(scope="module")
def model():
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    return spec, params, tk


@pytest.fixture(scope="module")
def eng(model):
    """One tiered engine for the module: 4 slots, 16-token pages so a
    ~50-char prompt spans several pages and spills are cheap."""
    spec, params, tk = model
    saved = {k: os.environ.get(k) for k in _KNOBS}
    os.environ["LOCALAI_KV_PAGE"] = "16"
    os.environ["LOCALAI_KV_TIER"] = "on"
    os.environ["LOCALAI_KV_TIER_IDLE_S"] = "0"
    try:
        e = LLMEngine(spec, params, tk, n_slots=4, max_seq=256,
                      prefill_buckets=(8, 32, 128),
                      cache_dtype=jnp.float32)
        assert e._tier is not None
        yield e
        e.close()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _drain(q, timeout=120):
    while True:
        ev = q.get(timeout=timeout)
        if ev.done:
            return ev


def _serve_wave(eng, prompts, max_tokens=6):
    reqs = [GenRequest(prompt_ids=eng.tokenize(p),
                       max_tokens=max_tokens, ignore_eos=True)
            for p in prompts]
    finals = [_drain(q) for q in eng.submit_many(reqs)]
    for f in finals:
        assert f.finish_reason == "length", f.error
    return reqs, finals


def _settle(eng, timeout_s=10.0):
    """Wait for the scheduler to go quiescent, then drive tier ticks
    from this thread until every in-flight transfer lands."""
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        with eng._lock:
            idle = (not eng._pending and not eng._flights
                    and not any(s.active for s in eng.slots))
        if idle:
            break
        time.sleep(0.02)
    time.sleep(0.05)
    eng._tier.settle()


def _leak_checks(eng):
    eng._tier.leak_check()
    eng._pool.leak_check()


# ---------------------------------------------------------------------------
# off-switch: no tier object, not a disabled one


def test_off_engine_has_no_tier_hooks(model):
    spec, params, tk = model
    saved = os.environ.get("LOCALAI_KV_TIER")
    os.environ["LOCALAI_KV_TIER"] = "off"
    try:
        e = LLMEngine(spec, params, tk, n_slots=2, max_seq=64,
                      prefill_buckets=(8, 32),
                      cache_dtype=jnp.float32)
        try:
            assert e._tier is None
            ev = e.generate(GenRequest(prompt_ids=e.tokenize("plain"),
                                       max_tokens=3, ignore_eos=True))
            assert ev.finish_reason == "length"
            e._pool.leak_check()
        finally:
            e.close()
    finally:
        if saved is None:
            os.environ.pop("LOCALAI_KV_TIER", None)
        else:
            os.environ["LOCALAI_KV_TIER"] = saved


def test_on_off_seeded_sampling_byte_identity(model, eng):
    """Tiering must be invisible to outputs: spilled pages round-trip
    host RAM in the native KV dtype and promote bit-exact, so a seeded
    churn+return workload streams byte-identical tokens on vs off —
    the off arm doubling as the HEAD-equivalence check (off has no
    tier object at all). The on arm is the module engine (this test
    runs first on it); only the off engine is built fresh — sampling
    is per-request seeded, so outputs are engine-history independent."""
    spec, params, tk = model
    users = [f"identity user {i} " + "w " * 12 for i in range(8)]
    waves = [users[:4], users[4:], users[:4]]  # wave 3 returns
    texts = {}
    hits0 = eng._tier.counters["prefetch_hit"]

    def run(e):
        outs = []
        for wave in waves:
            qs = e.submit_many([
                GenRequest(prompt_ids=e.tokenize(p),
                           max_tokens=10, temperature=0.8,
                           top_k=40, seed=7, ignore_eos=True)
                for p in wave])
            for q in qs:
                toks = []
                while True:
                    ev = q.get(timeout=120)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                    if ev.done:
                        assert ev.finish_reason == "length", ev.error
                        break
                outs.append(toks)
        return outs

    texts["on"] = run(eng)
    # the return wave must actually exercise promotion
    assert eng._tier.counters["prefetch_hit"] >= hits0 + 1
    saved = os.environ.get("LOCALAI_KV_TIER")
    os.environ["LOCALAI_KV_TIER"] = "off"
    try:
        e = LLMEngine(spec, params, tk, n_slots=4, max_seq=256,
                      prefill_buckets=(8, 32, 128),
                      cache_dtype=jnp.float32)
        assert e._tier is None
        try:
            texts["off"] = run(e)
        finally:
            e.close()
    finally:
        if saved is None:
            os.environ.pop("LOCALAI_KV_TIER", None)
        else:
            os.environ["LOCALAI_KV_TIER"] = saved
    assert texts["on"] == texts["off"]


# ---------------------------------------------------------------------------
# spill on churn -> prefetch on return


def test_churn_spills_and_return_prefetches_zero_reprefill(eng):
    tier = eng._tier
    users = [f"user {i:02d} " + "context " * 5 + f"tail{i}"
             for i in range(8)]
    # waves of distinct sessions: each admission past wave 1 reassigns
    # a slot, and capture-on-reuse must move the evictee down a tier
    _serve_wave(eng, users[:4])
    _serve_wave(eng, users[4:])
    _settle(eng)
    st = tier.stats()
    assert st["spills"] >= 4, st
    assert st["entries_warm"] >= 4, st
    assert st["host_pages"] > 0 and st["host_bytes"] > 0
    _leak_checks(eng)

    # wave 1 returns: every prompt is covered by a warm entry, so each
    # admission must be a prefetch hit that re-prefills NOTHING beyond
    # the relogit token (prompt tokens all arrive via the H2D stage)
    hits0 = tier.counters["prefetch_hit"]
    reused0 = eng.metrics.prefix_reused_tokens
    _, finals = _serve_wave(eng, users[:4])
    _settle(eng)
    assert tier.counters["prefetch_hit"] - hits0 == 4, tier.counters
    plens = [len(eng.tokenize(u)) for u in users[:4]]
    # the resident prefix after adoption covers the full prompt; the
    # engine relogits the last token, so >= plen-1 reuse per request
    assert eng.metrics.prefix_reused_tokens - reused0 >= \
        sum(plens) - len(plens)
    _leak_checks(eng)


def test_shared_prefix_spills_once(eng):
    """Content addressing: two sessions sharing full pages of prefix
    hold ONE host copy of those pages, refcounted."""
    tier = eng._tier
    shared = "shared system preamble " * 3  # ~69 chars -> 4 full pages
    _serve_wave(eng, [shared + "alpha", shared + "beta"])
    _settle(eng)
    sa, sb = (s for s in eng.slots
              if s.cache_tokens
              and s.cache_tokens[:8] == eng.tokenize(shared)[:8])
    dedup0 = tier.counters["dedup_pages"]
    pages0 = tier.stats()["host_pages"]
    now = time.perf_counter()
    tier._spill(sa, urgent=True, now=now)
    _settle(eng)
    tier._spill(sb, urgent=True, now=now)
    _settle(eng)
    st = tier.stats()
    shared_pages = len(eng.tokenize(shared)) // tier.P
    assert tier.counters["dedup_pages"] - dedup0 >= shared_pages
    # the second spill added only its distinct tail pages
    added = st["host_pages"] - pages0
    npg_each = -(-len(sa.cache_tokens) // tier.P)
    assert added < 2 * npg_each
    _leak_checks(eng)


# ---------------------------------------------------------------------------
# cold tier: warm -> disk -> warm through the prompt-cache format


def test_cold_save_load_roundtrip(eng, tmp_path):
    tier = eng._tier
    prompt = "cold storage session " + "x " * 20 + "end"
    _serve_wave(eng, [prompt])
    _settle(eng)
    slot = next(s for s in eng.slots
                if s.cache_tokens
                and s.cache_tokens[:8] == eng.tokenize(prompt)[:8])
    tier._spill(slot, urgent=True, now=time.perf_counter())
    _settle(eng)
    ent = next(e for e in tier._entries.values()
               if e.tokens[:8] == eng.tokenize(prompt)[:8])
    saved_dir, saved_cold = tier.cold_dir, tier.cold_s
    tier.cold_dir, tier.cold_s = str(tmp_path), 1e-6
    try:
        tier._start_save(ent)
        _settle(eng)
        assert ent.state == "cold" and ent.path
        assert ent.hpids == []  # host pages released on demotion
        # the file IS the prompt-cache format
        with np.load(ent.path) as data:
            assert set(data.files) >= {"tokens", "k", "v"}
            assert data["k"].shape[1] == ent.n
        assert tier.stats()["disk_pages"] > 0
        _leak_checks(eng)

        # churn every slot so no resident copy outcompetes the fetch
        # (the target slot's capture is dedup-skipped: the cold entry
        # already covers its exact state)
        _serve_wave(eng, [f"cold churn filler {i} " + "q " * 16
                          for i in range(4)])
        _settle(eng)

        # the session returns: admission holds the request inside the
        # fetch deadline while the load runs, then prefetches
        hits0 = tier.counters["prefetch_hit"]
        loads0 = tier.counters["loads"]
        _serve_wave(eng, [prompt])
        _settle(eng)
        assert tier.counters["loads"] - loads0 == 1
        assert tier.counters["prefetch_hit"] - hits0 == 1
        _leak_checks(eng)
    finally:
        tier.cold_dir, tier.cold_s = saved_dir, saved_cold


# ---------------------------------------------------------------------------
# the async guarantee: tier DMA never blocks a device step


def test_no_device_step_overlaps_blocking_transfer(eng):
    """Every kv:* span on the kv_tier track must be non-blocking, and
    (belt and braces) no step:* span on the device track may overlap a
    blocking transfer in time — the flightrec evidence that a spill or
    fetch never stalls the scheduler's device work."""
    FLIGHT.clear()
    _serve_wave(eng, [f"overlap probe {i} " + "y " * 24
                      for i in range(6)])
    _settle(eng)
    trace = FLIGHT.export_chrome_trace()
    tracks = {ev["tid"]: ev["args"]["name"]
              for ev in trace["traceEvents"]
              if ev.get("ph") == "M" and ev["name"] == "thread_name"}
    spans = [ev for ev in trace["traceEvents"] if ev.get("ph") == "X"]
    kv = [ev for ev in spans
          if tracks.get(ev["tid"]) == "kv_tier"
          and ev["name"].startswith("kv:")]
    steps = [ev for ev in spans
             if tracks.get(ev["tid"]) == "device"
             and ev["name"].startswith("step:")]
    assert kv, "traffic recorded no tier transfer spans"
    assert steps, "traffic recorded no device step spans"
    assert all(ev["args"]["blocking"] is False for ev in kv)
    blocking = [ev for ev in kv if ev["args"]["blocking"]]
    for b in blocking:  # empty today by construction; the real check
        b0, b1 = b["ts"], b["ts"] + b["dur"]
        for s in steps:
            s0, s1 = s["ts"], s["ts"] + s["dur"]
            assert s1 <= b0 or s0 >= b1, (
                f"device step {s['name']} overlaps blocking "
                f"transfer {b['name']}")
    _leak_checks(eng)


# ---------------------------------------------------------------------------
# accounting survives sustained churn


def test_leak_check_clean_under_churn(eng):
    tier = eng._tier
    for wave in range(4):
        _serve_wave(eng, [f"churn w{wave} u{i} " + "z " * 16
                          for i in range(4)], max_tokens=4)
    # revisit half of the sessions to mix promotions into the churn
    _serve_wave(eng, [f"churn w1 u{i} " + "z " * 16 for i in range(2)],
                max_tokens=4)
    _settle(eng)
    st = tier.stats()
    assert st["spills"] >= 8
    _leak_checks(eng)
    # budget pressure: shrink the host pool and force evictions
    saved = tier.host_budget
    tier.host_budget = 1  # everything is over budget
    try:
        _settle(eng)  # settle forces a policy scan
        for _ in range(32):
            tier.tick()
            tier._t_scan = 0.0
        assert tier.stats()["host_bytes"] <= st["host_bytes"]
        _leak_checks(eng)
    finally:
        tier.host_budget = saved


# ---------------------------------------------------------------------------
# the device half of a spill: b pages read, b written, nothing else


_PLANES = {  # a K / V plane in each served dtype, and a scale plane
    "int8": ((3, 19, 8, 128), jnp.int8),
    "bfloat16": ((2, 19, 8, 64), jnp.bfloat16),
    "scale": ((3, 19, 8), jnp.float32),
}


def _plane(shape, dtype):
    rng = np.random.default_rng(sum(shape))
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-128, 128, shape, np.int8))
    return jnp.asarray(rng.standard_normal(shape, np.float32)).astype(dtype)


def _spill_table(b, n_pages):
    """What ``_spill`` hands the gather: page ids in any order, the
    tail padded to a power of two with the trash page — and one id
    twice, which the gather must not mind."""
    from localai_tfp_tpu.engine.kv_pool import TRASH_PAGE

    ids = np.random.default_rng(b).permutation(
        np.arange(1, n_pages))[:b].astype(np.int32)
    if b >= 4:
        ids[1] = ids[0]
        ids[-(b // 4):] = TRASH_PAGE
    return ids


@pytest.mark.parametrize("b", [1, 4, 16])
@pytest.mark.parametrize("plane", sorted(_PLANES))
def test_gather_pages_is_bit_equal_to_plain_indexing(plane, b):
    from localai_tfp_tpu.engine.kv_tier import _gather_pages

    shape, dtype = _PLANES[plane]
    arr = _plane(shape, dtype)
    ids = _spill_table(b, shape[1])
    got = _gather_pages(arr, jnp.asarray(ids))
    want = arr[:, ids]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(np.asarray(got).view(np.uint8),
                          np.asarray(want).view(np.uint8))


@pytest.mark.parametrize("plane", sorted(_PLANES))
def test_gather_pages_lowers_to_slices_not_a_gather(plane):
    """``arr[:, tbl]`` lowers to ONE gather over the pool plane, which
    the v5e compiler turns into a copy of the whole plane; the spill's
    program must hold none."""
    from localai_tfp_tpu.engine.kv_tier import _gather_pages

    shape, dtype = _PLANES[plane]
    args = (jax.ShapeDtypeStruct(shape, dtype),
            jax.ShapeDtypeStruct((4,), jnp.int32))
    text = _gather_pages.lower(*args).as_text()
    assert "stablehlo.gather" not in text
    assert "stablehlo.dynamic_gather" not in text
    assert text.count("stablehlo.dynamic_slice") == 4
    # the test's own reading of what it guards against
    assert "stablehlo.gather" in jax.jit(
        lambda a, t: a[:, t]).lower(*args).as_text()


@pytest.mark.parametrize("cache_dtype", ["module-engine", "int8"])
def test_spilled_host_pages_equal_the_pool_pages(model, eng, cache_dtype,
                                                 monkeypatch):
    """Engine level: what a spill leaves in host RAM is, plane by plane
    and bit for bit, the pool's pages of that session (int8: K, V and
    both scale planes — four gathers a spill)."""
    if cache_dtype == "module-engine":
        e = eng
    else:
        spec, params, tk = model
        monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
        monkeypatch.setenv("LOCALAI_KV_TIER", "on")
        e = LLMEngine(spec, params, tk, n_slots=2, max_seq=128,
                      prefill_buckets=(8, 32, 128),
                      cache_dtype=cache_dtype)
    try:
        tier = e._tier
        prompt = f"bit equal session {cache_dtype} " + "p " * 14 + "end"
        _serve_wave(e, [prompt])
        _settle(e)
        head = e.tokenize(prompt)[:8]
        slot = next(s for s in e.slots
                    if s.cache_tokens and s.cache_tokens[:8] == head)
        n = len(slot.cache_tokens)
        table = e._pool.table(slot.idx)[:e._pool.pages_for(n)]
        assert len(table) >= 3  # 3 pages pad to 4: the trash page rides
        c = e.cache
        planes = {"k": c.k, "v": c.v}
        if c.quantized:
            planes.update(k_scale=c.k_scale, v_scale=c.v_scale)
        want = {nm: np.asarray(a)[:, table] for nm, a in planes.items()}
        assert tier._spill(slot, urgent=True, now=time.perf_counter())
        _settle(e)
        ent = next(x for x in tier._entries.values()
                   if x.tokens[:8] == head and x.n == n)
        assert len(ent.hpids) == len(table)
        for j, hpid in enumerate(ent.hpids):
            arrays = tier._host[hpid].arrays
            assert set(arrays) == set(planes)
            for nm, a in arrays.items():
                assert a.dtype == want[nm].dtype
                assert np.array_equal(a, want[nm][:, j]), (nm, j)
        _leak_checks(e)
    finally:
        if e is not eng:
            e.close()


def test_profile_kv_gather_mode_rehearsal_on_the_cpu():
    """tools/profile_kv.py --gather at its smoke planes: every point
    bit-equal to plain indexing, and no CPU time under a device
    metric's name."""
    from tools.profile_kv import _SMALL_PLANES, gather_alone

    rep = gather_alone(_SMALL_PLANES, calls=2)
    assert rep["ok"] and len(rep["points"]) == 4
    assert rep["device"]["platform"] == "cpu"
    for p in rep["points"]:
        assert p["bit_equal"] and p["roof_us"] is None
        assert p["_gather_pages"] == {"us_call": "not measured"}
