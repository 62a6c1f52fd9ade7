"""Pod-scale paged serving: the page arena + ragged attention across a
mesh (ISSUE 12).

A meshed engine pages its KV exactly like a single-chip one: the
[L, n_pages, page, kv_dim] arena shards its head-flat dim over "model"
(parallel/sharding.PAGED_KV_SPEC — each device holds its kv-head slice
of EVERY page) while the allocator and its int32 page tables stay
host-owned and global. Covered here:

- paged+ragged meshed serving is byte-identical to the dense meshed
  path (greedy AND seeded sampling), which LOCALAI_PAGED_KV=off
  selects
- prefix page-sharing/COW and ``leak_check`` hold under churn on a
  meshed engine (allocator state never left the host, so sharding the
  arena must not perturb it)
- a multihost follower replays sharded paged dispatches to a bitwise-
  identical arena (tables ride the codec as plain int32 payloads)
- KV tiering stays FORCE-OFF on meshed engines even with
  LOCALAI_KV_TIER=on (a host spill of a model-sharded page would be an
  implicit cross-shard all-gather)
- an int8 arena meshes too: quantized pages shard with their heads,
  the replicated per-row scale planes survive the _pin_win_sharding
  round-trip, and paged-vs-dense byte-identity still holds
- shard_engine_state refuses a kv_dim that does not divide the tp axis
  instead of silently replicating the cache (a tp-times HBM
  regression) — dense and paged alike, so a meshed LLMEngine with an
  indivisible kv_dim fails construction (no dense carve-out)
- the shard_map'd append+attend wrapper matches the dense oracle on
  this host's virtual mesh (fp + int8), via ops/kernel_check
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import init_params
from localai_tfp_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def model():
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    return spec, params, tk


def _mesh(model_ax=4, data_ax=2):
    return make_mesh({"data": data_ax, "seq": 1, "model": model_ax},
                     devices=jax.devices("cpu")[:data_ax * model_ax])


def _engine(model, **kw):
    spec, params, tk = model
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_seq", 128)
    kw.setdefault("prefill_buckets", (8, 32))
    kw.setdefault("cache_dtype", jnp.float32)
    return LLMEngine(spec, params, tk, **kw)


def _drain(q, timeout=120):
    toks = []
    while True:
        ev = q.get(timeout=timeout)
        if ev.done:
            return toks, ev
        if ev.token_id is not None:
            toks.append(ev.token_id)


def _serve(eng, prompts):
    """Exact per-request token streams. Stream events are
    harvest-coalesced (multi-token spans per event — timing-dependent),
    so byte-identity must compare ``slot.generated`` at finish, not the
    event train."""
    gen: dict[str, list[int]] = {}
    orig = eng._finish

    def spy(slot, reason):
        if slot.request is not None:
            gen[slot.request.id] = list(slot.generated)
        return orig(slot, reason)

    eng._finish = spy
    # 6 decode steps: enough to exercise append/attend/sample on every
    # step (the 19-token prompts already span two 16-token pages after
    # prefill; decode stays inside page 2 at any depth <= 12)
    reqs = (
        [GenRequest(prompt_ids=ids, max_tokens=6, ignore_eos=True)
         for ids in prompts[:2]]
        + [GenRequest(prompt_ids=ids, max_tokens=6, temperature=0.8,
                      top_k=40, seed=7, ignore_eos=True)
           for ids in prompts[2:]])
    for q in eng.submit_many(reqs):
        _, ev = _drain(q)
        assert ev.finish_reason == "length", ev.error
        assert ev.completion_tokens == 6
    return [gen[r.id] for r in reqs]


def test_meshed_paged_on_off_byte_identity(model, monkeypatch):
    """The tentpole contract: a meshed engine with the sharded page
    arena (and the ragged full-width dispatch shapes) streams the SAME
    BYTES as the dense meshed engine (LOCALAI_PAGED_KV=off) — greedy
    and seeded sampling."""
    from localai_tfp_tpu.parallel.sharding import PAGED_KV_SPEC

    monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
    prompts = [list(range(1, 20)), [9, 8, 7, 6, 5],
               list(range(1, 20)), [3, 1, 4, 1, 5]]
    mesh = _mesh()
    outs = {}
    for paged in ("on", "off"):
        monkeypatch.setenv("LOCALAI_PAGED_KV", paged)
        eng = _engine(model, mesh=mesh)
        assert eng._paged == (paged == "on")
        assert eng.attention_path == (
            "paged_xla_gather" if eng._paged else "dense_xla")
        try:
            if eng._paged:
                # the arena actually lives sharded on the mesh
                sh = eng.cache.k.sharding
                assert sh.spec == PAGED_KV_SPEC, sh
                eng._pool.leak_check()
            outs[paged] = _serve(eng, prompts)
            if eng._paged:
                eng._pool.leak_check()
        finally:
            eng.close()
    assert outs["on"] == outs["off"]


# slow tier: meshed int8 numerics stay tier-1 via the kernel parity
# test below; unmeshed int8 serving identity lives in test_kv_quant
@pytest.mark.slow
def test_meshed_paged_int8_byte_identity(model, monkeypatch):
    """The quantized arena on a mesh: int8 pages shard with their
    heads while the [L, B, W] per-row scale planes stay replicated —
    including across the _pin_win_sharding round-trip, where the
    gathered window's slot dim is replicated (the very condition GSPMD
    miscompiles for the K/V rows). Paged+ragged meshed serving with an
    int8 cache must stream the same bytes as the dense meshed int8
    engine, greedy and seeded."""
    monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
    prompts = [list(range(1, 20)), [9, 8, 7, 6, 5],
               list(range(1, 20)), [3, 1, 4, 1, 5]]
    mesh = _mesh()
    outs = {}
    for paged in ("on", "off"):
        monkeypatch.setenv("LOCALAI_PAGED_KV", paged)
        eng = _engine(model, mesh=mesh, cache_dtype="int8")
        assert eng._paged == (paged == "on")
        assert eng.cache.quantized
        try:
            if eng._paged:
                # quantized rows shard like fp rows; scales replicate
                from localai_tfp_tpu.parallel.sharding import (
                    PAGED_KV_SPEC,
                )

                assert eng.cache.k.sharding.spec == PAGED_KV_SPEC
                assert eng.cache.k_scale.sharding.is_fully_replicated
            outs[paged] = _serve(eng, prompts)
        finally:
            eng.close()
    assert outs["on"] == outs["off"]


# slow tier: the pool/COW invariants are host-side and churn-tested
# unmeshed in test_paged_kv; the GSPMD sharding class this once caught
# is pinned statically by the sharding-contract lint rule
@pytest.mark.slow
def test_meshed_page_share_cow_leak_check(model, monkeypatch):
    """Prefix page-sharing, COW, and pool invariants are host-side
    logic the sharded arena must not perturb: shared-prefix admissions
    transfer pages by refcount on a meshed engine too, and churn with
    cancels leaves the pool leak-free."""
    monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
    monkeypatch.setenv("LOCALAI_PAGED_KV", "on")
    prefix = list(range(1, 33))  # 2 full 16-token pages
    eng = _engine(model, mesh=_mesh(), n_slots=4)
    assert eng._paged
    rng = np.random.default_rng(5)
    try:
        qa = eng.submit(GenRequest(prompt_ids=prefix + [40, 41],
                                   max_tokens=12, ignore_eos=True))
        while True:  # donor prefix committed once the first token lands
            ev = qa.get(timeout=120)
            assert not ev.done, ev.error
            if ev.token_id is not None:
                break
        shared0 = eng._pool.allocs["shared"]
        qb = eng.submit(GenRequest(prompt_ids=prefix + [50, 51],
                                   max_tokens=6, ignore_eos=True))
        _drain(qb)
        _drain(qa)
        assert eng._pool.allocs["shared"] - shared0 >= 2
        # churn: waves beyond slot capacity + a mid-stream cancel
        for _ in range(2):
            reqs = [GenRequest(
                prompt_ids=[int(x) for x in rng.integers(
                    1, 200, int(rng.integers(4, 40)))],
                max_tokens=int(rng.integers(2, 8)),
                ignore_eos=True) for _ in range(eng.n_slots + 2)]
            qs = eng.submit_many(reqs)
            eng.cancel(reqs[0].id)
            for q in qs[1:]:
                _drain(q)
            _drain(qs[0])
        import time as _t

        _t.sleep(0.2)
        eng._pool.leak_check()
        for s in eng.slots:
            assert not s.active
            eng._pool.drop(s.idx)
        st = eng._pool.stats()
        assert st.in_use == 0 and st.refs == 0 and st.free == st.total
    finally:
        eng.close()


# slow tier: follower replay of dispatch records stays tier-1 in
# test_multihost; paged payload replayability (structural) in
# test_paged_kv
@pytest.mark.slow
def test_meshed_follower_replays_paged_dispatches(model, monkeypatch):
    """Multihost: a follower meshed engine replays the leader's paged
    dispatches — page tables cross as plain int32 payloads, allocator
    state never crosses — and ends with a bitwise-identical sharded
    arena (the multi-controller SPMD requirement on a real pod)."""
    from localai_tfp_tpu.parallel import multihost

    monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
    monkeypatch.setenv("LOCALAI_PAGED_KV", "on")
    spec, params, tk = model
    mesh = _mesh()
    kw = dict(n_slots=2, max_seq=128, prefill_buckets=(8, 32),
              cache_dtype=jnp.float32, decode_steps=4, mesh=mesh)
    channel = multihost.LocalChannel()
    end = channel.follower_end()
    leader = LLMEngine(spec, params, tk, channel=channel, **kw)
    follower = LLMEngine(spec, params, tk, follower=True, **kw)
    assert leader._paged and follower._paged
    t = threading.Thread(
        target=multihost.run_follower_engine, args=(follower, end),
        kwargs={"timeout": 60}, daemon=True,
    )
    t.start()
    base = tk.encode("the quick brown fox")
    toks1, _ = _drain(leader.submit(GenRequest(
        prompt_ids=base, max_tokens=6, ignore_eos=True)))
    _drain(leader.submit(GenRequest(  # prefix reuse: share/kvcopy replay
        prompt_ids=base + toks1[:2], max_tokens=4,
        temperature=0.8, seed=3, ignore_eos=True)))
    leader.close()
    channel.publish("stop", None)
    t.join(timeout=60)
    assert not t.is_alive()
    np.testing.assert_array_equal(
        np.asarray(leader.cache.k), np.asarray(follower.cache.k))
    np.testing.assert_array_equal(
        np.asarray(leader.cache.v), np.asarray(follower.cache.v))
    np.testing.assert_array_equal(
        np.asarray(leader.sampling.history),
        np.asarray(follower.sampling.history))


def test_meshed_engine_forces_kv_tier_off(model, monkeypatch):
    """LOCALAI_KV_TIER=on must NOT tier a meshed engine: spilling a
    PAGED_KV_SPEC page to host RAM would all-gather the model shards on
    every spill. The same knob still tiers an unmeshed engine."""
    monkeypatch.setenv("LOCALAI_KV_TIER", "on")
    monkeypatch.setenv("LOCALAI_PAGED_KV", "on")
    meshed = _engine(model, mesh=_mesh(), autostart=False)
    try:
        assert meshed._paged and meshed._tier is None
    finally:
        meshed.close()
    plain = _engine(model, autostart=False)
    try:
        assert plain._tier is not None  # the knob itself still works
    finally:
        plain.close()


def test_shard_engine_state_rejects_indivisible_kv_dim(model, monkeypatch):
    """kv_dim % tp != 0 must error early and loudly — in BOTH modes
    (the dense cache and the paged arena share the trailing kv_dim) —
    the old ``_divisible_spec`` fallback replicated the WHOLE cache per
    shard (a tp-times HBM capacity regression masquerading as
    working)."""
    from localai_tfp_tpu.models.transformer import KVCache
    from localai_tfp_tpu.ops.sampling import SamplingState
    from localai_tfp_tpu.parallel.sharding import shard_engine_state

    _, _, tk = model
    bad = tiny_spec(vocab_size=tk.vocab_size, max_position=512,
                    n_kv_heads=1, d_head=20)  # kv_dim 20, tp 8
    mesh = make_mesh({"data": 1, "seq": 1, "model": 8},
                     devices=jax.devices("cpu"))
    sampling = SamplingState.create(2, bad.vocab_size)
    dense = KVCache.create(bad, 2, 32, jnp.float32)
    with pytest.raises(ValueError, match="not divisible"):
        shard_engine_state(dense, sampling, mesh)
    arena = KVCache.create(bad, 8, 16, jnp.float32)  # paged geometry
    with pytest.raises(ValueError, match="not divisible"):
        shard_engine_state(arena, sampling, mesh, paged=True)
    # and there is deliberately NO dense engine carve-out: a meshed
    # LLMEngine with an indivisible kv_dim fails construction with the
    # same actionable message whether paging is on or off, instead of
    # silently serving a tp-times-replicated cache
    params = init_params(jax.random.PRNGKey(1), bad, dtype=jnp.float32)
    for paged in ("on", "off"):
        monkeypatch.setenv("LOCALAI_PAGED_KV", paged)
        with pytest.raises(ValueError, match="not divisible"):
            LLMEngine(bad, params, tk, n_slots=2, max_seq=128,
                      prefill_buckets=(8, 32), cache_dtype=jnp.float32,
                      mesh=mesh, autostart=False)


def test_meshed_ragged_kernel_parity_fp_and_int8():
    """The shard_map'd append+attend wrapper (the meshed serving route
    for every ragged dispatch kind) vs the dense single-device oracle
    on this host's virtual devices — decode seed rows and mixed ragged
    rows, fp and int8 (ops/kernel_check meshed legs, which bench.py
    runs on the real pod)."""
    from localai_tfp_tpu.ops.kernel_check import (
        check_meshed_paged_gather, check_meshed_ragged_attention,
    )

    err = check_meshed_ragged_attention(False, mix="mixed")
    assert err is not None, "conftest forces 8 devices; mesh missing"
    assert err < 2e-2
    assert check_meshed_ragged_attention(False, mix="decode") < 2e-2
    assert check_meshed_ragged_attention(True, mix="mixed") < 5e-2
    # the GSPMD gather fallback is pure indexing: exact or broken
    assert check_meshed_paged_gather(False) == 0.0
    assert check_meshed_paged_gather(True) == 0.0
