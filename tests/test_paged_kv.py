"""Paged KV pool: block-granular HBM allocation + zero-copy sharing.

The dense cache pre-reserves max_seq per slot; the paged pool
(engine/kv_pool.py) backs slots with fixed-size pages from one shared
arena, shares prefix pages by refcount instead of row copy, and must
be byte-identical to the dense path. Covered here:

- allocator churn fuzz: admit/release/share/COW loops never leak a
  page, never double-own a writable page, and refcounts return to zero
- whole-page shared-prefix admission dispatches ZERO kvcopies (the
  zero-copy claim, cross-checked against allocator outcome counters)
- engine-level churn (waves + mid-stream cancels + slot reuse) leaves
  the pool leak-free
- gather/scatter page views are exact inverses and trash-redirected
  writes never land
- paged dispatch payloads stay multihost-replayable (scalars + index
  arrays only — the codec round-trips every record bit-exactly)
- LOCALAI_PAGED_KV on/off produce byte-identical streams
"""

import queue as _q

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
from localai_tfp_tpu.engine.kv_pool import (
    TRASH_PAGE,
    PagePool,
    PagePoolExhausted,
)
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import init_params


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


class CopySpy:
    """Record every dispatch at the engine._run layer: kind counts for
    the zero-copy regression plus raw payloads for the replay-invariant
    check."""

    def __init__(self, eng):
        self.eng = eng
        self.records: list[tuple[str, dict]] = []
        self._orig = eng._run
        eng._run = self._run

    def _run(self, kind, payload):
        self.records.append((kind, dict(payload)))
        return self._orig(kind, payload)

    def count(self, kind):
        return sum(1 for k, _ in self.records if k == kind)


def _drain(q, timeout=120):
    toks = []
    while True:
        ev = q.get(timeout=timeout)
        if ev.done:
            return toks, ev
        if ev.token_id is not None:
            toks.append(ev.token_id)


def _first_token(q, timeout=120):
    while True:
        ev = q.get(timeout=timeout)
        assert not ev.done, f"finished early: {ev.finish_reason} {ev.error}"
        if ev.token_id is not None:
            return ev


# ---------------------------------------------------------- pool unit


def test_pool_basic_share_cow_lifecycle():
    pool = PagePool(8, 16)
    assert pool.ensure(0, 40) == 3  # 3 pages for 40 tokens
    t0 = list(pool.table(0))
    assert all(pool.writable(p) for p in t0)
    # zero-copy share of the first 2 full pages into slot 1
    assert pool.share(1, 0, 2) == 2
    assert pool.table(1) == t0[:2]
    assert not pool.writable(t0[0]) and not pool.writable(t0[1])
    assert pool.stats().shared == 2
    # aligned frontier (32 = 2 pages): no COW needed, nothing to copy
    assert pool.prepare_write(1, 32) is None
    # unaligned frontier inside a shared page: COW swaps in a fresh page
    pool.share(2, 0, 2)
    cow = pool.prepare_write(2, 24)
    assert cow is not None
    src, dst = cow
    assert src == t0[1] and pool.writable(dst)
    assert pool.table(2)[0] == t0[0]  # untouched shared page remains
    for s in (0, 1, 2):
        pool.drop(s)
    st = pool.stats()
    assert st.in_use == 0 and st.free == st.total and st.refs == 0
    pool.leak_check()


def test_pool_exhaustion_raises_and_stays_consistent():
    pool = PagePool(4, 16)  # 3 data pages
    pool.ensure(0, 48)
    with pytest.raises(PagePoolExhausted):
        pool.ensure(1, 16)
    pool.leak_check()
    pool.drop(0)
    assert pool.ensure(1, 16) == 1
    pool.leak_check()


def test_pool_churn_fuzz():
    """Randomized admit/cancel/evict/preempt churn: after every single
    operation the structural invariants hold (no leaked page, no free
    page referenced, refcount == table references, trash never owned),
    and a full drop returns every refcount to zero."""
    rng = np.random.default_rng(0)
    pool = PagePool(48, 16)
    slots = 8
    cap = 47 * 16
    for _ in range(3000):
        op = int(rng.integers(0, 6))
        s = int(rng.integers(0, slots))
        try:
            if op == 0:  # admit / grow
                pool.ensure(s, int(rng.integers(0, cap // 4)))
            elif op == 1:  # cancel / evict
                pool.drop(s)
            elif op == 2:  # preempt to a shorter prefix
                pool.truncate(s, int(rng.integers(0, cap // 4)))
            elif op == 3:  # zero-copy prefix share
                src = int(rng.integers(0, slots))
                if src != s and pool.held(src):
                    pool.share(
                        s, src,
                        int(rng.integers(0, pool.held(src) + 1)))
            elif op == 4:  # write-frontier privatization (maybe COW)
                held = pool.held(s)
                pos = int(rng.integers(0, held * 16 + 1)) if held else 0
                pool.prepare_write(s, pos)
            else:  # fresh single-page append (decode growth)
                pool.append_fresh(s)
        except PagePoolExhausted:
            pool.drop(s)  # the engine's reclaim analogue
        pool.leak_check()
        # no page may ever be writable through two tables
        owners: dict[int, int] = {}
        for t in pool._tables.values():
            for pg in t:
                owners[pg] = owners.get(pg, 0) + 1
        for pg, n in owners.items():
            assert pg != TRASH_PAGE
            if pool.writable(pg):
                assert n == 1, f"writable page {pg} owned by {n} tables"
    for s in range(slots):
        pool.drop(s)
    st = pool.stats()
    assert st.in_use == 0 and st.refs == 0 and st.free == st.total
    pool.leak_check()


def test_prefix_index_page_run_splits_full_and_tail():
    from localai_tfp_tpu.engine.prefix_index import PrefixIndex

    idx = PrefixIndex()
    idx.set_tokens(0, list(range(40)))
    # 40 matched tokens at 16-token pages: 2 zero-copy full pages + an
    # 8-row tail the engine row-copies
    assert idx.page_run(list(range(40)) + [99], 16) == (2, 8, {0})
    assert idx.page_run([7, 7, 7], 16) == (0, 0, set())


# ------------------------------------------------- transformer views


def test_gather_scatter_kv_pages_roundtrip():
    """gather_kv_pages must reproduce the dense window exactly through
    a shuffled table; scatter_kv_pages must write ONLY the pages its wb
    names, with trash-redirected entries dropped."""
    from localai_tfp_tpu.models.transformer import (
        KVCache, gather_kv_pages, scatter_kv_pages,
    )

    rng = np.random.default_rng(1)
    L, NP, P, F, B, WP = 2, 7, 4, 8, 3, 2
    arena = KVCache(
        k=jnp.asarray(rng.standard_normal((L, NP, P, F)), jnp.float32),
        v=jnp.asarray(rng.standard_normal((L, NP, P, F)), jnp.float32))
    phys = jnp.asarray(rng.permutation(np.arange(1, 7))
                       .reshape(B, WP).astype(np.int32))
    win = gather_kv_pages(arena, phys, P)
    assert win.k.shape == (L, B, WP * P, F)
    pn = np.asarray(phys)
    for b in range(B):
        for p in range(WP):
            np.testing.assert_array_equal(
                np.asarray(win.k)[:, b, p * P:(p + 1) * P],
                np.asarray(arena.k)[:, pn[b, p]])
    # writeback: row 0 persists only its second page; rows 1-2 nothing
    marked = KVCache(k=win.k + 100.0, v=win.v - 100.0)
    wb = np.full((B, WP), TRASH_PAGE, np.int32)
    wb[0, 1] = pn[0, 1]
    out = scatter_kv_pages(arena, marked, jnp.asarray(wb), P)
    np.testing.assert_array_equal(
        np.asarray(out.k)[:, pn[0, 1]],
        np.asarray(arena.k)[:, pn[0, 1]] + 100.0)
    for pg in range(1, NP):  # every other data page untouched
        if pg == pn[0, 1]:
            continue
        np.testing.assert_array_equal(np.asarray(out.k)[:, pg],
                                      np.asarray(arena.k)[:, pg])


# --------------------------------------------------------- engine level


def test_whole_page_shared_prefix_zero_copies(model, monkeypatch):
    """Regression for the zero-copy claim: a sharer whose matched
    prefix is whole-page-aligned admits with NO kvcopy dispatch — the
    pages transfer by refcount — and the allocator's `shared` outcome
    counter (telemetry ground truth) shows exactly those pages."""
    monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
    prefix = list(range(1, 33))  # 32 tokens == 2 full 16-token pages
    tail_a = [40, 41, 42, 43]
    tail_b = [50, 51, 52, 53]  # diverges at its first token
    eng = _engine(model)
    assert eng._paged and eng._page == 16
    spy = CopySpy(eng)
    try:
        qa = eng.submit(GenRequest(prompt_ids=prefix + tail_a,
                                   max_tokens=24, ignore_eos=True))
        _first_token(qa)  # donor prefix committed, slot still DECODE
        shared0 = eng._pool.allocs["shared"]
        qb = eng.submit(GenRequest(prompt_ids=prefix + tail_b,
                                   max_tokens=8, ignore_eos=True))
        _, ev_b = _drain(qb)
        _, ev_a = _drain(qa)
    finally:
        eng.close()
    assert ev_a.finish_reason == "length", ev_a.error
    assert ev_b.finish_reason == "length", ev_b.error
    assert spy.count("kvcopy") == 0, (
        "whole-page prefix share must not row-copy")
    assert eng._pool.allocs["shared"] - shared0 == 2
    assert eng.metrics.prefix_reused_tokens >= len(prefix)


def test_unaligned_prefix_copies_only_the_tail_page(model, monkeypatch):
    """A prefix ending mid-page shares its full pages by reference and
    row-copies exactly ONE page (the sub-page tail)."""
    monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
    prefix = list(range(1, 41))  # 40 tokens: 2 full pages + 8-row tail
    eng = _engine(model)
    assert eng._paged
    spy = CopySpy(eng)
    try:
        qa = eng.submit(GenRequest(prompt_ids=prefix + [60, 61],
                                   max_tokens=24, ignore_eos=True))
        _first_token(qa)
        qb = eng.submit(GenRequest(prompt_ids=prefix + [70, 71],
                                   max_tokens=8, ignore_eos=True))
        _, ev_b = _drain(qb)
        _drain(qa)
    finally:
        eng.close()
    assert ev_b.finish_reason == "length", ev_b.error
    copies = [p for k, p in spy.records if k == "kvcopy"]
    assert len(copies) == 1, copies
    assert copies[0]["n"] == 16  # one whole-page tail copy


def test_engine_churn_no_page_leaks(model, monkeypatch):
    """Waves beyond slot capacity + mid-stream cancels + slot reuse:
    the pool's invariants hold afterwards and dropping the idle
    residents returns every page to the free list."""
    monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
    spec, params, tk = model
    eng = _engine(model)
    assert eng._paged
    rng = np.random.default_rng(2)
    try:
        for wave in range(3):
            n = eng.n_slots + 2  # force queueing + slot reuse/eviction
            reqs = [GenRequest(
                prompt_ids=[int(x) for x in rng.integers(
                    1, 200, int(rng.integers(4, 60)))],
                max_tokens=int(rng.integers(2, 12)),
                ignore_eos=True) for _ in range(n)]
            qs = eng.submit_many(reqs)
            eng.cancel(reqs[0].id)  # cancel one immediately
            for q in qs[1:]:
                _drain(q)
            _drain(qs[0])  # the cancelled one must also terminate
        # settle, then check structural invariants on the idle engine
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


def test_paged_dispatch_payloads_stay_replayable(model, monkeypatch):
    """Multihost-replay invariant: every dispatch a paged engine emits
    — including the page-table payloads — must survive the broadcast
    codec bit-exactly (scalars + ndarrays only; allocator state never
    crosses)."""
    monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
    from localai_tfp_tpu.parallel import multihost

    prefix = list(range(1, 33))
    eng = _engine(model)
    assert eng._paged
    spy = CopySpy(eng)
    try:
        qa = eng.submit(GenRequest(prompt_ids=prefix + [40],
                                   max_tokens=16, ignore_eos=True))
        _first_token(qa)
        qb = eng.submit(GenRequest(prompt_ids=prefix + [50, 51, 52, 53,
                                                        54, 55, 56, 57],
                                   max_tokens=8, ignore_eos=True))
        _drain(qb)
        _drain(qa)
    finally:
        eng.close()
    assert {"mixed", "decodek"} <= {k for k, _ in spy.records}
    paged_kinds = set()
    for kind, payload in spy.records:
        if "pt" in payload:
            paged_kinds.add(kind)
            assert payload["pt"].dtype == np.int32
            assert payload["wb"].dtype == np.int32
        hdr, buf = multihost.encode_record(kind, payload)
        kind2, out = multihost.decode_record(int(hdr[0]), buf)
        assert kind2 == kind
        assert set(out) == set(payload)

        def same(a, b):
            if isinstance(a, dict):
                return (isinstance(b, dict) and set(a) == set(b)
                        and all(same(v, b[k]) for k, v in a.items()))
            if a is None or isinstance(a, (bool, str)):
                return a == b
            return np.array_equal(np.asarray(a), np.asarray(b))

        for key, val in payload.items():
            assert same(val, out[key]), key
    assert paged_kinds, "no paged dispatch carried a page table"


def test_paged_on_off_byte_identity(model, monkeypatch):
    """LOCALAI_PAGED_KV=off restores the dense cache with byte-identical
    streams — greedy and seeded sampling, shared-prefix traffic."""
    spec, params, tk = model
    prompts = [
        list(range(1, 33)) + [40 + i] for i in range(3)
    ] + [[9, 8, 7, 6, 5]]
    texts = {}
    for mode in ("on", "off"):
        monkeypatch.setenv("LOCALAI_PAGED_KV", mode)
        monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
        eng = _engine(model)
        assert eng._paged == (mode == "on")
        try:
            qs = eng.submit_many(
                [GenRequest(prompt_ids=ids, max_tokens=12,
                            temperature=0.8, top_k=40, seed=7,
                            ignore_eos=True) for ids in prompts]
                + [GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=12,
                              ignore_eos=True)])
            outs = []
            for q in qs:
                toks, ev = _drain(q)
                assert ev.finish_reason == "length", ev.error
                outs.append(toks)
            texts[mode] = outs
        finally:
            eng.close()
    assert texts["on"] == texts["off"]


def test_pool_pressure_reclaims_idle_residents(model, monkeypatch):
    """An arena sized below worst case serves more slots than the dense
    layout by reclaiming FREE slots' resident prefixes under pressure —
    admission never fails while reclaimable pages exist."""
    monkeypatch.setenv("LOCALAI_KV_PAGE", "16")
    # 13 data pages = 208 tokens of arena for 4 slots x 256 max_seq
    # (dense equivalent: 0.8 slots!)
    eng = _engine(model, kv_pages=14)
    assert eng._paged
    rng = np.random.default_rng(3)
    try:
        for wave in range(4):
            reqs = [GenRequest(
                prompt_ids=[int(x) for x in rng.integers(1, 200, 40)],
                max_tokens=6, ignore_eos=True) for _ in range(4)]
            for q in eng.submit_many(reqs):
                _, ev = _drain(q)
                assert ev.finish_reason == "length", ev.error
        eng._pool.leak_check()
        assert eng._pool.allocs["fresh"] > 0
    finally:
        eng.close()
