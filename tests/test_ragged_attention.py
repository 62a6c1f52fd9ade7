"""Ragged paged attention serving paths (engine + ops/
ragged_paged_attention.py): every dispatch kind — decode scans, prefill
chunks, prefill finals, mixed steps — rides FULL-width page tables
through one unified path, collapsing the bucket x window jit-variant
ladder to one variant per token-budget shape.

Invariants enforced here:
- an identical request schedule produces BYTE-IDENTICAL outputs on
  the pool and on the dense cache (the reference, windowed along its
  ladder), seeded sampling included — full-width page tables are a
  dispatch-shape change, not a math change;
- ragged dispatches really are full-width (page tables span
  max_seq // page entries for every kind) and the
  engine_ragged_rows_total counter attributes rows by kind;
- grammar constraints and logit-bias bans flow through ragged rows;
- zero-copy shared pages and COW privatization read correctly through
  ragged dispatches (byte-identical to an unshared engine);
- payloads stay scalar-only (multihost followers replay ragged
  dispatches like any other record).
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
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=1024)
    params = init_params(jax.random.PRNGKey(2), spec, dtype=jnp.float32)
    return spec, params, tk


def _engine(model, prefix=False, **kw):
    spec, params, tk = model
    kw.setdefault("n_slots", 4)
    # max_seq ABOVE the window floor (256): the dense reference
    # genuinely windows its dispatches at 256 while the pool pins full
    # width, so the comparison exercises different dispatch shapes —
    # not two identical programs
    kw.setdefault("max_seq", 512)
    kw.setdefault("prefill_buckets", (8, 32, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("autostart", True)
    eng = LLMEngine(spec, params, tk, **kw)
    # prefix reuse is timing-dependent (which donor is resident when a
    # request admits varies with scheduling interleave); the dedicated
    # shared-page test below controls it explicitly
    eng._prefix_enabled = prefix
    return eng


class DispatchSpy:
    """Record every dispatch's kind and paged-table geometry, and
    enforce the multihost replay invariant inline: payload leaves must
    be plain host data (numpy / python scalars), never device arrays —
    followers replay every ragged dispatch like any other record."""

    def __init__(self, eng):
        self.eng = eng
        self.records = []
        self._orig = eng._run
        eng._run = self._run

    @staticmethod
    def _leaves(x):
        if isinstance(x, dict):
            for v in x.values():
                yield from DispatchSpy._leaves(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                yield from DispatchSpy._leaves(v)
        else:
            yield x

    def _run(self, kind, payload):
        rec = {"kind": kind}
        if isinstance(payload, dict) and "pt" in payload:
            rec["pt_pages"] = payload["pt"].shape[1]
            rec["wb_pages"] = payload["wb"].shape[1]
        for leaf in self._leaves(payload):
            assert not isinstance(leaf, jax.Array), (
                f"device array in {kind} payload — not replayable")
        self.records.append(rec)
        return self._orig(kind, payload)


class FinishSpy:
    """Exact generated token ids per request at _finish time (stream
    events coalesce text spans per harvest)."""

    def __init__(self, eng):
        self.generated = {}
        self._orig = eng._finish
        eng._finish = self._finish

    def _finish(self, slot, reason):
        if slot.request is not None:
            self.generated[slot.request.id] = list(slot.generated)
        return self._orig(slot, reason)


def _drain(q, timeout=180):
    while True:
        ev = q.get(timeout=timeout)
        if ev.done:
            return ev


def _first_token(q, timeout=180):
    while True:
        ev = q.get(timeout=timeout)
        assert not ev.done, f"finished early: {ev.finish_reason} {ev.error}"
        if ev.token_id is not None:
            return ev


def _schedule(eng, tk):
    """Fixed mixed-traffic schedule: two seeded sampled streams decode,
    a burst of three admissions (one prompt long enough to need
    non-final chunks) lands mid-stream. Returns {name: (token ids,
    final event)}."""
    fin = FinishSpy(eng)
    reqs, out = {}, {}
    ra = GenRequest(prompt_ids=tk.encode("ragged stream alpha"),
                    max_tokens=24, temperature=0.9, top_k=12, seed=7,
                    ignore_eos=True)
    rb = GenRequest(prompt_ids=tk.encode("beta stays live too"),
                    max_tokens=24, temperature=0.7, top_p=0.9, seed=11,
                    ignore_eos=True)
    qa, qb = eng.submit(ra), eng.submit(rb)
    reqs["a"], reqs["b"] = ra, rb
    _first_token(qa)
    _first_token(qb)
    burst = [
        GenRequest(prompt_ids=tk.encode("one burst request " * 9),
                   max_tokens=6, temperature=0.8, seed=3,
                   ignore_eos=True),
        GenRequest(prompt_ids=tk.encode("two burst request"),
                   max_tokens=6, ignore_eos=True),
        # longer than the largest bucket (128): non-final chunk rows
        GenRequest(prompt_ids=tk.encode("three burst request " * 10),
                   max_tokens=6, temperature=0.6, seed=5,
                   ignore_eos=True),
    ]
    qs = eng.submit_many(burst)
    for name, r, q in zip(("c", "d", "e"), burst, qs):
        reqs[name] = r
        out[name] = _drain(q)
    out["a"] = _drain(qa)
    out["b"] = _drain(qb)
    return {n: (fin.generated[reqs[n].id], out[n]) for n in out}


def test_ragged_on_off_byte_identical(model, monkeypatch):
    """The pool's full-width dispatches stream the same bytes as the
    dense cache (greedy AND seeded sampling) even though the two
    dispatch different window shapes. The pool run also carries the
    dispatch-shape and row-counter assertions (full-width tables;
    engine_ragged_rows_total by kind)."""
    spec, params, tk = model
    monkeypatch.setenv("LOCALAI_PAGED_KV", "off")
    eng_off = _engine(model)
    monkeypatch.delenv("LOCALAI_PAGED_KV")
    assert not eng_off._paged
    try:
        spy_off = DispatchSpy(eng_off)
        want = _schedule(eng_off, tk)
    finally:
        eng_off.close()
    # the reference really ran windowed: nothing it dispatched carried
    # page tables
    assert spy_off.records
    assert not any("pt_pages" in r for r in spy_off.records)
    eng_on = _engine(model)
    assert eng_on._paged
    snap = REGISTRY.snapshot()
    try:
        spy = DispatchSpy(eng_on)
        got = _schedule(eng_on, tk)
        m = eng_on._mlabel
    finally:
        eng_on.close()
    # the ragged engine must actually have dispatched full-width tables
    full = eng_on.max_seq // eng_on._page
    paged = [r for r in spy.records if "pt_pages" in r]
    assert paged and all(r["pt_pages"] == full and r["wb_pages"] == full
                         for r in paged), paged
    for name in want:
        assert got[name][0] == want[name][0], f"stream {name} diverged"
        assert got[name][1].full_text == want[name][1].full_text
        assert got[name][1].finish_reason == want[name][1].finish_reason
    # engine_ragged_rows_total attributes rows by kind
    delta = REGISTRY.delta(snap)

    def cnt(kind):
        return delta.get(
            f'engine_ragged_rows_total{{model="{m}",kind="{kind}"}}',
            0.0)

    assert cnt("decode") > 0  # scans/mixed decode rows
    assert cnt("final") >= 5  # every request took one final chunk row
    assert cnt("prefill") >= 1  # the 200-token prompt's chunk rows


def test_grammar_and_logit_bias_through_ragged_rows(model):
    """Host-interactive slots (grammar constraint, logit-bias ban)
    drain correctly while another stream decodes through ragged
    dispatches."""
    from localai_tfp_tpu.grammars.native import make_constraint

    spec, params, tk = model
    prompt = tk.encode("tool call now")
    eng = _engine(model)
    try:
        # greedy continuation to ban below — generated on the SAME
        # engine (a second engine would recompile every dispatch fn)
        free = eng.generate(GenRequest(prompt_ids=prompt, max_tokens=12,
                                       ignore_eos=True))
        banned = free.full_text
        assert len(banned) >= 1
        fin = FinishSpy(eng)
        qa = eng.submit(GenRequest(
            prompt_ids=tk.encode("background stream"), max_tokens=40,
            ignore_eos=True))
        _first_token(qa)
        constraint = make_constraint('root ::= "ok"', tk)
        qg = eng.submit(GenRequest(prompt_ids=prompt, max_tokens=16,
                                   constraint=constraint))
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


def test_shared_and_cow_pages_read_through_ragged(model, monkeypatch):
    """Zero-copy prefix shares + COW privatization under ragged
    dispatches: a second request admitted onto a donor's shared pages
    must produce exactly the stream an unshared engine produces, and
    the pool must show real sharing happened (and stay leak-free)."""
    monkeypatch.setenv("LOCALAI_KV_PAGE", "64")  # page-granular sharing
    # at toy prompt lengths
    spec, params, tk = model
    shared = tk.encode("shared prefix body " * 8)  # > 2 pages of 64
    tail_a = tk.encode("then request A")
    tail_b = tk.encode("and request B instead")
    assert len(shared) >= 128

    def run(prefix_enabled):
        # A decodes while B admits: B lands on a DIFFERENT slot, so the
        # prefix cache serves it by zero-copy page shares from the
        # active donor (same-slot resident reuse would need no shares)
        eng = _engine(model, prefix=prefix_enabled)
        try:
            qa = eng.submit(GenRequest(
                prompt_ids=shared + tail_a, max_tokens=16,
                ignore_eos=True))
            _first_token(qa)
            shares0 = eng._pool.allocs["shared"]
            ev_b = _drain(eng.submit(GenRequest(
                prompt_ids=shared + tail_b, max_tokens=6,
                ignore_eos=True)))
            ev_a = _drain(qa)
            shares1 = eng._pool.allocs["shared"]
            cows = eng._pool.allocs["cow"]
            eng._pool.leak_check()
        finally:
            eng.close()
        assert ev_a.finish_reason == ev_b.finish_reason == "length", (
            ev_a.error, ev_b.error)
        return ev_a.full_text, ev_b.full_text, shares1 - shares0, cows

    a_ref, b_ref, shares_ref, _ = run(prefix_enabled=False)
    a_sh, b_sh, shares, cows = run(prefix_enabled=True)
    assert shares_ref == 0 and shares > 0  # B really read shared pages
    assert (a_sh, b_sh) == (a_ref, b_ref)  # byte-identical streams


# The multihost scalar-payload replay invariant is enforced inline by
# DispatchSpy on every dispatch of the byte-identity schedule above —
# decode scans, prefill chunks, finals, and mixed steps all pass
# through it, so a device array leaking into any ragged payload fails
# test_ragged_on_off_byte_identical directly.


# ----------------------------- the layer's window as the kernel's operand


@pytest.mark.parametrize("window,kind,cache,heads", [
    *((w, k, c, (4, 2)) for w in (0, 40) for k in ("decode", "mixed")
      for c in ("f32", "int8")),
    # 30 query heads on 30 kv heads (olmo_hybrid's full layers): a head
    # count that is no power of two, groups of one
    (0, "decode", "f32", (30, 30)), (0, "mixed", "f32", (30, 30)),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_window_operand_matches_the_reference(kind, cache, window, heads):
    """The sliding window rides into the kernel as a traced scalar (a
    layer scan's per-layer value; 0 = full attention): rows whose
    context crosses it — the boundary inside a 16-token page, whole
    pages below it skipped — read what ``ragged_attention_reference``
    reads, on raw and on int8 pages, whatever the head counts."""
    import dataclasses

    from localai_tfp_tpu.ops.kernel_check import SMALL, check_serving_rows

    geom = dataclasses.replace(SMALL, n_heads=heads[0], n_kv_heads=heads[1])
    err = check_serving_rows(geom, kind, cache, seed=3, window=window)
    assert err < (5e-2 if cache == "int8" else 2e-4), err


def test_window_zero_is_full_attention_and_a_window_is_not():
    """0 and None are the same program result; a window changes it for
    a row whose context is longer than the window."""
    import numpy as np

    from localai_tfp_tpu.ops.ragged_paged_attention import (
        ragged_paged_attention,
    )

    rng = np.random.default_rng(0)
    page, n_kv, dh, H = 16, 2, 128, 4
    ak = jnp.asarray(rng.standard_normal((1, 9, page, n_kv * dh)),
                     jnp.float32)
    av = jnp.asarray(rng.standard_normal((1, 9, page, n_kv * dh)),
                     jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, 4, H, dh)), jnp.float32)
    pt = jnp.arange(1, 9, dtype=jnp.int32)[None]

    def run(window):
        return ragged_paged_attention(
            q, ak, av, jnp.asarray(0, jnp.int32), pt,
            jnp.asarray([100], jnp.int32), jnp.asarray([4], jnp.int32),
            n_kv, scale=dh ** -0.5, page=page, window=window)

    full = run(None)
    np.testing.assert_array_equal(np.asarray(run(0)), np.asarray(full))
    np.testing.assert_array_equal(
        np.asarray(run(jnp.asarray(0, jnp.int32))), np.asarray(full))
    # a window wider than the context changes nothing, a narrow one does
    np.testing.assert_allclose(np.asarray(run(4096)), np.asarray(full),
                               rtol=1e-6, atol=1e-6)
    assert float(jnp.max(jnp.abs(run(24) - full))) > 1e-3


def test_per_layer_windows_take_the_kernel_route(model, monkeypatch):
    """A model whose layers differ in their windows is no longer ruled
    out of the kernel route (the window is the kernel's operand)."""
    import dataclasses

    spec, _, tk = model
    wspec = dataclasses.replace(
        spec, d_head=64, sliding_window=32,  # kv_dim 128: whole lanes
        layer_types=("sliding_attention", "full_attention"))
    params = init_params(jax.random.PRNGKey(2), wspec, dtype=jnp.float32)
    monkeypatch.setenv("LOCALAI_DECODE_KERNEL", "1")
    eng = LLMEngine(wspec, params, tk, n_slots=2, max_seq=128,
                    prefill_buckets=(8, 32), cache_dtype=jnp.float32,
                    autostart=False)
    try:
        assert eng.kernel_ineligible == ""
        assert eng.attention_path == "ragged_paged_kernel"
        assert eng._layer_windows == {0: 1, 32: 1}
    finally:
        eng.close()


# ------------------------- the page walk's hand-over from one row to the next


def _handover_case(name):
    """(kind, q_lens, pos0, window) of one batch at SMALL-like sizes
    (page 16, 8 pages a row): ``decode`` rows are seeded ``[B, 1]``,
    ``prompt`` rows ``[B, 48]`` walked in three query blocks of 16."""
    return {
        # a parked (length 0) row between two live rows
        "parked_between": ("decode", [1, 0, 1], [70, 33, 20], 0),
        "first_parked": ("decode", [0, 1, 1], [70, 33, 20], 0),
        "last_parked": ("decode", [1, 1, 0], [70, 33, 20], 0),
        "all_parked": ("decode", [0, 0, 0], [70, 33, 20], 0),
        "single_row": ("decode", [1], [70], 0),
        # a seeded row at position 0 reads no HBM page at all
        "pos0_zero_then_long": ("decode", [1, 1], [0, 127], 0),
        # a full-attention-length row, then one whose window moves its
        # first page past 0 (and a short one the window does not touch)
        "window_after_full": ("decode", [1, 1, 1], [15, 120, 9], 40),
        # several query blocks a row; the first row's last blocks lie
        # wholly beyond its q_len, the second row fills every block
        "prompt_blocks_beyond": ("prompt", [5, 48, 0, 20],
                                 [60, 30, 11, 0], 0),
        "prompt_window": ("prompt", [48, 3, 33], [64, 100, 0], 24),
    }[name]


@pytest.mark.parametrize("cache", ["bf16", "int8"])
@pytest.mark.parametrize("case", [
    "parked_between", "first_parked", "last_parked", "all_parked",
    "single_row", "pos0_zero_then_long", "window_after_full",
    "prompt_blocks_beyond", "prompt_window"])
def test_page_walk_hands_over_across_rows(case, cache, monkeypatch):
    """The kernel's page walk runs from the first grid step to the
    last: a step starts the first page of the next step THAT READS and
    that step waits for it. Whatever lies between two reading steps —
    a parked row, a seeded row at position 0, a query block beyond
    q_len — and wherever the next row's window puts its first page,
    every live query reads what ``ragged_attention_reference`` reads,
    and a parked row's output stays finite."""
    import numpy as np

    from localai_tfp_tpu.models.transformer import _quantize_rows
    from localai_tfp_tpu.ops import ragged_paged_attention as rpa

    kind, q_lens, pos0, window = _handover_case(case)
    page, n_kv, dh, H, max_pages = 16, 2, 128, 4, 8
    if kind == "prompt":
        # three query blocks a row at this width (one at the default)
        monkeypatch.setattr(rpa, "_ROWS_PER_STEP", 64)
    B = len(q_lens)
    T = 1 if kind == "decode" else 48
    F = n_kv * dh
    rng = np.random.default_rng(len(case))
    n_pages = B * max_pages + 1
    pt = rng.permutation(np.arange(1, n_pages)).reshape(
        B, max_pages).astype(np.int32)
    for b in range(B):  # unallocated entries point at the trash page
        pt[b, -(-(pos0[b] + max(q_lens[b], 1)) // page):] = 0
    arena = rng.standard_normal((2, 2, n_pages, page, F), np.float32) * 0.5
    act = jnp.bfloat16
    if cache == "int8":
        (ak, ks), (av, vs) = (_quantize_rows(jnp.asarray(a)) for a in arena)
    else:
        ak, av = (jnp.asarray(a, act) for a in arena)
        ks = vs = None
    q = jnp.asarray(rng.standard_normal((B, T, H, dh), np.float32) * 0.3,
                    act)
    seed_kv = None
    if kind == "decode":
        seed_kv = tuple(jnp.asarray(
            rng.standard_normal((B, F), np.float32) * 0.5, act)
            for _ in range(2))
    if kind == "prompt":
        assert rpa._q_tiling(T, H, H // n_kv)[0] == 16  # nq == 3
    kw = dict(scale=dh ** -0.5, page=page,
              window=jnp.asarray(window, jnp.int32), cache_k_scale=ks,
              cache_v_scale=vs, seed_kv=seed_kv)
    args = (q, ak, av, jnp.asarray(1, jnp.int32), jnp.asarray(pt),
            jnp.asarray(pos0, jnp.int32), jnp.asarray(q_lens, jnp.int32),
            n_kv)
    got = np.asarray(rpa.ragged_paged_attention(*args, **kw))
    want = np.asarray(rpa.ragged_attention_reference(*args, **kw))
    assert np.isfinite(got).all()
    tol = 5e-2 if cache == "int8" else 2e-2
    for b, n in enumerate(q_lens):
        if n:
            np.testing.assert_allclose(got[b, :n], want[b, :n],
                                       atol=tol, rtol=0)


def test_a_parked_row_reads_no_page_through_forward_rows():
    """``forward_rows`` hands the kernel length 0 for a row that is not
    live: what such a row computes no longer depends on the pages under
    the position it carries (with no ``live`` mask it does), and the
    live rows compute what they computed."""
    import numpy as np

    from localai_tfp_tpu.models.llm_spec import LLMSpec
    from localai_tfp_tpu.models.transformer import (
        KVCache, Rows, forward_rows,
    )

    page, B, max_pages = 16, 3, 4
    spec = LLMSpec(vocab_size=64, d_model=128, n_layers=2, n_heads=2,
                   n_kv_heads=1, d_head=128, d_ff=128, max_position=64)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    n_pages = B * max_pages + 1
    pt = jnp.asarray(np.arange(1, n_pages).reshape(B, max_pages),
                     jnp.int32)
    trash = jnp.zeros_like(pt)
    live = jnp.asarray([True, False, True])
    wt = jnp.where(live[:, None], pt, trash)  # parked rows write trash
    arena = rng.standard_normal(
        (2, spec.n_layers, n_pages, page, spec.kv_dim)).astype(np.float32)
    other = arena.copy()  # the same but under the parked row's pages
    other[:, :, np.asarray(pt[1])] += 1.0
    toks = jnp.asarray(rng.integers(0, 64, (B, 1)), jnp.int32)
    pos0 = jnp.asarray([20, 37, 9], jnp.int32)

    def run(a, live):
        rows = Rows(toks, pos0, page_table=pt, write_table=wt,
                    q_lens=jnp.ones((B,), jnp.int32), live=live)
        (h,), _, _ = forward_rows(
            spec, params, (rows,),
            KVCache(k=jnp.asarray(a[0]), v=jnp.asarray(a[1])),
            kv_page=page)
        return np.asarray(h)

    masked, masked_other = run(arena, live), run(other, live)
    np.testing.assert_array_equal(masked, masked_other)
    assert np.isfinite(masked).all()
    unmasked, unmasked_other = run(arena, None), run(other, None)
    assert np.abs(unmasked[1] - unmasked_other[1]).max() > 1e-3
    np.testing.assert_array_equal(masked[[0, 2]], unmasked[[0, 2]])


def test_sweep_fits_row_cost_from_kernel_times(monkeypatch):
    """``kernel_check --sweep`` (the kernel's stopwatch, chip only):
    given a device and the kernel's event times it reports µs a call, µs
    a row, the fit ``us_row = a + b * pages`` over the points with no
    parked row, and the live context's share of the HBM roof."""
    import types

    from localai_tfp_tpu.ops import kernel_check as kc

    dev = types.SimpleNamespace(platform="tpu", device_kind="cpu")
    monkeypatch.setattr(kc.jax, "devices", lambda *a: [dev])
    geom, calls, pages = kc.SMALL, 2, (1, 2, 4)
    per_point = [geom.n_slots * (0.5 + 2.0 * p) for p in pages] + [7.0]
    monkeypatch.setattr(
        kc, "_kernel_times_us",
        lambda _dir: [t for t in per_point for _ in range(calls)])
    res = kc.sweep_decode_kernel(geom, "int8", pages=pages, parked=(0, 1),
                                 parked_pages=2, calls=calls)
    assert (res["a_us"], res["b_us"]) == (0.5, 2.0)
    assert [(p["pages"], p["parked"]) for p in res["points"]] == [
        (1, 0), (2, 0), (4, 0), (2, 1)]
    assert res["points"][-1]["us_call"] == 7.0
    # 2 live rows x 24 tokens x K and V x 256 int8 bytes, at 50 GB/s
    assert res["points"][-1]["roof_share"] == round(
        2 * 24 * 2 * 256 / 50e9 / 7e-6, 4)
    assert res["page_pair_dma_us"] == round(2 * 16 * 256 / 50e9 * 1e6, 3)


# ------------------------------------- the kernel over a LATENT arena


@pytest.mark.parametrize("T", [1, 24], ids=["decode_rows", "prompt_chunk"])
def test_latent_arena_at_128_heads_of_576(T):
    """Absorbed latent attention at DeepSeek-V3's shape — 128 query
    heads against ONE cached row of 512 + 64 values in 640 lanes, the
    value the same page's first 512 lanes — interpreted: rows of
    different contexts across pages, a parked row, a chunk whose tail
    is padding; against the dense softmax over the same rows."""
    import numpy as np

    from localai_tfp_tpu.ops.ragged_paged_attention import (
        ragged_paged_attention,
    )

    H, row, lat, r, page, B = 128, 640, 576, 512, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(T), 3)
    n_pages = 1 + B * 4
    arena = jax.random.normal(ks[0], (2, n_pages, page, row), jnp.float32)
    arena = arena.at[..., lat:].set(0.0)  # the row's zero lanes
    q = jax.random.normal(ks[1], (B, T, H, row), jnp.float32) * 0.1
    q = q.at[..., lat:].set(0.0)
    table = 1 + jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4)
    pos0 = jnp.asarray([37, 0, 16, 5], jnp.int32)
    q_lens = jnp.asarray([T, T, 0, max(1, T - 3)], jnp.int32)  # row 2 parked
    scale = 0.11
    out = ragged_paged_attention(
        q, arena, None, jnp.int32(1), table, pos0, q_lens, 1, scale=scale,
        page=page, v_lanes=r)
    assert out.shape == (B, T, H * r)
    out = np.asarray(out).reshape(B, T, H, r)
    rows = np.asarray(arena[1][table]).reshape(B, 4 * page, row)
    for b in range(B):
        for t in range(int(q_lens[b])):
            n = int(pos0[b]) + t + 1
            s = np.asarray(q[b, t]) @ rows[b, :n].T * scale  # [H, n]
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ rows[b, :n, :r]
            np.testing.assert_allclose(out[b, t], want, rtol=2e-4,
                                       atol=2e-5)


def test_kernel_check_holds_the_expanded_flash_kernel():
    """``kernel_check``'s leg for a latent model's PROMPT rows
    (ops/latent_flash_attention.py), at the interpreter's size here and
    at DeepSeek-V3's published widths on the chip: within bf16 rounding
    of the XLA form, and a token's bits the same at two indices of two
    chunks — the leg fails a run on either."""
    from localai_tfp_tpu.ops import kernel_check as kc

    res = kc.check_latent_flash(kc._LATENT_SMALL)
    assert res["max_rel_err"] < kc._TOL_FP and res["rows_equal"], res
    assert kc.LATENT_WIDTHS["deepseek"] == (128, 128, 64, 128, 512, 256, 512)


@pytest.mark.parametrize("planes",
                         ["bf16_kv", "int8_kv_scales", "latent_row"])
def test_append_rows_lands_where_the_write_table_says(planes):
    """``append_rows`` — the ONE table scatter of the ragged routes
    (K/V rows, their scale planes, a latent arena's one plane): token t
    of row b lands on page ``write_table[b, (pos0[b] + t) // page]`` at
    offset ``(pos0[b] + t) % page`` of the given layer; positions
    beyond ``q_lens`` and pages the table does not grant (entry 0) land
    on the trash page 0; nothing else of the arena moves."""
    import numpy as np

    from localai_tfp_tpu.ops.ragged_paged_attention import append_rows

    L, NP, PAGE, F, B, T, layer = 2, 7, 4, 8, 3, 6, 1
    rng = np.random.default_rng(0)
    dt, with_scales = {"bf16_kv": (jnp.bfloat16, False),
                       "int8_kv_scales": (jnp.int8, True),
                       "latent_row": (jnp.bfloat16, False)}[planes]
    n_rows = 1 if planes == "latent_row" else 2
    shapes = [(L, NP, PAGE, F)] * n_rows + [(L, NP, PAGE)] * (
        2 if with_scales else 0)
    dtypes = [dt] * n_rows + [jnp.float32] * (2 if with_scales else 0)
    before = [jnp.asarray(rng.integers(-9, 9, s), d)
              for s, d in zip(shapes, dtypes)]
    values = [jnp.asarray(rng.integers(10, 99, (B, T, *s[3:])), d)
              for s, d in zip(shapes, dtypes)]
    # row 0 straddles granted pages 3 and 5; row 1 fills page 6 and runs
    # on into a page it was not granted (entry 0); row 2 is parked
    pos0 = jnp.asarray([2, 4, 9], jnp.int32)
    q_lens = jnp.asarray([5, 6, 0], jnp.int32)
    write_table = jnp.asarray([[3, 5, 0], [0, 6, 0], [0, 0, 4]], jnp.int32)
    after = jax.jit(append_rows, static_argnums=6)(
        tuple(before), tuple(values), jnp.int32(layer), write_table, pos0,
        q_lens, PAGE)
    assert len(after) == len(before)
    for old, new, val in zip(before, after, values):
        assert new.dtype == old.dtype and new.shape == old.shape
        want = np.array(old)
        touched_trash = False
        for b in range(B):
            for t in range(T):
                pos = int(pos0[b]) + t
                pg = int(write_table[b, pos // PAGE])
                if t >= int(q_lens[b]) or pg == 0:
                    touched_trash = True
                    continue  # the trash page: whatever lands, unread
                want[layer, pg, pos % PAGE] = np.asarray(val[b, t])
        assert touched_trash
        got = np.array(new)
        # every page but the trash page is exactly what the table says
        # (the other layer wholly untouched)
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
        np.testing.assert_array_equal(got[1 - layer],
                                      np.array(old)[1 - layer])
    # the granted positions: row 0's five tokens, row 1's first four
    k, was, new = np.array(after[0]), np.array(before[0]), values[0]
    np.testing.assert_array_equal(k[layer, 3, 2:], np.asarray(new[0, :2]))
    np.testing.assert_array_equal(k[layer, 5, :3], np.asarray(new[0, 2:5]))
    np.testing.assert_array_equal(k[layer, 5, 3], was[layer, 5, 3])
    np.testing.assert_array_equal(k[layer, 6], np.asarray(new[1, :4]))
    np.testing.assert_array_equal(k[layer, 4], was[layer, 4])
