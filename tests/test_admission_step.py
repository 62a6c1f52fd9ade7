"""The admission step's variant set is CLOSED, and the decode programs
beside it did not move.

- every (rows, bucket, window) key step() can build for any wave is in
  warmup()'s enumeration — one function (_mixed_variants over
  _row_ladder) yields both;
- at the benchmark cell's geometry (16 slots x 4096, default buckets,
  the pool) warmup compiles no more variants than the parent's 24;
- ``decodek`` / ``decode1`` lower to the SAME StableHLO as at the
  parent commit 35555e3 (PR 36), so ``decode_step_dev_ms`` cannot move
  by construction. Run as a script under conftest.py's environment
  (exec it first, then runpy this file as __main__), it prints the
  table for whatever tree is on PYTHONPATH: that is how the parent's
  column was recorded.
"""

import hashlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.engine.engine import LLMEngine
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import init_params


def _model():
    tk = ByteTokenizer()
    # kernel-eligible shapes (kv_dim % 128 == 0): every route is one
    # knob away
    spec = tiny_spec(vocab_size=tk.vocab_size, n_heads=4, n_kv_heads=2,
                     d_head=64)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    return spec, params, tk


@pytest.fixture(scope="module")
def model():
    return _model()


# ------------------------------------------------------- the closed set

_GEOMETRIES = {
    # the benchmark cell: 16 slots x 4096, default buckets, the pool
    "cell": (dict(n_slots=16, max_seq=4096), {}),
    # a dense cache has a window ladder beside the row ladder
    "dense": (dict(n_slots=4, max_seq=1024, prefill_buckets=(8, 32, 128)),
              {"LOCALAI_PAGED_KV": "off"}),
    # a slot count that is no power of two; a budget under a bucket
    "odd": (dict(n_slots=6, max_seq=512, prefill_buckets=(8, 64, 256)),
            {"LOCALAI_PREFILL_GROUP_TOKENS": "600"}),
}


@pytest.fixture(params=list(_GEOMETRIES))
def planned(request, model, monkeypatch):
    """An engine whose dispatch layer is stubbed (the assertions are
    about the variant PLAN), and the (kind, key) records of its
    warmup()."""
    spec, params, tk = model
    kw, env = _GEOMETRIES[request.param]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    eng = LLMEngine(spec, params, tk, cache_dtype=jnp.float32,
                    autostart=False, **kw)
    plan = []
    eng._run = lambda kind, p: plan.append(
        (kind, p["toks"].shape + (p["window"],) if kind == "mixed"
         else None))
    eng.warmup()
    yield request.param, eng, plan
    eng.close()


def test_every_shape_a_wave_can_ask_for_is_warmed(planned):
    """The property over the ladder: whatever the wave (row count,
    remainders), whatever rows decode and wherever they stand, the
    step's (rows, bucket, window) is one warmup() compiled."""
    name, eng, plan = planned
    warmed = {key for kind, key in plan if kind == "mixed"}
    assert warmed == set(eng._mixed_variants())
    big = eng._step_buckets[-1]
    rems = sorted({1, 2, 7, 8, 9, 100, big - 1, big, big + 1, 3 * big}
                  | {b + d for b in eng.prefill_buckets for d in (0, 1)})
    rng = np.random.default_rng(0)
    waves = [[r] * n for r in rems for n in range(1, eng.n_slots + 1)]
    waves += [list(rng.choice(rems, size=n))
              for n in range(2, eng.n_slots + 1) for _ in range(8)]
    for wave in waves:
        rows, bucket = eng._mixed_shape([int(r) for r in wave])
        assert rows * bucket <= max(eng._prefill_group_tokens, bucket)
        assert rows >= min(len(wave), eng._row_ladder(bucket)[-1])
        for at in (0, 200, eng.max_seq - big - 2):
            # the window the route picks for rows standing at ``at``
            need = at + min(max(wave), bucket) + 1
            w = eng._route.window(need, "mixed", ())
            assert (rows, bucket, w) in warmed, (wave, at)


def test_no_more_variants_than_the_parent(planned):
    """24 at the cell's geometry on the parent (PERF §6 PR 36): the row
    ladder is paid for by the prefill_final variants that went."""
    name, eng, plan = planned
    assert eng.warmup_variants == len(plan)
    kinds = {kind for kind, _ in plan}
    assert kinds <= {"mixed", "decodek", "decode1", "kvcopy"}
    if name == "cell":
        assert len(plan) <= 24, len(plan)
        assert eng._step_buckets == (4, 16, 128)
        assert [eng._row_ladder(b) for b in eng._step_buckets] == [
            (16,), (8, 16), (1, 2, 4, 8, 16)]


@pytest.mark.parametrize("rems,want", [
    ([300] * 15, (16, 128)),            # the cell's wave: three steps
    ([300] * 14 + [600], (16, 128)),    # every row rides every step
    ([600], (1, 128)),                  # how a prompt chunks hangs on
    ([600, 40, 3], (4, 128)),           # its own length alone
    ([100] * 3, (4, 128)),              # rows round up the ladder
    ([10] * 2, (8, 16)),                # under a rung: rows merge
    ([3], (16, 4)),
    ([2000] * 16, (16, 128)),
    ([5000], (1, 128)),
])
def test_every_row_rides_every_step_whatever_its_bucket(model, rems, want):
    spec, params, tk = model
    eng = LLMEngine(spec, params, tk, n_slots=16, max_seq=4096,
                    cache_dtype=jnp.float32, autostart=False)
    try:
        assert eng._mixed_shape(rems) == want
    finally:
        eng.close()


def _expert_engine(**kw):
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, n_heads=4, n_kv_heads=2,
                     d_head=64, n_experts=4, experts_per_token=2)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    return LLMEngine(spec, params, tk, cache_dtype=jnp.float32,
                     autostart=False, **kw)


@pytest.mark.parametrize("rems,want", [
    ([2300], (1, 512)),                 # five steps, not eighteen
    ([2300, 2100], (1, 512)),           # one long prompt a step: the
    ([600, 40, 3], (1, 512)),           # next one rides after it
    ([100] * 3, (4, 128)),              # a step is 512 tokens' worth
    ([100], (4, 128)),
    ([10] * 2, (16, 16)),
    ([3000] * 16, (1, 512)),
])
def test_an_expert_models_step_takes_four_times_the_chunk(rems, want):
    """A grouped matmul reads the experts that have tokens whatever the
    rows (nearly all of them from a chunk of 128 on), so an expert
    model's chunk is 512 tokens and a long prompt holds the decoding
    rows for a third of the steps; that chunk is all the prompt tokens
    the step takes, in one row or in several; the warmed set follows."""
    eng = _expert_engine(n_slots=16, max_seq=4096)
    try:
        assert eng._step_buckets[-1] == 512
        assert eng._mixed_shape(rems) == want
        shapes = {(r, b) for r, b, _ in eng._mixed_variants()}
        assert want in shapes
        assert all(r * b <= 512 for r, b in shapes)
    finally:
        eng.close()


def test_an_expert_models_prompts_are_admitted_one_after_the_other():
    """Three long prompts land at once beside a row that decodes: no
    step carries more than one chunk's worth of prompt tokens, no two
    of them get their first token in one step (rows that start together
    end together, and replies of equal length keep them together), and
    each reply is what the prompt gets alone."""
    from localai_tfp_tpu.engine.engine import GenRequest

    eng = _expert_engine(n_slots=4, max_seq=2048)
    eng._prefix_enabled = False
    rng = np.random.default_rng(11)

    def req(n, out):
        return GenRequest(
            prompt_ids=[int(t) for t in rng.integers(1, 200, n)],
            max_tokens=out, temperature=0, ignore_eos=True)

    done, steps = {}, []
    finish, run = eng._finish, eng._run

    def spy_finish(slot, reason):
        if slot.request is not None:
            done[slot.request.id] = list(slot.generated)
        return finish(slot, reason)

    def spy_run(kind, p):
        if kind == "mixed":
            steps.append((p["toks"].shape, int(p["final"].sum()),
                          int(p["active"].sum())))
        return run(kind, p)

    eng._finish, eng._run = spy_finish, spy_run

    def serve(reqs):
        for r in reqs:
            eng.submit(r)
        for _ in range(20000):
            if all(r.id in done for r in reqs):
                break
            eng.step()
        return [done[r.id] for r in reqs]

    try:
        first = req(40, 64)
        eng.submit(first)
        while not any(s.generated for s in eng.slots):
            eng.step()
        steps.clear()
        wave = [req(n, 6) for n in (700, 1100, 530)]
        together = serve(wave)
        assert all(r * b <= 512 for (r, b), *_ in steps)
        assert max(f for _, f, _ in steps) == 1
        assert sum(f for _, f, _ in steps) == 3
        # the row that decoded rode every one of those steps
        assert len(steps) == 2 + 3 + 2 and all(a for _, _, a in steps)
        assert first.id not in done
        for r, got in zip(wave, together):
            alone = serve([GenRequest(
                prompt_ids=r.prompt_ids, max_tokens=6, temperature=0,
                ignore_eos=True)])[0]
            assert got == alone
    finally:
        eng.close()


# ------------------------------------- the decode programs did not move

_DECODE_ROUTES = {
    "paged_xla_gather": {},
    "ragged_paged_kernel": {"LOCALAI_DECODE_KERNEL": "1"},
    "dense_xla": {"LOCALAI_PAGED_KV": "off"},
    "dense_decode_kernel": {"LOCALAI_PAGED_KV": "off",
                            "LOCALAI_DECODE_KERNEL": "1"},
}

# sha256[:8] of the sorted sha256s of each program's lowering
# (fn.lower(*args).as_text()) — shapes of tests/test_ragged_attention.py
# (4 slots x 512, decode_steps 8). Pinned at 35555e3 by PR 37 (the
# decode programs did not change with the admission step) and RE-PINNED
# by PR 38, which changed every one of them on purpose: a step program
# returns one more value (the expert statistics, empty for this dense
# model), the layer scan runs over ``layer_stacks`` (one stack here),
# and the two kernel routes hand the kernel the layer's window as an
# operand (0 here). PR 39 re-pinned the two KERNEL routes only (the
# ragged kernel's body changed on purpose: its page walk crosses grid
# steps, its heads run phase by phase, and a parked row is given
# length 0); the two XLA routes kept PR 38's digests. PR 44 re-pinned
# ALL of them on purpose: every program's layers pass q, k, v through
# an ``optimization_barrier`` before the split into heads
# (models/transformer.py ``_layer_body``; values bit-equal:
# tests/test_projection_barrier.py), and the ragged int8 route gathers
# its scale planes over (layer, page) at once. PR 52 re-pinned the
# ragged int8 route ALONE, for one line that moved: the table scatter
# is ``ops/ragged_paged_attention.append_rows`` for every caller, so
# the ``stablehlo.iota`` of its row index is emitted after the rows'
# quantisation and not before it — the same ops (the multiset of the
# text's lines is the parent's) and the compiled step programs
# byte-equal (``tools/step_hlo.py --all``; CHANGES, PR 52); the other
# seven digests are PR 44's. What the pin is for is unchanged: a
# later PR that touches none of that must leave these programs as
# they are.
_PARENT = {
    ("paged_xla_gather", "float32"): ("55b97305", "3xb25c9ebd"),
    ("paged_xla_gather", "int8"): ("c8552eaa", "3x9dc4f1e3"),
    ("ragged_paged_kernel", "float32"): ("de80d6e6", "3x4095641c"),
    ("ragged_paged_kernel", "int8"): ("01f1e95d", "3x39bf83aa"),
    ("dense_xla", "float32"): ("f373a359", "6x217c2668"),
    ("dense_xla", "int8"): ("33d1bb98", "6xd5bcc7d0"),
    ("dense_decode_kernel", "float32"): ("176f53ac", "3x44c047f9"),
    ("dense_decode_kernel", "int8"): ("8dbd84df", "3x4a3a4572"),
}


def _decode_lowerings(eng):
    """(decode1 digest, decodek digest, decodek count) of one engine."""
    S = eng.n_slots

    def tabs(window):
        if not eng._paged:
            return ()
        wp = window // eng._page
        return (jnp.zeros((S, wp), jnp.int32),) * 2

    def text(fn, *args):
        return hashlib.sha256(
            fn.lower(*args).as_text().encode()).hexdigest()

    def digest(hashes):
        return hashlib.sha256(
            "".join(sorted(hashes)).encode()).hexdigest()[:8]

    toks = jnp.zeros((S, 1), jnp.int32)
    pos = jnp.zeros((S,), jnp.int32)
    act = jnp.zeros((S,), bool)
    # cache and sampler state by shape alone, as the parent's engine
    # built them (uncommitted: a committed argument's placement is
    # written into the text)
    cache, samp = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (eng.cache, eng.sampling))
    d1 = text(eng._decode_fn, eng.params, toks, cache, pos,
              eng._all_slot_ids, samp, act, None, *tabs(eng.max_seq))
    dk = [text(eng._decode_k_fn(k, w), eng.params, toks, cache, pos,
               eng._all_slot_ids, samp, act, *tabs(w))
          for k in sorted(eng._warm_ks) if k > 1
          for w in eng._route.ladder("decode")]
    return digest([d1]), digest(dk), len(dk)


def _engine_for(model, route, dtype, monkeypatch):
    spec, params, tk = model
    for k, v in _DECODE_ROUTES[route].items():
        monkeypatch.setenv(k, v)
    eng = LLMEngine(spec, params, tk, n_slots=4, max_seq=512,
                    prefill_buckets=(8, 32, 128),
                    cache_dtype=jnp.dtype(dtype), autostart=False)
    assert eng.attention_path == route
    return eng


@pytest.mark.parametrize("route,dtype", list(_PARENT))
def test_decode_programs_lower_as_at_the_parent(
        model, monkeypatch, route, dtype):
    eng = _engine_for(model, route, dtype, monkeypatch)
    try:
        d1, dk, n = _decode_lowerings(eng)
    finally:
        eng.close()
    assert (d1, f"{n}x{dk}") == _PARENT[(route, dtype)]


if __name__ == "__main__":
    import tests.conftest  # noqa: F401  the suite's JAX settings: the
    # default matmul precision is written into a lowering's text
    mp = pytest.MonkeyPatch()
    m = _model()
    for route, dtype in itertools.product(
            _DECODE_ROUTES, ("float32", "int8")):
        e = _engine_for(m, route, dtype, mp)
        d1, dk, n = _decode_lowerings(e)
        e.close()
        mp.undo()
        print(f'    ("{route}", "{dtype}"): ("{d1}", "{n}x{dk}"),')


# --------------------------------------------- one pass, two row groups

@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8])
def test_two_groups_in_one_pass_are_two_passes(model, cache_dtype):
    """forward_rows over a decode group [S, 1] (identity rows, a parked
    row masked) and a prompt group [R, T] (slot_ids, one pad row at the
    sentinel) is, row for row, forward_hidden over the one and then the
    other: the same hidden states and the same cache."""
    from localai_tfp_tpu.models.transformer import (
        KVCache, Rows, forward_hidden, forward_rows,
    )

    spec, params, _ = model
    S, R, T = 4, 2, 8
    rng = np.random.default_rng(3)
    cache = KVCache.create(spec, S, 64, cache_dtype)
    # rows 0 and 2 hold a prefix and decode; row 1 takes the prompt
    warm = jnp.asarray(rng.integers(1, 200, (S, 16)), jnp.int32)
    _, cache = forward_hidden(spec, params, warm,
                              jnp.zeros((S,), jnp.int32), cache, None)
    dec = Rows(jnp.asarray(rng.integers(1, 200, (S, 1)), jnp.int32),
               jnp.asarray([16, 0, 16, 16], jnp.int32),
               write_mask=jnp.asarray([True, False, True, False]))
    pro = Rows(jnp.asarray(rng.integers(1, 200, (R, T)), jnp.int32),
               jnp.asarray([0, 0], jnp.int32),
               slot_ids=jnp.asarray([1, S], jnp.int32))
    (hd, hp), fused, _ = forward_rows(spec, params, (dec, pro), cache)
    want_d, two = forward_hidden(spec, params, dec.tokens, dec.pos0,
                                 cache, None, write_mask=dec.write_mask)
    want_p, two = forward_hidden(spec, params, pro.tokens, pro.pos0, two,
                                 pro.slot_ids)
    assert hd.shape == (S, 1, spec.d_model) and hp.shape[:2] == (R, T)
    np.testing.assert_allclose(hd, want_d, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(hp[0], want_p[0], rtol=2e-5, atol=2e-5)
    for got, want in zip(jax.tree.leaves(fused), jax.tree.leaves(two)):
        if got.dtype == jnp.int8:
            assert np.abs(np.asarray(got, np.int32)
                          - np.asarray(want, np.int32)).max() <= 1
        else:
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
