"""engine/cache_route.py: the ONE place that decides how a dispatch
program reaches the KV cache.

- the route an engine picks follows from what it observes (mesh axes,
  page geometry, platform and shape eligibility), never from a knob of
  its own;
- ``close(open(cache))`` returns the cache bit for bit on every route
  (the open/close pair adds nothing of its own to a program);
- on the host, the pool routes plan every dispatch at full width while
  the dense route walks its power-of-two ladder and prefers a window it
  already compiled;
- the forward's ``select`` (models/cache_attention.py), handed a route's
  own ``forward_kw``, picks the function of that route's name: the two
  places that know the decision agree.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.config import knobs
from localai_tfp_tpu.engine.cache_route import choose_route
from localai_tfp_tpu.engine.engine import LLMEngine, _rows_kw
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models import cache_attention as ca
from localai_tfp_tpu.models.transformer import KVCache, Rows, init_params
from localai_tfp_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def model():
    tk = ByteTokenizer()
    # kernel-eligible shapes (kv_dim % 128 == 0) so forcing the kernel
    # is the only thing between the gather route and the ragged one
    spec = tiny_spec(vocab_size=tk.vocab_size, n_heads=4, n_kv_heads=2,
                     d_head=64)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    return spec, params, tk


@pytest.mark.parametrize("case,env,mesh_shape,max_seq,want", [
    ("unmeshed_cpu", {}, None, 512, "paged_xla_gather"),
    ("kernel_forced", {"LOCALAI_DECODE_KERNEL": "1"}, None, 512,
     "ragged_paged_kernel"),
    ("model_mesh", {}, {"data": 1, "seq": 1, "model": 2}, 512,
     "paged_xla_gather"),
    ("seq_mesh", {}, {"data": 1, "seq": 2, "model": 1}, 512, "dense_xla"),
    # 100 = 4 * 25: the largest power-of-two page is 4 (< 8)
    ("page_under_8", {}, None, 100, "dense_xla"),
    ("dense_kernel_forced",
     {"LOCALAI_DECODE_KERNEL": "1", "LOCALAI_PAGED_KV": "off"}, None, 512,
     "dense_decode_kernel"),
])
def test_route_follows_what_the_engine_observes(
        model, monkeypatch, case, env, mesh_shape, max_seq, want):
    # the knob that used to select a fourth route is gone (spelled in
    # two halves: nothing outside the records may name it)
    assert "LOCALAI_RAGGED" + "_ATTN" not in knobs.REGISTRY
    spec, params, tk = model
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    mesh = None
    if mesh_shape is not None:
        n = int(np.prod(list(mesh_shape.values())))
        mesh = make_mesh(mesh_shape, devices=jax.devices("cpu")[:n])
    eng = LLMEngine(spec, params, tk, n_slots=2, max_seq=max_seq,
                    prefill_buckets=(8, 32), cache_dtype=jnp.float32,
                    mesh=mesh, autostart=False)
    try:
        assert eng.attention_path == eng._route.name == want
        assert eng._paged == want.startswith(("paged", "ragged"))
    finally:
        eng.close()


def _filled(cache: KVCache, seed: int) -> KVCache:
    rng = np.random.default_rng(seed)

    def fill(a):
        if a is None:
            return None
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 127, a.shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    return KVCache(k=fill(cache.k), v=fill(cache.v),
                   k_scale=fill(cache.k_scale), v_scale=fill(cache.v_scale))


@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("name", ["ragged_paged_kernel",
                                  "paged_xla_gather", "dense_xla"])
def test_close_of_open_is_the_cache_bit_for_bit(name, dtype):
    spec = tiny_spec()
    n_slots, max_seq, page = 2, 512, 64
    paged = name != "dense_xla"
    route = choose_route(paged=paged, kernel=name.startswith("ragged"),
                         max_seq=max_seq, page=page, mesh=None)
    assert route.name == name
    cdt = jnp.float32 if dtype == "float32" else "int8"
    if paged:
        wp = max_seq // page
        cache = _filled(KVCache.create(spec, n_slots * wp + 1, page, cdt), 1)
        # every non-trash page belongs to exactly one (row, logical page)
        phys = 1 + np.random.default_rng(2).permutation(
            n_slots * wp).reshape(n_slots, wp).astype(np.int32)
        tables = (jnp.asarray(phys), jnp.asarray(phys))
    else:
        cache = _filled(KVCache.create(spec, n_slots, max_seq, cdt), 1)
        tables = ()

    @jax.jit
    def roundtrip(cache, *tables):
        view = route.open(cache, tables, 256)
        return view, route.close(cache, view, tables)

    view, out = roundtrip(cache, *tables)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(cache)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # what the forward runs on: the arena itself, the rows' pages as one
    # dense [L, B, max_seq, F] view, or the window's leading positions
    L, F = cache.k.shape[0], cache.k.shape[-1]
    want = {"ragged_paged_kernel": cache.k.shape,
            "paged_xla_gather": (L, n_slots, max_seq, F),
            "dense_xla": (L, n_slots, 256, F)}[name]
    assert view.k.shape == want


@pytest.mark.parametrize("kind,need,compiled,dense,dense_kernel", [
    # nothing compiled covers the bucket: a decode scan compiles its
    # bucket, the prompt kinds fall back to the always-warmed max_seq
    ("decode", 300, (), 512, 2048),
    ("decode", 300, (256, 1024, 2048), 1024, 2048),
    ("mixed", 300, (), 2048, 2048),
    ("mixed", 300, (1024, 2048), 1024, 1024),
    ("mixed", 10, (256, 2048), 256, 256),
    ("mixed", 5000, (256,), 2048, 2048),
    # chunk prefills are warmed along the whole ladder: the bucket
    ("prefill", 600, (2048,), 1024, 1024),
])
def test_host_side_window_choice(kind, need, compiled, dense, dense_kernel):
    kw = dict(max_seq=2048, page=256, mesh=None)
    for kernel in (False, True):
        pool = choose_route(paged=True, kernel=kernel, **kw)
        assert pool.window(need, kind, compiled) == 2048
        assert pool.ladder(kind, need) == [2048]
    for kernel, want in ((False, dense), (True, dense_kernel)):
        route = choose_route(paged=False, kernel=kernel, **kw)
        assert route.window(need, kind, iter(compiled)) == want
        rungs = route.ladder(kind, need)
        assert rungs[-1] == 2048 and rungs == sorted(set(rungs))
        # whatever window() picks with nothing compiled is a rung
        assert route.window(need, kind) in rungs


@pytest.mark.parametrize("name,latent,want,stacked", [
    ("ragged_paged_kernel", False, ca.ragged, True),
    ("latent_paged_kernel", True, ca.latent_ragged, True),
    ("paged_xla_gather", False, ca.xla, False),
    ("paged_xla_gather", True, ca.latent_xla, False),
    ("dense_decode_kernel", False, ca.dense_kernel, True),
    ("dense_xla", False, ca.xla, False),
    ("dense_xla", True, ca.latent_xla, False),
])
def test_select_picks_the_function_of_the_routes_name(
        name, latent, want, stacked):
    """A decode step's rows as the engine builds them (``_rows_kw`` of
    the route's ``forward_kw``) select the route's own function."""
    route = choose_route(
        paged=name.startswith(("paged", "ragged", "latent")),
        kernel=name.endswith("_kernel"), max_seq=512, page=64, mesh=None,
        latent=latent)
    assert route.name == name
    spec = tiny_spec(kv_lora_rank=128) if latent else tiny_spec()
    S = 2
    tables = ((jnp.zeros((S, 8), jnp.int32),) * 2
              if hasattr(route, "page") else ())
    tokens, pos0 = jnp.zeros((S, 1), jnp.int32), jnp.zeros((S,), jnp.int32)
    per, kw = _rows_kw(route.forward_kw(
        tables, jnp.ones((S,), jnp.int32), decode=True))
    rows = Rows(tokens, pos0, **per)
    assert ca.select(spec, (rows,), kw.get("decode_kernel", False)) \
        == (want, stacked)
    # a mixed step's two groups never take the one-token kernel of the
    # dense cache: its prompt rows reach the cache through slot ids
    if name == "dense_decode_kernel":
        per2, _ = _rows_kw(route.forward_kw(
            tables, jnp.ones((S,), jnp.int32), slot_ids=pos0))
        assert ca.select(spec, (rows, Rows(tokens, pos0, **per2)),
                         True) == (ca.xla, False)
        assert ca.select(spec, (Rows(tokens, pos0, **per2),),
                         True) == (ca.xla, False)
