"""``afmoe`` (arcee Trinity) on the normal path, at a tiny size on the
CPU: seeded weights with a NONZERO selection bias, written by the
benchmark's checkpoint writer and read back through models/hf_loader.py.

- the spec and the two parameter stacks (leading dense layers, expert
  layers) are what the config says;
- the step programs' forward (decode rows beside a prompt chunk through
  the ragged route, then decoding through the paged cache, contexts past
  the tiny window and across pages) gives the plain reference's logits
  (benchmark/models/afmoe.py), and every ``mutate`` of the reference is
  caught by the tolerance that comparison passes;
- the engine serves it through its scheduler, step programs, paged pool
  and the ragged kernel, with no kernel ineligibility, and its greedy
  tokens are the reference's;
- the routed expert dispatch equals the dense all-experts form it
  replaced, for every expert type the spec knows;
- the expert and context counters count what they say.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark.lib import checkpoint, models, reference
from localai_tfp_tpu.models.llm_spec import spec_from_hf_config, tiny_spec
from localai_tfp_tpu.models.transformer import (
    DENSE_STACK, EXPERT_LEAVES, KVCache, Rows, _act, _lm_head, _moe_mlp,
    _route, forward, forward_rows, forward_train, init_params, layer_stacks,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(ROOT, "benchmark", "models")

TINY = {
    "architectures": ["AfmoeForCausalLM"], "model_type": "afmoe",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "num_hidden_layers": 4, "num_dense_layers": 1, "vocab_size": 259,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "sliding_window": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "mup_enabled": True, "rope_theta": 10000, "rope_scaling": None,
    "rms_norm_eps": 1e-05, "max_position_embeddings": 4096,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
}
TOL = 0.01  # what the float32 system passes by four orders and every
# mutation of the reference fails


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    before = models.use(MODELS)
    d = str(tmp_path_factory.mktemp("afmoe"))
    checkpoint.write_hf_checkpoint(d, TINY, seed=3, threads=2)
    from localai_tfp_tpu.models.hf_loader import load_params

    spec, params = load_params(d, dtype=jnp.float32)
    yield d, spec, params
    models.use(before)


def test_spec_is_what_the_config_says():
    spec = spec_from_hf_config(TINY)
    assert (spec.n_experts, spec.experts_per_token, spec.moe_d_ff) == (8, 2, 32)
    assert spec.n_dense_layers == 1 and spec.moe_dense_layers == ()
    assert spec.moe_score_func == "sigmoid" and spec.moe_select_bias
    assert spec.moe_route_scale == 2.826 and spec.moe_norm_topk
    assert spec.moe_shared_expert and not spec.moe_shared_gated
    assert spec.moe_shared_d_ff == 32
    assert spec.attn_output_gate and spec.rope_sliding_only
    assert spec.qk_norm and spec.sandwich_norms
    assert spec.embedding_multiplier == 8.0  # sqrt(64): mup_enabled
    assert spec.sliding_window == 16 and spec.layer_types[3] == "full_attention"
    assert spec.extra["model_type"] == "afmoe"
    no_mup = spec_from_hf_config(dict(TINY, mup_enabled=False))
    assert no_mup.embedding_multiplier == 1.0


@pytest.mark.parametrize("key", ["n_group", "topk_group"])
def test_grouped_selection_reaches_the_spec(key):
    """Refused until PR 45 (``_route`` had no group-limited pick); now
    the spec carries the groups and ``_route`` honours them
    (tests/test_deepseek_v3.py holds the pick to the reference)."""
    spec = spec_from_hf_config(dict(TINY, **{key: 2}))
    assert (spec.moe_n_group, spec.moe_topk_group) == (
        (2, 1) if key == "n_group" else (1, 2))


def test_loader_builds_two_stacks(tiny):
    _, spec, params = tiny
    L, Ld, E = 4, 1, 8
    assert params["wq"].shape == (L - Ld, 64, 256)
    assert params[DENSE_STACK + "wq"].shape == (Ld, 64, 256)
    assert params["w_attn_gate"].shape == (L - Ld, 64, 256)
    assert params[DENSE_STACK + "w_up"].shape == (Ld, 64, 128)
    assert "w_up" not in params and DENSE_STACK + "router" not in params
    assert params["router"].shape == (L - Ld, 64, E)
    assert params["moe_gate"].shape == (L - Ld, E, 64, 32)
    assert params["moe_down"].shape == (L - Ld, E, 32, 64)
    assert params["shared_up"].shape == (L - Ld, 64, 32)
    assert "shared_router" not in params  # no gate of its own
    # the selection bias: float32 whatever the dtype, drawn nonzero
    assert params["router_bias"].shape == (L - Ld, E)
    assert params["router_bias"].dtype == jnp.float32
    assert float(jnp.min(jnp.abs(params["router_bias"]))) > 0.01
    for k in ("ln1_w", "ln_post_attn_w", "ln2_w", "ln_post_ffw_w",
              "q_norm_w", "k_norm_w"):
        assert params[k].shape[0] == L - Ld
        assert params[DENSE_STACK + k].shape[0] == Ld
    stacks = layer_stacks(spec, params)
    assert [(s.first, s.n) for s in stacks] == [(0, 1), (1, 3)]
    assert stacks[0][2]["_window"].tolist() == [16]
    assert sorted(stacks[1][3]) == ["moe_down", "moe_gate", "moe_up"]
    assert stacks[1][2]["_window"].tolist() == [16, 16, 0]
    assert stacks[1][2]["_rope_on"].tolist() == [1, 1, 0]


def test_init_params_has_the_loaders_tree(tiny):
    _, spec, params = tiny
    made = init_params(jax.random.PRNGKey(0), spec, jnp.float32)
    assert {k: v.shape for k, v in made.items()} == {
        k: v.shape for k, v in params.items()}
    assert made["router_bias"].dtype == jnp.float32
    # a model without leading dense layers draws what it always drew
    plain = tiny_spec(n_experts=4)
    a = init_params(jax.random.PRNGKey(5), plain, jnp.float32)
    assert not any(k.startswith(DENSE_STACK) for k in a)
    assert a["moe_gate"].shape[0] == plain.n_layers


def _reference_logits(ckpt, ids, mutate=None):
    sh = reference.Shards(ckpt)
    hidden = models.of(TINY).forward_hidden(sh, TINY, [list(ids)], mutate)[0]
    return hidden @ sh.get("lm_head.weight").T


S, PAGE, MAXP, CH = 4, 8, 8, 8
T_PROMPT, T_DEC = 40, 8


def _through_the_step_programs(spec, params, ids, others, kv="f32"):
    """Row 0's prompt in chunks of CH beside rows 1.. decoding, then
    every row decoding with row 0 fed ``ids``: the forward the engine's
    step programs run (forward_rows through the ragged route, the
    kernel interpreted), logits before the sampler -> ([T, V] of row 0,
    expert statistics per step)."""
    shape = (spec.n_layers, S * MAXP + 1, PAGE, spec.kv_dim)
    if kv == "int8":
        cache = KVCache(k=jnp.zeros(shape, jnp.int8),
                        v=jnp.zeros(shape, jnp.int8),
                        k_scale=jnp.zeros(shape[:3], jnp.float32),
                        v_scale=jnp.zeros(shape[:3], jnp.float32))
    else:
        cache = KVCache(k=jnp.zeros(shape, jnp.float32),
                        v=jnp.zeros(shape, jnp.float32))
    table = (1 + np.arange(S)[:, None] * MAXP
             + np.arange(MAXP)[None]).astype(np.int32)
    tab = jnp.asarray(table)
    parked = table.copy()
    parked[0] = 0  # a parked row writes the trash page
    ones = jnp.ones((S,), jnp.int32)

    @jax.jit
    def mixed(cache, dtoks, dpos, live, ptoks, ppos):
        dg = Rows(dtoks, dpos, page_table=tab, write_table=jnp.asarray(parked),
                  q_lens=ones, live=live)
        pg = Rows(ptoks, ppos, page_table=tab[:1], write_table=tab[:1],
                  q_lens=jnp.full((1,), CH, jnp.int32))
        (_, ph), cache, ex = forward_rows(spec, params, (dg, pg), cache,
                                          kv_page=PAGE)
        return _lm_head(spec, params, ph)[0], cache, ex

    @jax.jit
    def decode(cache, dtoks, dpos):
        dg = Rows(dtoks, dpos, page_table=tab, write_table=tab, q_lens=ones,
                  live=jnp.ones((S,), bool))
        (dh,), cache, ex = forward_rows(spec, params, (dg,), cache,
                                        kv_page=PAGE)
        return _lm_head(spec, params, dh)[:1, 0], cache, ex

    logits, stats, step = [], [], 0
    live = np.ones((S,), bool)
    live[0] = False
    for c in range(T_PROMPT // CH):
        lg, cache, ex = mixed(
            cache, jnp.asarray(others[:, step][:, None]),
            jnp.full((S,), step, jnp.int32), jnp.asarray(live),
            jnp.asarray(ids[None, c * CH:(c + 1) * CH]),
            jnp.asarray([c * CH], jnp.int32))
        logits.append(np.asarray(lg))
        stats.append(np.asarray(ex))
        step += 1
    for t in range(T_DEC):
        dtoks = others[:, step][:, None].copy()
        dtoks[0, 0] = ids[T_PROMPT + t]
        dpos = np.full((S,), step, np.int32)
        dpos[0] = T_PROMPT + t
        lg, cache, ex = decode(cache, jnp.asarray(dtoks), jnp.asarray(dpos))
        logits.append(np.asarray(lg))
        stats.append(np.asarray(ex))
        step += 1
    return np.concatenate(logits), np.stack(stats)


@pytest.fixture(scope="module")
def sequences():
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 257, T_PROMPT + T_DEC).astype(np.int32)
    others = rng.integers(0, 257, (S, T_PROMPT // CH + T_DEC + 2)).astype(
        np.int32)
    return ids, others


def _rel(got, want):
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


@pytest.mark.parametrize("kv,tol", [("f32", 2e-5), ("int8", 0.05)])
def test_step_programs_match_the_reference_past_the_window(
        tiny, sequences, kv, tol):
    """Prefill in chunks beside decoding rows, then decode through the
    paged cache: every position's logits against the reference's full
    pass. The context (48) is three windows long and six pages."""
    ckpt, spec, params = tiny
    ids, others = sequences
    got, stats = _through_the_step_programs(spec, params, ids, others, kv)
    want = _reference_logits(ckpt, ids)
    assert got.shape == want.shape == (T_PROMPT + T_DEC, 259)
    # int8 rows move a near-tie of the router now and then (one
    # position in 48 here, 0.28): the median position is held
    rel = _rel(got, want)
    assert (rel.max() if kv == "f32" else np.median(rel)) < tol
    if kv == "f32":
        # the same row computes the same thing whatever rides beside it
        assert (got.argmax(-1) == want.argmax(-1)).all()
        # a decode step routes S rows x k over 3 expert layers; a mixed
        # step the S - 1 live rows + the chunk; the parked row nowhere
        E, K = 8, 2
        assert stats[-1, :E].sum() == S * K * 3
        assert stats[0, :E].sum() == (S - 1 + CH) * K * 3
        assert (stats[:, E] <= 3 * E).all() and (stats[:, E] >= 3 * K).all()


MUTATIONS = [{"zero_layer": 1}, {"zero_layer": 0}, {"rope_on_full": True},
             {"drop_bias": True}, {"bias_in_weight": True},
             {"drop_gate": True}, {"window": 4096}]


@pytest.mark.parametrize("mutate", MUTATIONS, ids=lambda m: next(iter(m)))
def test_the_tolerance_catches_each_mutation(tiny, sequences, mutate):
    """What the system is held to is tight enough to tell each way the
    model file can be broken: the selection bias dropped or leaking
    into the weight, rotary on a full layer, the output gate missing,
    a window that is not honoured, a layer of either stack gone."""
    ckpt, spec, params = tiny
    ids, _ = sequences
    want = _reference_logits(ckpt, ids)
    broken = _reference_logits(ckpt, ids, mutate)
    assert np.sqrt(((broken - want) ** 2).sum() / (want ** 2).sum()) > TOL
    logits, _ = forward(
        spec, params, jnp.asarray(ids[None]), jnp.zeros((1,), jnp.int32),
        KVCache.create(spec, 1, 64, jnp.float32), jnp.zeros((1,), jnp.int32))
    got = np.asarray(logits[0])
    assert np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()) < TOL / 100
    assert np.sqrt(((got - broken) ** 2).sum() / (broken ** 2).sum()) > TOL


def test_training_forward_is_the_serving_forward(tiny, sequences):
    _, spec, params = tiny
    ids, _ = sequences
    toks = jnp.asarray(ids[None, :32])
    served, _ = forward(spec, params, toks, jnp.zeros((1,), jnp.int32),
                        KVCache.create(spec, 1, 32, jnp.float32),
                        jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(np.asarray(forward_train(spec, params, toks)),
                               np.asarray(served), rtol=2e-4, atol=2e-4)
    # and it has a gradient in both stacks
    g = jax.grad(lambda p: forward_train(spec, p, toks).sum())(params)
    assert float(jnp.abs(g[DENSE_STACK + "w_up"]).sum()) > 0
    assert float(jnp.abs(g["moe_down"]).sum()) > 0


def test_quantization_reaches_the_dense_stack(tiny):
    from localai_tfp_tpu.models.quant import QTensor, quantize_params

    _, _, params = tiny
    q = quantize_params(params)
    for k in ("wq", "w_attn_gate", DENSE_STACK + "wo", DENSE_STACK + "w_down"):
        assert isinstance(q[k], QTensor), k
    for k in ("moe_gate", "shared_up", "router", "router_bias"):
        assert not isinstance(q[k], QTensor), k


# --------------------------------- routed dispatch == the dense form


def _dense_moe(spec, lp, x):
    """The all-experts form ``_moe_mlp`` had before PR 38: every expert
    evaluated for every token, combined under the top-k weights."""
    E = spec.n_experts
    B, T, D = x.shape
    idx, w = _route(spec, lp, x.reshape(B * T, D))
    gate = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                   * w[..., None], axis=-2).reshape(B, T, E)
    g = jnp.einsum("btd,edf->btef", x, lp["moe_gate"])
    u = jnp.einsum("btd,edf->btef", x, lp["moe_up"])
    y = jnp.einsum("btef,efd->bted", _act(spec, g) * u, lp["moe_down"])
    out = jnp.einsum("bted,bte->btd", y, gate.astype(y.dtype))
    if "shared_gate" in lp:
        s = (_act(spec, x @ lp["shared_gate"]) * (x @ lp["shared_up"])) \
            @ lp["shared_down"]
        sg = 1.0
        if "shared_router" in lp:
            sg = jax.nn.sigmoid(jnp.einsum(
                "btd,d->bt", x, lp["shared_router"]))[..., None]
        out = out + s * sg
    return out


EXPERT_SPECS = {
    "mixtral": dict(n_experts=4, experts_per_token=2),
    "qwen2_moe": dict(n_experts=6, experts_per_token=3, moe_d_ff=48,
                      moe_shared_expert=True, moe_shared_d_ff=96,
                      moe_norm_topk=False, qkv_bias=True),
    "qwen3_moe": dict(n_experts=8, experts_per_token=4, moe_d_ff=48,
                      qk_norm=True),
    "afmoe": dict(n_experts=8, experts_per_token=2, moe_d_ff=32,
                  moe_shared_expert=True, moe_shared_d_ff=32,
                  moe_shared_gated=False, moe_score_func="sigmoid",
                  moe_select_bias=True, moe_route_scale=2.826),
}


@pytest.mark.parametrize("masked", [False, True], ids=["all", "some_parked"])
@pytest.mark.parametrize("family", sorted(EXPERT_SPECS))
def test_routed_dispatch_equals_the_dense_form(family, masked):
    spec = tiny_spec(**EXPERT_SPECS[family])
    params = init_params(jax.random.PRNGKey(7), spec, jnp.float32)
    lp = {k: v[1] for k, v in params.items()
          if k.startswith(("router", "moe_", "shared_"))}
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 5, spec.d_model))
    valid = None
    if masked:
        valid = jnp.asarray(np.random.default_rng(0).random((3, 5)) < 0.6)
    whole = {k: params[k] for k in EXPERT_LEAVES}  # layer 1 of the stack
    got, counts = jax.jit(
        lambda lp, x: _moe_mlp(spec, lp, x, valid, (whole, 1)))(lp, x)
    want = _dense_moe(spec, lp, x)
    keep = np.ones((3, 5), bool) if valid is None else np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(want)[keep],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    # each real token is counted at each of its k experts, no other
    idx, _ = _route(spec, lp, x.reshape(15, -1))
    want_counts = np.bincount(np.asarray(idx)[keep.reshape(15)].ravel(),
                              minlength=spec.n_experts)
    assert np.asarray(counts).tolist() == want_counts.tolist()
    assert counts.sum() == keep.sum() * spec.experts_per_token


def test_selection_bias_chooses_and_never_weighs():
    spec = tiny_spec(**EXPERT_SPECS["afmoe"])
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((6, spec.d_model)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((spec.d_model, 8)) * 0.1,
                         jnp.float32)
    bias = jnp.asarray([5.0, 0, 0, 0, 0, 0, 0, -5.0], jnp.float32)
    idx, w = _route(spec, {"router": router, "router_bias": bias}, x)
    idx0, w0 = _route(spec, {"router": router}, x)
    assert (np.asarray(idx) == 0).any(axis=1).all()  # pushed in
    assert not (np.asarray(idx) == 7).any()  # pushed out
    s = np.asarray(jax.nn.sigmoid(x @ router))
    picked = np.take_along_axis(s, np.asarray(idx), axis=1)
    np.testing.assert_allclose(
        np.asarray(w), 2.826 * picked / picked.sum(1, keepdims=True),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.826, rtol=1e-5)
    assert not np.array_equal(np.asarray(idx0), np.asarray(idx))


# ------------------------------------------------ the engine serves it


def _serve(monkeypatch, tiny, cache_dtype=jnp.float32, **kw):
    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer

    _, spec, params = tiny
    monkeypatch.setenv("LOCALAI_DECODE_KERNEL", "1")  # interpreted here
    monkeypatch.setenv("LOCALAI_KV_PAGE", "8")
    tk = ByteTokenizer()
    assert tk.vocab_size <= spec.vocab_size
    kw.setdefault("n_slots", 4)
    eng = LLMEngine(spec, params, tk, max_seq=64, prefill_buckets=(8, 32),
                    cache_dtype=cache_dtype, autostart=False, **kw)
    eng._prefix_enabled = False
    return eng


def _value(family, **labels):
    from localai_tfp_tpu.telemetry.registry import REGISTRY

    total = 0.0
    for ln in REGISTRY.render().splitlines():
        if not ln.startswith(family + "{"):
            continue
        if all(f'{k}="{v}"' in ln for k, v in labels.items()):
            total += float(ln.rsplit(" ", 1)[1])
    return total


def test_engine_serves_afmoe_on_the_kernel_route(monkeypatch, tiny):
    """Through the scheduler, the mixed step, the k-step scans, the
    paged pool and the (interpreted) ragged kernel: no ineligibility,
    and the greedy tokens are the reference's argmax along the way —
    contexts past the window, across pages."""
    from localai_tfp_tpu.engine.engine import GenRequest

    ckpt, spec, _ = tiny
    eng = _serve(monkeypatch, tiny, tag="afmoe-serve")
    try:
        assert eng.kernel_ineligible == ""
        assert eng.attention_path == "ragged_paged_kernel"
        # the grouped-matmul kernel is the chip's: lax.ragged_dot here
        assert eng.expert_path == "ragged_dot"
        assert eng._layer_windows == {0: 1, 16: 3}
        assert eng._n_expert_layers == 3
        prompt = [int(t) for t in np.random.default_rng(4).integers(
            0, 257, 21)]
        req = GenRequest(prompt_ids=prompt, max_tokens=12, temperature=0,
                         ignore_eos=True)
        done = {}
        finish = eng._finish

        def spy(slot, reason):  # events coalesce tokens; the slot has all
            if slot.request is not None:
                done[slot.request.id] = list(slot.generated)
            return finish(slot, reason)

        eng._finish = spy
        eng.submit(req)
        for _ in range(5000):
            if req.id in done:
                break
            eng.step()
        toks = done[req.id]
        assert len(toks) == 12
        want = _reference_logits(ckpt, prompt + toks)
        assert want[len(prompt) - 1:-1].argmax(-1).tolist() == toks
        # every routed token was counted: rows x k a layer-step
        m = "afmoe-serve"
        steps = _value("engine_expert_layer_steps_total", model=m)
        assert steps > 0 and steps % 3 == 0
        tokens = _value("engine_expert_tokens_total", model=m)
        # (a k-step scan in the air when the reply ends still ran: the
        # count is of the positions dispatched, as the token counter's)
        real = _value("engine_dispatch_tokens_total", model=m, part="real")
        assert real >= len(prompt) + len(toks) - 1
        assert tokens == real * 2 * 3
        touched = _value("engine_experts_touched_total", model=m)
        assert 2 * steps <= touched <= min(8 * steps, tokens)
        # past the window the attention had less to read than was held
        read = _value("engine_attn_context_tokens_total", model=m)
        held = _value("engine_attn_context_held_tokens_total", model=m)
        assert held >= real * (real - 1) // 2
        assert 0.5 * held < read < 0.9 * held
    finally:
        eng.close()


def test_counters_of_a_uniform_model_read_what_they_hold(monkeypatch):
    """No windows, no experts: read == held, and no expert counter."""
    from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer

    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size)
    params = init_params(jax.random.PRNGKey(1), spec, jnp.float32)
    eng = LLMEngine(spec, params, tk, n_slots=2, max_seq=64,
                    prefill_buckets=(8, 32), cache_dtype=jnp.float32,
                    autostart=True, tag="uniform-ctx")
    try:
        q = eng.submit(GenRequest(prompt_ids=tk.encode("hello there"),
                                  max_tokens=20, ignore_eos=True))
        while not q.get(timeout=120).done:
            pass
        m = "uniform-ctx"
        read = _value("engine_attn_context_tokens_total", model=m)
        assert read > 0
        assert read == _value("engine_attn_context_held_tokens_total",
                              model=m)
        assert _value("engine_expert_tokens_total", model=m) == 0
        assert eng._layer_windows == {0: spec.n_layers}
    finally:
        eng.close()


@pytest.mark.parametrize("rows,want", [
    ([(0, 5)], (10, 10)),  # a chunk from nothing: its causal sum
    ([(30, 1)], ((3 * 16 + 30) / 4, 30)),  # a decode row past the window
    ([(10, 1)], (10, 10)),  # and inside it
    ([(12, 8)], ((3 * (12 + 13 + 14 + 15 + 4 * 16) + 124) / 4, 124)),
    ([(3, 2), (40, 3)], (7 + (3 * 48 + 123) / 4, 7 + 123)),
])
def test_context_read_is_the_mean_over_layers(monkeypatch, tiny, rows, want):
    eng = _serve(monkeypatch, tiny, tag="afmoe-ctx")
    try:
        read, held = eng._context_tokens(rows)
        assert (read, held) == (pytest.approx(want[0]), want[1])
    finally:
        eng.close()


def test_embeddings_of_a_long_prompt_go_through_in_chunks(monkeypatch, tiny):
    """Past the last prefill bucket the embeddings path takes whole
    chunks on its throwaway cache; what it returns is the one-pass
    value (the benchmark's parity prompt of 2.4k tokens rides this)."""
    ckpt, spec, _ = tiny
    eng = _serve(monkeypatch, tiny, tag="afmoe-embed")
    try:
        monkeypatch.setattr(type(eng), "_EMBED_CHUNK", 16)
        text = "".join(chr(97 + i % 26) for i in range(44))  # > bucket 32
        ids = eng.tokenizer.encode(text, add_bos=True)
        assert 32 < len(ids) <= 48
        got = eng.embed(text)
        sh = reference.Shards(ckpt)
        want = models.of(TINY).forward_hidden(sh, TINY, [ids])[0].mean(0)
        assert reference.rel_l2(got, want) < 2e-4
    finally:
        eng.close()


def test_config_file_of_the_cell_is_this_model():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini-pp4-stage.json")) as f:
        config = json.load(f)
    spec = spec_from_hf_config(checkpoint.hf_config(config))
    assert (spec.n_layers, spec.n_dense_layers, spec.n_experts) == (8, 2, 128)
    assert spec.layer_types == ("sliding_attention",) * 3 + (
        "full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)
    assert spec.sliding_window == 2048 and spec.kv_dim == 512
    assert dataclasses.replace(spec).moe_route_scale == 2.826


def test_every_expert_keeps_a_series_of_its_own():
    """The registry folds label sets past a family's cap into "other"
    (64 by default): the per-expert counter's cap holds a model's every
    expert, or max ÷ mean over experts reads the fold, not the load."""
    from localai_tfp_tpu.telemetry import metrics as tm

    for e in range(512):
        tm.ENGINE_EXPERT_TOKENS.labels(model="cap-test", expert=str(e)).inc()
    from localai_tfp_tpu.telemetry.registry import REGISTRY

    lines = [ln for ln in REGISTRY.render().splitlines()
             if ln.startswith("engine_expert_tokens_total{")
             and 'model="cap-test"' in ln]
    assert len(lines) == 512 and not any('"other"' in ln for ln in lines)
