"""``deepseek_v3`` on the normal path, at a tiny size on the CPU: seeded
weights with a NONZERO selection bias, written by the benchmark's
checkpoint writer in the checkpoint's own layout (rotary pairs
interleaved, experts named by PUBLISHED id) and read back through
models/hf_loader.py.

- the spec and the two parameter stacks are what the config says, the
  share included (a router over 16 published experts, 4 held from id 4);
- the absorbed form (the latent route's kernel, interpreted) equals the
  expanded form (the XLA route) position by position;
- the step programs' forward (decode rows beside a prompt chunk through
  the paged latent cache, then decoding) gives the plain reference's
  LOGITS — both copies of it: numpy from the shards
  (benchmark/models/deepseek_v3.py) and jax.numpy on the program's tree
  (tools/mla_parity.py) — and every ``mutate`` and every lower-precision
  row is caught by the tolerance that comparison passes;
- group-limited routing is the reference's, a tie across groups too;
- THE SHARE TEST: 8 experts in 4 shares of 2 — the shares' routed parts
  plus the shared expert counted once are the uncut layer's output;
- rows in other slots and a parked row do not move a row's bits;
- a prompt row long enough that up-projecting its context once costs
  less than absorbing W_kvb into every query (``latent_prompt_form``,
  from the widths alone) attends in the EXPANDED form inside the flash
  kernel (ops/latent_flash_attention.py, interpreted): equal to the XLA
  form at every position, a token's bits the same wherever its chunk
  started, and the step programs' logits the reference's;
- the engine serves it through its scheduler, step programs, paged pool
  and the latent kernel route, reuses latent pages through the prefix
  index, embeds long prompts in chunks, counts what it says, and
  refuses by name what was not taught the row.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import checkpoint, models, reference
from localai_tfp_tpu.models import transformer as tr
from localai_tfp_tpu.models.llm_spec import spec_from_hf_config, tiny_spec
from tools.mla_parity import reference_logits, rows_rounded

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(ROOT, "benchmark", "models")

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0}
TINY = {
    "architectures": ["DeepseekV3ForCausalLM"], "model_type": "deepseek_v3",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 259,
    "q_lora_rank": 48, "kv_lora_rank": 128, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "v_head_dim": 16,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "experts_first": 4, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "moe_layer_freq": 1,
    "num_nextn_predict_layers": 0, "rope_theta": 10000,
    "rope_scaling": YARN, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 4096, "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}
TOL = 0.005  # what the float32 system passes by three orders and every
# mutation and every lower-precision row fails


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    before = models.use(MODELS)
    d = str(tmp_path_factory.mktemp("dsv3"))
    checkpoint.write_hf_checkpoint(d, TINY, seed=3, threads=2)
    from localai_tfp_tpu.models.hf_loader import load_params

    spec, params = load_params(d, dtype=jnp.float32)
    yield d, spec, params
    models.use(before)


def _numpy_logits(ckpt, ids, mutate=None):
    sh = reference.Shards(ckpt)
    hidden = models.of(TINY).forward_hidden(sh, TINY, [list(ids)], mutate)[0]
    return hidden @ sh.get("lm_head.weight").T


def _rel(got, want):
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


# ------------------------------------------------------- spec and loader


def test_spec_is_what_the_config_says():
    spec = spec_from_hf_config(TINY)
    assert (spec.kv_lora_rank, spec.q_lora_rank, spec.qk_nope_dim,
            spec.qk_rope_dim, spec.v_head_dim) == (128, 48, 16, 16, 16)
    assert (spec.n_kv_heads, spec.d_head, spec.rotary_dim) == (1, 32, 16)
    assert (spec.latent_width, spec.latent_row, spec.kv_dim) == (
        144, 256, 256)
    assert spec.q_dim == 4 * 32 and spec.o_dim == 4 * 16
    # the share: the router's width stays the published count
    assert (spec.n_experts, spec.n_held, spec.experts_first) == (16, 4, 4)
    assert (spec.moe_n_group, spec.moe_topk_group) == (4, 2)
    assert (spec.moe_score_func, spec.moe_select_bias, spec.moe_norm_topk,
            spec.moe_route_scale, spec.n_dense_layers) == (
        "sigmoid", True, True, 2.5, 1)
    # mscale^2 in the softmax scale, 1.0 on cos / sin
    m = 0.1 * np.log(40.0) + 1.0
    assert spec.attn_scale_mult == pytest.approx(m * m)
    assert tr.rope_attn_scale(spec) == 1.0
    assert tr.latent_scale(spec) == pytest.approx(m * m / np.sqrt(32.0))
    # all held when the repo's own keys are absent
    whole = {k: v for k, v in TINY.items()
             if k not in ("n_routed_experts_published", "experts_first")}
    sw = spec_from_hf_config(whole)
    assert (sw.n_experts, sw.experts_held, sw.n_held) == (4, 0, 4)


def test_published_widths_give_the_issues_row():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v3-ep16-share.json")) as f:
        spec = spec_from_hf_config(checkpoint.hf_config(json.load(f)))
    assert (spec.latent_width, spec.latent_row) == (576, 640)
    assert (spec.n_heads, spec.d_head, spec.v_head_dim) == (128, 192, 128)
    assert (spec.n_experts, spec.n_held, spec.experts_first) == (256, 16, 0)
    assert tr.latent_scale(spec) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40.0) + 1.0) ** 2)


@pytest.mark.parametrize("block,want", [
    # both given: their ratio (deepseek: equal, 1.0)
    ({"type": "yarn", "factor": 40, "mscale": 1.0, "mscale_all_dim": 1.0},
     1.0),
    ({"type": "yarn", "factor": 40, "mscale": 0.707, "mscale_all_dim": 1.0},
     (0.1 * 0.707 * np.log(40) + 1) / (0.1 * np.log(40) + 1)),
    # neither: 0.1 ln(factor) + 1, as before this repair
    ({"type": "yarn", "factor": 8.0}, 0.1 * np.log(8.0) + 1.0),
    ({"type": "yarn", "factor": 8.0, "attention_factor": 1.25}, 1.25),
    ({"rope_type": "llama3", "factor": 8.0}, 1.0),
], ids=["both_equal", "both_differ", "neither", "own_factor", "not_yarn"])
def test_yarn_cos_sin_factor(block, want):
    assert tr.rope_attn_scale(tiny_spec(rope_scaling=block)) == \
        pytest.approx(want)


def test_afmoe_with_groups_is_no_longer_refused():
    cfg = {"model_type": "afmoe", "hidden_size": 64, "num_experts": 8,
           "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2}
    spec = spec_from_hf_config(cfg)
    assert (spec.moe_n_group, spec.moe_topk_group) == (4, 2)


def test_loader_builds_two_stacks_of_latent_layers(tiny):
    _, spec, params = tiny
    assert params["wq_a"].shape == (2, 64, 48)
    assert params["wkv_a"].shape == (2, 64, 144)
    assert params["wkv_b_k"].shape == (2, 4, 16, 128)
    assert params["wkv_b_v"].shape == (2, 4, 128, 16)
    assert params["wo"].shape == (2, 64, 64)
    assert params["router"].shape == (2, 64, 16)  # published width
    assert params["router_bias"].shape == (2, 16)
    assert params["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(params["router_bias"]).max()) > 0
    assert params["moe_gate"].shape == (2, 4, 64, 32)  # the held
    assert params[tr.DENSE_STACK + "w_up"].shape == (1, 64, 96)
    assert tr.DENSE_STACK + "router" not in params
    # init_params draws the same tree
    init = tr.init_params(jax.random.PRNGKey(0), spec, jnp.float32)
    assert {k: v.shape for k, v in init.items()} == {
        k: v.shape for k, v in params.items()}
    # the experts are the PUBLISHED ids 4..7, the rotary rows moved
    sh = reference.Shards(tiny[0])
    np.testing.assert_array_equal(
        np.asarray(params["moe_up"][0, 1]),
        sh.get("model.layers.1.mlp.experts.5.up_proj.weight").T)
    kva = sh.get("model.layers.1.self_attn.kv_a_proj_with_mqa.weight")
    np.testing.assert_array_equal(
        np.asarray(params["wkv_a"][0, :, 128:136]), kva[128:144:2].T)


# ------------------------------------------ the forms and the reference

S, PAGE, MAXP, CH = 4, 8, 8, 8
T_PROMPT, T_DEC = 40, 8


def _through_the_step_programs(spec, params, ids, others, row_kind=None,
                               parked_extra=False, ch=CH):
    """Row 0's prompt in chunks of ``ch`` beside rows 1.. decoding, then
    every row decoding with row 0 fed ``ids``: the forward the engine's
    step programs run (forward_rows through the latent route, the
    kernel interpreted) -> ([T, V] logits of row 0, expert statistics).
    ``parked_extra``: row 3 is parked throughout as well. (At CH = 8
    the prompt row is absorbed like the decode rows; ``ch`` = 20 is
    past ``expanded_from`` and starts its second chunk off a page
    boundary.)"""
    with rows_rounded(row_kind):
        cache = tr.KVCache.create(spec, S * MAXP + 1, PAGE, jnp.float32)
        assert cache.v.shape[-1] == 0 and cache.k.shape[-1] == 256
        table = (1 + np.arange(S)[:, None] * MAXP
                 + np.arange(MAXP)[None]).astype(np.int32)
        tab = jnp.asarray(table)
        parked = table.copy()
        parked[0] = 0
        full = table.copy()
        if parked_extra:
            parked[3] = full[3] = 0
        ones = jnp.ones((S,), jnp.int32)

        @jax.jit
        def mixed(cache, dtoks, dpos, live, ptoks, ppos):
            dg = tr.Rows(dtoks, dpos, page_table=tab,
                         write_table=jnp.asarray(parked), q_lens=ones,
                         live=live)
            pg = tr.Rows(ptoks, ppos, page_table=tab[:1],
                         write_table=tab[:1],
                         q_lens=jnp.full((1,), ch, jnp.int32))
            (_, ph), cache, ex = tr.forward_rows(
                spec, params, (dg, pg), cache, kv_page=PAGE)
            return tr._lm_head(spec, params, ph)[0], cache, ex

        @jax.jit
        def decode(cache, dtoks, dpos, live):
            dg = tr.Rows(dtoks, dpos, page_table=tab,
                         write_table=jnp.asarray(full), q_lens=ones,
                         live=live)
            (dh,), cache, ex = tr.forward_rows(
                spec, params, (dg,), cache, kv_page=PAGE)
            return tr._lm_head(spec, params, dh)[:1, 0], cache, ex

        logits, stats, step = [], [], 0
        live = np.ones((S,), bool)
        live[0] = False
        if parked_extra:
            live[3] = False
        for c in range(T_PROMPT // ch):
            lg, cache, ex = mixed(
                cache, jnp.asarray(others[:, step][:, None]),
                jnp.full((S,), step, jnp.int32), jnp.asarray(live),
                jnp.asarray(ids[None, c * ch:(c + 1) * ch]),
                jnp.asarray([c * ch], jnp.int32))
            logits.append(np.asarray(lg))
            stats.append(np.asarray(ex))
            step += 1
        live[0] = True
        for t in range(T_DEC):
            dtoks = others[:, step][:, None].copy()
            dtoks[0, 0] = ids[T_PROMPT + t]
            dpos = np.full((S,), step, np.int32)
            dpos[0] = T_PROMPT + t
            lg, cache, ex = decode(cache, jnp.asarray(dtoks),
                                   jnp.asarray(dpos), jnp.asarray(live))
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


@pytest.fixture(scope="module")
def served(tiny, sequences):
    _, spec, params = tiny
    return _through_the_step_programs(spec, params, *sequences)


def test_absorbed_equals_expanded_at_every_position(tiny, sequences):
    """The kernel route absorbs W_kvb into the query and the output and
    reads cached rows as they are; the XLA route up-projects the rows.
    One prompt, chunk by chunk through pages of 8, against one pass on
    a dense cache."""
    _, spec, params = tiny
    ids = sequences[0]
    dense = tr.KVCache.create(spec, 2, 64, jnp.float32)
    want, _ = tr.forward(spec, params, jnp.asarray(ids[None]),
                         jnp.zeros((1,), jnp.int32), dense, jnp.array([1]))
    arena = tr.KVCache.create(spec, 9, PAGE, jnp.float32)
    pt = jnp.arange(1, 9, dtype=jnp.int32)[None]
    got, pos = [], 0
    for chunk in (16, 16, 8, 1, 1, 1, 1, 1, 1, 1, 1):
        lg, arena = tr.forward(
            spec, params, jnp.asarray(ids[None, pos:pos + chunk]),
            jnp.array([pos], jnp.int32), arena, None, page_table=pt,
            kv_page=PAGE, q_lens=jnp.array([chunk], jnp.int32),
            write_table=pt)
        got.append(lg)
        pos += chunk
    got = np.asarray(jnp.concatenate(got, axis=1)[0])
    assert _rel(got, np.asarray(want[0])).max() < 2e-5
    # what was cached is the row and nothing else: [c | k_r | zeros]
    row = np.asarray(arena.k[0, 1, 0])
    assert np.abs(row[:144]).min() > 0 and not row[144:].any()
    assert arena.v.size == 0


def test_step_programs_match_both_copies_of_the_reference(
        tiny, sequences, served):
    ckpt, spec, params = tiny
    ids = sequences[0]
    got, stats = served
    want = _numpy_logits(ckpt, ids)
    r = _rel(got, want)
    assert r.max() < 2e-5, r.max()
    second = np.asarray(reference_logits(spec, params, ids))
    assert _rel(second, want).max() < 2e-5
    assert _rel(got, second).max() < 2e-5
    assert r.max() < TOL / 100
    # statistics: [held experts | touched | absent]; every real token's
    # k assignments are either on a held expert or absent
    assert stats.shape[1] == spec.n_held + 2
    rows_mixed, rows_dec = (S - 1) + CH, S
    for st, rows in zip(stats, [rows_mixed] * (T_PROMPT // CH)
                        + [rows_dec] * T_DEC):
        assert st[:4].sum() + st[5] == rows * 4 * 2  # k = 4, 2 layers
        assert 0 < st[4] <= 2 * 4


MUTATIONS = [{"zero_layer": 1}, {"drop_kr": True}, {"drop_mscale": True},
             {"unnormed_c": True}, {"rope_half": True}, {"drop_bias": True},
             {"bias_in_weight": True}, {"no_groups": True},
             {"drop_shared": True}, {"drop_route_scale": True}]


@pytest.mark.parametrize("mutate", MUTATIONS, ids=lambda m: next(iter(m)))
def test_the_tolerance_catches_each_mutation(tiny, sequences, served,
                                             mutate):
    got, _ = served
    r = _rel(got, _numpy_logits(tiny[0], sequences[0], mutate))
    assert np.median(r) > TOL, (mutate, np.median(r))


@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_a_lower_precision_row_fails_the_tolerance(tiny, sequences, kind):
    """The control one step below the stated precision: the latent row
    cached in int8 (per-row scale) or fp8."""
    ckpt, spec, params = tiny
    got, _ = _through_the_step_programs(
        spec, params, *sequences, row_kind=kind)
    r = _rel(got, _numpy_logits(ckpt, sequences[0]))
    assert np.median(r[:T_PROMPT]) > TOL and np.median(r[T_PROMPT:]) > TOL


def test_other_slots_and_a_parked_row_do_not_move_a_rows_bits(
        tiny, sequences, served):
    _, spec, params = tiny
    ids, others = sequences
    changed = others.copy()
    changed[1:] = (changed[1:] * 7 + 3) % 257
    got, _ = _through_the_step_programs(spec, params, ids, changed)
    np.testing.assert_array_equal(got, served[0])
    parked, _ = _through_the_step_programs(spec, params, ids, others,
                                           parked_extra=True)
    np.testing.assert_array_equal(parked, served[0])


# ------------------------------------------ the expanded flash kernel


def test_the_form_comes_from_the_widths_alone(tiny):
    from localai_tfp_tpu.ops.latent_flash_attention import (
        ABSORBED, EXPANDED, expanded_from, latent_prompt_form,
    )

    # T* = r (d_n + d_v) / (2 r - d_n - d_v)
    assert expanded_from(512, 128, 128) == pytest.approx(170.67, abs=0.01)
    assert expanded_from(128, 16, 16) == pytest.approx(18.29, abs=0.01)
    assert expanded_from(64, 64, 64) == float("inf")  # never cheaper
    _, spec, _ = tiny
    assert [latent_prompt_form(spec, T) for T in (1, 8, 16, 18)] == \
        [ABSORBED] * 4
    assert [latent_prompt_form(spec, T) for T in (19, 32, 512)] == \
        [EXPANDED] * 3
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v3-ep16-share.json")) as f:
        published = spec_from_hf_config(json.load(f))
    assert [latent_prompt_form(published, T)
            for T in (1, 4, 128, 170, 171, 256, 512)] == \
        [ABSORBED] * 4 + [EXPANDED] * 3
    wide = dataclasses.replace(published, qk_nope_dim=512, v_head_dim=512)
    assert latent_prompt_form(wide, 4096) == ABSORBED


def _flash_case(spec, seed=0, n_rows=3, n_pos=64):
    """Latent rows for ``n_rows`` slots of ``n_pos`` positions in an
    arena of pages of 8 (layer 1 of 2), W_kvb stacks of 3 layers (the
    kernel reads layer 2), and a query for every absolute position."""
    r, dr, dn = spec.kv_lora_rank, spec.qk_rope_dim, spec.qk_nope_dim
    H, F = spec.n_heads, spec.latent_row
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    maxp = n_pos // PAGE
    rows = jax.random.normal(ks[0], (n_rows, n_pos, F))
    rows = rows.at[..., r + dr:].set(0)
    arena = jnp.zeros((2, 1 + n_rows * maxp, PAGE, F)).at[1, 1:].set(
        rows.reshape(n_rows * maxp, PAGE, F))
    table = (1 + jnp.arange(n_rows * maxp, dtype=jnp.int32)).reshape(
        n_rows, maxp)
    wk = jax.random.normal(ks[1], (3, H, dn, r)) * r ** -0.5
    wv = jax.random.normal(ks[2], (3, H, r, spec.v_head_dim)) * r ** -0.5
    qn = jax.random.normal(ks[3], (n_rows, n_pos, H, dn))
    qr = jax.random.normal(ks[4], (n_rows, n_pos, H, dr))
    return rows, arena, table, wk, wv, qn, qr


def _flash(spec, case, pos0, q_lens, T):
    """The kernel over chunks of ``T`` queries starting at ``pos0``."""
    from localai_tfp_tpu.ops.latent_flash_attention import (
        join_query, latent_flash_attention,
    )

    rows, arena, table, wk, wv, qn, qr = case
    at = np.asarray(pos0)[:, None] + np.arange(T)[None]
    b = np.arange(len(pos0))[:, None]
    q = join_query(qn[b, at], qr[b, at],
                   spec.latent_row - spec.kv_lora_rank)
    return np.asarray(latent_flash_attention(
        q, arena, jnp.int32(1), table[:len(pos0)],
        jnp.asarray(pos0, jnp.int32), jnp.asarray(q_lens, jnp.int32),
        wk, wv, jnp.int32(2), scale=tr.latent_scale(spec), page=PAGE))


@pytest.mark.parametrize("pos0,q_lens", [
    ((0, 16, 24), (32, 32, 32)),
    ((5, 21, 3), (32, 32, 32)),
    ((16, 11, 0), (7, 20, 1)),
], ids=["on_page_boundaries", "off_page_boundaries", "a_last_chunk"])
def test_expanded_kernel_equals_the_xla_form_at_every_position(
        tiny, pos0, q_lens, monkeypatch):
    """Three rows a call, each a 32-query chunk at its own position:
    every valid query's output is ``latent_attend_expanded``'s over the
    row's whole view; queries past ``q_lens`` are finite."""
    _, spec, _ = tiny
    case = _flash_case(spec)
    rows, _, _, wk, wv, qn, qr = case
    T = 32
    got = _flash(spec, case, pos0, q_lens, T)
    assert np.isfinite(got).all()
    at = np.asarray(pos0)[:, None] + np.arange(T)[None]
    b = np.arange(3)[:, None]
    want = np.asarray(tr.latent_attend_expanded(
        spec, {"wkv_b_k": wk[2], "wkv_b_v": wv[2]}, qn[b, at], qr[b, at],
        rows, jnp.asarray(at, jnp.int32)))
    for i, n in enumerate(q_lens):
        assert _rel(got[i, :n], want[i, :n]).max() < 2e-5, (i, n)
    # the chunk loop a row longer than ``_QUERY_CHUNK`` takes (here
    # two chunks of 16 queries, the second skipped where it holds no
    # token or sees nothing of a page)
    from localai_tfp_tpu.ops import latent_flash_attention as lfa

    monkeypatch.setattr(lfa, "_QUERY_CHUNK", 16)
    halves = _flash(spec, case, pos0, q_lens, T)
    for i, n in enumerate(q_lens):
        assert _rel(halves[i, :n], want[i, :n]).max() < 2e-5, (i, n)


def test_a_token_reads_equal_bits_wherever_its_chunk_started(tiny):
    """The same token at the same absolute position over the same
    cached pages, at two indices of two chunks (one starting on a page
    boundary, one not, one a last chunk cut by ``q_lens``): equal bits
    — what the benchmark's repeated-prompt probe holds."""
    _, spec, _ = tiny
    case = _flash_case(spec)
    T = 32
    a = _flash(spec, case, (16,), (32,), T)[0]  # positions 16..47
    b = _flash(spec, case, (21,), (32,), T)[0]  # positions 21..52
    c = _flash(spec, case, (8,), (30,), T)[0]  # positions 8..37, cut
    np.testing.assert_array_equal(a[5:], b[:27])
    np.testing.assert_array_equal(a[:22], c[8:30])


def test_other_rows_and_a_parked_row_do_not_move_the_kernels_bits(tiny):
    _, spec, _ = tiny
    case = _flash_case(spec)
    alone = _flash(spec, case, (11,), (32,), 32)[0]
    among = _flash(spec, case, (11, 3, 30), (32, 17, 32), 32)[0]
    np.testing.assert_array_equal(among, alone)
    rows, arena, table, wk, wv, qn, qr = case
    other = (rows, arena.at[1, 9:].multiply(3.0), table, wk, wv,
             qn.at[1:].add(1.0), qr)  # rows 1, 2: other pages, queries
    np.testing.assert_array_equal(
        _flash(spec, other, (11, 3, 30), (32, 17, 32), 32)[0], alone)
    parked = _flash(spec, case, (11, 3, 30), (32, 0, 32), 32)
    np.testing.assert_array_equal(parked[0], alone)
    assert not parked[1].any()  # a parked row reads and writes nothing


def test_step_programs_with_an_expanded_prompt_row_match_the_reference(
        tiny, sequences, served):
    """``_through_the_step_programs`` with 20-token prompt rows (past
    ``expanded_from``; the second starts at position 20, off a page
    boundary): the logits are the reference's and the decode positions
    — same absorbed kernel over the same pages — the bits the 8-token
    chunks left; other slots and a parked row move no bit."""
    ckpt, spec, params = tiny
    ids, others = sequences
    got, _ = _through_the_step_programs(spec, params, ids, others, ch=20)
    assert _rel(got, _numpy_logits(ckpt, ids)).max() < 2e-5
    assert _rel(got, served[0][:T_PROMPT + T_DEC]).max() < 2e-5
    changed = others.copy()
    changed[1:] = (changed[1:] * 7 + 3) % 257
    np.testing.assert_array_equal(_through_the_step_programs(
        spec, params, ids, changed, parked_extra=True, ch=20)[0], got)


# ----------------------------------------------------- routing, the share


def _route_spec(**over):
    kw = dict(n_experts=16, experts_per_token=4, moe_score_func="sigmoid",
              moe_select_bias=True, moe_norm_topk=True, moe_route_scale=2.5,
              moe_n_group=4, moe_topk_group=2, moe_d_ff=32,
              moe_shared_expert=True, moe_shared_d_ff=32,
              moe_shared_gated=False)
    kw.update(over)
    return tiny_spec(**kw)


def test_group_limited_routing_is_the_references(tiny):
    ckpt, spec, params = tiny
    sh = reference.Shards(ckpt)
    g = lambda n: sh.get("model.layers.1." + n)  # noqa: E731
    x = np.random.default_rng(5).normal(size=(200, 64)).astype(np.float32)
    want_idx, want_w = models.of(TINY).route(x, g, TINY, {})
    lp = {"router": params["router"][0], "router_bias":
          params["router_bias"][0]}
    idx, w = tr._route(spec, lp, jnp.asarray(x))
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want_idx, -1))
    order = np.argsort(np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1),
        np.take_along_axis(want_w, np.argsort(want_idx, -1), -1),
        rtol=1e-5)
    # every pick lies in at most topk_group groups
    assert (np.array([len(set(r // 4)) for r in np.asarray(idx)]) <= 2).all()
    # and the grouping matters: a plain top-k picks otherwise somewhere
    plain, _ = models.of(TINY).route(x, g, TINY, {"no_groups": True})
    assert (np.sort(plain, -1) != np.sort(want_idx, -1)).any()


def test_a_tie_across_groups_goes_to_the_lower_index():
    """Equal scores everywhere: groups 0 and 1 are kept (lowest index
    on a tie), and inside them experts 0..3."""
    spec = _route_spec()
    lp = {"router": jnp.zeros((64, 16)), "router_bias": jnp.zeros((16,))}
    idx, w = tr._route(spec, lp, jnp.ones((3, 64)))
    assert np.asarray(idx).tolist() == [[0, 1, 2, 3]] * 3
    np.testing.assert_allclose(np.asarray(w), 2.5 / 4, rtol=1e-6)
    cfg = dict(TINY, n_group=4, topk_group=2)
    z = {"mlp.gate.weight": np.zeros((16, 64), np.float32),
         "mlp.gate.e_score_correction_bias": np.zeros((16,), np.float32)}
    top, _ = models.of(TINY).route(np.ones((3, 64), np.float32),
                                   z.__getitem__, cfg, {})
    assert top.tolist() == [[0, 1, 2, 3]] * 3
    # a tie BETWEEN groups with unequal members: groups 1 and 2 hold the
    # same two best scores; group 1 wins
    bias = np.zeros((16,), np.float32)
    bias[[4, 5, 8, 9]] = 0.25
    bias[12] = 0.2
    lp["router_bias"] = jnp.asarray(bias)
    idx, _ = tr._route(dataclasses.replace(spec, moe_topk_group=1,
                                           experts_per_token=2), lp,
                       jnp.ones((1, 64)))
    assert np.asarray(idx).tolist() == [[4, 5]]


def test_the_shares_add_up_to_the_uncut_layer():
    """model-configs section 4: 8 experts in 4 shares of 2. Each share
    routes over all 8, adds its own experts' weighted outputs; the
    shared expert is counted ONCE — the sum is the uncut layer's
    output, and every real assignment is held by exactly one share."""
    uncut = _route_spec(n_experts=8, experts_per_token=3, moe_n_group=4,
                        moe_topk_group=3)
    rng = jax.random.PRNGKey(7)
    full = tr.init_params(rng, uncut, jnp.float32)
    lp = {k: v[0] for k, v in full.items() if v.ndim >= 2
          and k not in ("embed", "lm_head") and v.shape[0] == uncut.n_layers}
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 11, 64))
    valid = jnp.ones((3, 11), bool).at[1, 7:].set(False)

    def layer(spec, first):
        held = slice(first, first + spec.n_held)
        whole = {k: full[k][:, held] for k in tr.EXPERT_LEAVES}
        part = {k: v for k, v in lp.items() if k not in tr.EXPERT_LEAVES}
        return tr._moe_mlp(spec, part, x, valid, (whole, 0))

    want, counts = layer(uncut, 0)
    no_routed = {k: v for k, v in lp.items() if k not in tr.EXPERT_LEAVES}
    # the shared expert alone
    shared = (tr._act(uncut, x @ no_routed["shared_gate"])
              * (x @ no_routed["shared_up"])) @ no_routed["shared_down"]
    total, held_sum, absent = jnp.zeros_like(want), 0, []
    for first in (0, 2, 4, 6):
        spec = dataclasses.replace(uncut, experts_held=2,
                                   experts_first=first)
        out, c = layer(spec, first)
        assert c.shape == (3,)  # [2 held | absent]
        np.testing.assert_array_equal(np.asarray(c[:2]),
                                      np.asarray(counts[first:first + 2]))
        total = total + (out - shared)
        held_sum += int(c[:2].sum())
        absent.append(int(c[2]))
    n_real = int(valid.sum()) * 3
    assert held_sum == n_real == int(counts.sum())
    assert absent == [n_real - int(counts[f:f + 2].sum())
                      for f in (0, 2, 4, 6)]
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), rtol=2e-5, atol=2e-6)
    # positions without a token add nothing in any share
    assert not np.asarray(total)[1, 7:].any()


# ----------------------------------------------------------- the engine


def _serve(monkeypatch, tiny, **kw):
    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer

    _, spec, params = tiny
    monkeypatch.setenv("LOCALAI_DECODE_KERNEL", "1")  # interpreted here
    monkeypatch.setenv("LOCALAI_KV_PAGE", "8")
    kw.setdefault("n_slots", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    return LLMEngine(spec, params, ByteTokenizer(), max_seq=64,
                     prefill_buckets=(8, 32), autostart=False, **kw)


def _value(family, **labels):
    from localai_tfp_tpu.telemetry.registry import REGISTRY

    total = 0.0
    for ln in REGISTRY.render().splitlines():
        if not ln.startswith(family + "{"):
            continue
        if all(f'{k}="{v}"' in ln for k, v in labels.items()):
            total += float(ln.rsplit(" ", 1)[1])
    return total


def _generate(eng, prompt, n=12):
    from localai_tfp_tpu.engine.engine import GenRequest

    req = GenRequest(prompt_ids=prompt, max_tokens=n, temperature=0,
                     ignore_eos=True)
    done = {}
    finish = eng._finish

    def spy(slot, reason):
        if slot.request is not None:
            done[slot.request.id] = list(slot.generated)
        return finish(slot, reason)

    eng._finish = spy
    try:
        eng.submit(req)
        for _ in range(5000):
            if req.id in done:
                break
            eng.step()
    finally:
        eng._finish = finish
    return done[req.id]


def test_engine_serves_it_on_the_latent_kernel_route(monkeypatch, tiny):
    ckpt, spec, _ = tiny
    m = "dsv3-serve"
    eng = _serve(monkeypatch, tiny, tag=m)
    try:
        assert eng.kernel_ineligible == ""
        assert eng.attention_path == "latent_paged_kernel"
        assert eng._n_expert_layers == 2
        assert eng.experts_held() == [4, 7]
        # the ladder at toy widths; ONE prompt-row shape from the
        # width on where an expert stack's bits hang on a step's rows
        assert eng._step_buckets == (8, 32)
        with monkeypatch.context() as mp:
            mp.setattr(type(eng), "_EXPERT_ONE_SHAPE_D_MODEL", spec.d_model)
            assert eng._step_buckets == (32,)
            assert {b for _, b, _ in eng._mixed_variants()} == {32}
            mp.setattr(eng, "spec", dataclasses.replace(
                spec, n_experts=0))  # a dense latent model: the ladder
            assert eng._step_buckets == (8, 32)
        # 256 lanes x 4 B x 3 layers, nothing beside the row
        assert eng.kv_row_bytes == 3072
        assert eng.cache_bytes() == {"kv": 3072 * 33 * 8}
        assert _value("engine_kv_row_bytes", model=m) == 3072
        assert "latent_cache" in eng.hbm_stats()["components"] \
            if eng.hbm_stats() else True
        prompt = [int(t) for t in np.random.default_rng(4).integers(
            0, 257, 21)]
        toks = _generate(eng, prompt)
        assert len(toks) == 12
        want = _numpy_logits(ckpt, prompt + toks)
        assert want[len(prompt) - 1:-1].argmax(-1).tolist() == toks
        # every routed token was counted, held or absent
        real = _value("engine_dispatch_tokens_total", model=m, part="real")
        held = _value("engine_expert_assignments_total", model=m,
                      where="held")
        absent = _value("engine_expert_assignments_total", model=m,
                        where="absent")
        assert held + absent == real * 4 * 2 and held > 0 and absent > 0
        assert _value("engine_expert_tokens_total", model=m) == held
        # labelled by PUBLISHED id
        from localai_tfp_tpu.telemetry.registry import REGISTRY
        ids = {ln.split('expert="')[1].split('"')[0]
               for ln in REGISTRY.render().splitlines()
               if ln.startswith("engine_expert_tokens_total{")
               and f'model="{m}"' in ln}
        assert ids == {"4", "5", "6", "7"}
        steps = _value("engine_expert_layer_steps_total", model=m)
        touched = _value("engine_experts_touched_total", model=m)
        assert 0 < touched <= 4 * steps
        read = _value("engine_attn_context_tokens_total", model=m)
        assert read == _value("engine_attn_context_held_tokens_total",
                              model=m) > 0
    finally:
        eng.close()


@pytest.mark.parametrize("on_the_kernel", [False, True],
                         ids=["xla_dispatch", "held_rows_dispatch"])
def test_dispatch_rows_are_counted_moved_and_slots(monkeypatch, tiny,
                                                   on_the_kernel):
    """engine_expert_dispatch_rows_total{kind}: ``slots`` the sorted
    rows a step's arrays hold (token rows x K a layer-step, padding
    too), ``moved`` what the dispatch around the grouped matmul
    touched — every slot where XLA gathers, masks and un-sorts (the
    CPU: ``expert_path`` is ``ragged_dot``), the held assignments where
    a share rides the grouped kernel (``expert_path`` as a chip reports
    it; the counts are the served programs' own either way)."""
    m = f"dsv3-rows-{int(on_the_kernel)}"
    eng = _serve(monkeypatch, tiny, tag=m)
    try:
        assert eng.expert_path == "ragged_dot"
        if on_the_kernel:
            eng.expert_path = "grouped_kernel"
        prompt = [int(t) for t in np.random.default_rng(9).integers(
            0, 257, 21)]
        _generate(eng, prompt, n=6)
        fam = "engine_expert_dispatch_rows_total"
        moved, slots = (_value(fam, model=m, kind=k)
                        for k in ("moved", "slots"))
        held = _value("engine_expert_assignments_total", model=m,
                      where="held")
        absent = _value("engine_expert_assignments_total", model=m,
                        where="absent")
        padded = _value("engine_dispatch_tokens_total", model=m,
                        part="padded")
        # 2 expert layers, top-4: every padded token row is 4 slots a layer
        assert slots == padded * 4 * 2 >= held + absent > 0
        assert moved == (held if on_the_kernel else slots)
        assert 0 < held < slots
    finally:
        eng.close()


def test_prompt_tokens_are_counted_by_the_form_of_their_step(
        monkeypatch, tiny):
    """engine_latent_prompt_tokens_total{form}, at the dispatch site: a
    21-token prompt rides the 32-token bucket (past ``expanded_from`` =
    18.3 at the toy widths: the flash kernel), a 5-token prompt the
    8-token bucket (absorbed) — real tokens, not the buckets."""
    ckpt, _, _ = tiny
    m = "dsv3-forms"
    eng = _serve(monkeypatch, tiny, tag=m)
    try:
        rng = np.random.default_rng(7)
        long = [int(t) for t in rng.integers(0, 257, 21)]
        toks = _generate(eng, long, n=3)
        fam = "engine_latent_prompt_tokens_total"
        assert _value(fam, model=m, form="expanded") == 21
        assert _value(fam, model=m, form="absorbed") == 0
        want = _numpy_logits(ckpt, long + toks)
        assert want[len(long) - 1:-1].argmax(-1).tolist() == toks
        short = [int(t) for t in rng.integers(0, 257, 5)]
        _generate(eng, short, n=2)
        assert _value(fam, model=m, form="expanded") == 21
        assert _value(fam, model=m, form="absorbed") == 5
    finally:
        eng.close()


@pytest.mark.parametrize("before,after,want", [
    ({"expanded": 1000.0, "absorbed": 40.0},
     {"expanded": 9000.0, "absorbed": 40.0}, 100.0),
    ({"expanded": 0.0, "absorbed": 0.0},
     {"expanded": 600.0, "absorbed": 200.0}, 75.0),
    ({"absorbed": 10.0}, {"absorbed": 510.0}, 0.0),
    (None, None, None),
], ids=["every_step_a_whole_row", "a_ladder", "all_absorbed", "the_parent"])
def test_the_benchmarks_reader_of_the_counter(before, after, want):
    """``latent_prompt_expanded_share`` (a ``.json`` ratio reader, listed
    for ``deepseekv3_docs_closed`` alone under the layer the other
    attention readers have): the window's DELTA of the expanded form's
    tokens over both forms', in percent; a program without the counter
    — the parent — has nothing to read."""
    from benchmark.lib import layer_metrics, manifest

    def scrape(d):
        fams = {"engine_dispatch_tokens_total": [
            ({"model": "m", "kind": "mixed", "part": "real"}, 1.0)]}
        if d is not None:
            fams["engine_latent_prompt_tokens_total"] = [
                ({"model": "m", "form": f}, v) for f, v in d.items()]
        return fams

    run = {"metrics_before": scrape(before), "metrics_after": scrape(after)}
    got = layer_metrics.evaluate(
        os.path.join(ROOT, "benchmark", "layer_metrics"),
        "latent_prompt_expanded_share", None, run)
    assert got == (want if want is None else pytest.approx(want))
    entry = next(m for m in manifest.load(ROOT)["per_layer"]
                 if m["name"] == "latent_prompt_expanded_share")
    assert entry == {
        "name": "latent_prompt_expanded_share", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "attention kernel", "moves": "tpot_p50_ms",
        "workloads": ["deepseekv3_docs_closed"]}


@pytest.mark.parametrize("before,after,want", [
    ({"moved": 0.0, "slots": 0.0}, {"moved": 285.0, "slots": 4224.0},
     100 * 285 / 4224),
    ({"moved": 50.0, "slots": 50.0}, {"moved": 4274.0, "slots": 4274.0},
     100.0),
    (None, None, None),
], ids=["the_held_rows_alone", "every_row_by_xla", "the_parent"])
def test_the_benchmarks_reader_of_the_dispatch_rows(before, after, want):
    """``expert_dispatch_rows_share`` (a ``.json`` ratio reader, listed
    for ``deepseekv3_docs_closed`` alone under the expert layer): the
    window's DELTA of the rows the dispatch moved over the slots its
    arrays hold, in percent; a program without the counter — the parent
    — has nothing to read."""
    from benchmark.lib import layer_metrics, manifest

    def scrape(d):
        fams = {"engine_dispatch_tokens_total": [
            ({"model": "m", "kind": "mixed", "part": "real"}, 1.0)]}
        if d is not None:
            fams["engine_expert_dispatch_rows_total"] = [
                ({"model": "m", "kind": k}, v) for k, v in d.items()]
        return fams

    run = {"metrics_before": scrape(before), "metrics_after": scrape(after)}
    got = layer_metrics.evaluate(
        os.path.join(ROOT, "benchmark", "layer_metrics"),
        "expert_dispatch_rows_share", None, run)
    assert got == (want if want is None else pytest.approx(want))
    entry = next(m for m in manifest.load(ROOT)["per_layer"]
                 if m["name"] == "expert_dispatch_rows_share")
    assert entry == {
        "name": "expert_dispatch_rows_share", "unit": "%",
        "better": "lower", "source": "program_counter",
        "layer": "expert layer", "moves": "tpot_p50_ms",
        "workloads": ["deepseekv3_docs_closed"]}


def test_prefix_reuse_shares_latent_pages(monkeypatch, tiny):
    """The same 40-token prompt twice: the second admission reuses the
    first's full pages through the prefix index and decodes the same
    tokens."""
    m = "dsv3-prefix"
    eng = _serve(monkeypatch, tiny, tag=m)
    try:
        prompt = [int(t) for t in np.random.default_rng(6).integers(
            0, 257, 40)]
        first = _generate(eng, prompt, 6)
        assert _value("engine_prefix_reused_tokens_total", model=m) == 0
        second = _generate(eng, prompt, 6)
        assert second == first
        assert _value("engine_prefix_reused_tokens_total", model=m) >= 32
    finally:
        eng.close()


def test_embeddings_of_a_long_prompt_go_through_in_chunks(monkeypatch, tiny):
    ckpt, spec, _ = tiny
    eng = _serve(monkeypatch, tiny, tag="dsv3-embed")
    try:
        eng._EMBED_CHUNK = 8
        text = "latent rows " * 3
        got = np.asarray(eng.embed(text), np.float32)
        ids = eng.tokenizer.encode(text, add_bos=True)
        sh = reference.Shards(ckpt)
        want = models.of(TINY).forward_hidden(sh, TINY, [ids])[0].mean(0)
        assert reference.rel_l2(got, want) < 1e-4
    finally:
        eng.close()


@pytest.mark.parametrize("path", ["kv_tier", "kv_migrate", "prompt_cache",
                                  "weight_pager", "kv_cache_dtype_int8",
                                  "mesh", "forward_train"])
def test_each_refusal_names_the_model(monkeypatch, tiny, path):
    eng = _serve(monkeypatch, tiny, tag="dsv3-refuse", kv_tier=True,
                 weight_paging=True)
    try:
        assert "deepseek_v3" in eng.state_refusals[path]
        assert eng._tier is None and eng._pager is None
    finally:
        eng.close()


def test_the_refused_paths_raise_by_name(monkeypatch, tiny):
    _, spec, params = tiny
    with pytest.raises(NotImplementedError, match="deepseek_v3.*int8"):
        _serve(monkeypatch, tiny, cache_dtype=jnp.int8)
    with pytest.raises(NotImplementedError, match="deepseek_v3.*int8"):
        tr.KVCache.create(spec, 2, 16, jnp.int8)
    with pytest.raises(NotImplementedError, match="deepseek_v3.*training"):
        tr.forward_train(spec, params, jnp.zeros((1, 4), jnp.int32))
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    with pytest.raises(NotImplementedError, match="deepseek_v3.*mesh"):
        _serve(monkeypatch, tiny, mesh=mesh)
    # a draft model is dropped with its reason
    eng = _serve(monkeypatch, tiny, tag="dsv3-draft",
                 draft=(tiny_spec(vocab_size=259), {}))
    try:
        assert eng.draft is None
        assert "deepseek_v3" in eng.state_refusals["speculative"]
    finally:
        eng.close()
    # without the pool the dense decode kernel has no absorbed form
    monkeypatch.setenv("LOCALAI_PAGED_KV", "off")
    eng = _serve(monkeypatch, tiny, tag="dsv3-dense")
    try:
        assert eng.attention_path == "dense_xla"
        assert "latent cache" in eng.kernel_ineligible
        prompt = [5, 6, 7, 8, 9, 10, 11]
        assert len(_generate(eng, prompt, 4)) == 4
    finally:
        eng.close()


def test_config_file_of_the_cell_is_this_model():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v3-ep16-share.json")) as f:
        config = json.load(f)
    assert config["model_type"] == TINY["model_type"]
    for key in TINY:
        assert key in config, key
