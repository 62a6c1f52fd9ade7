"""The ``deepseek_v3`` model file (benchmark/models/deepseek_v3.py), the
configuration ``deepseek-v3-ep16-share``, its mix, its cell and the
three per-layer readers that came with it: the tensor table loads
through models/hf_loader.py (experts named by PUBLISHED id, a router as
wide as the published count), the plain reference agrees with the
program, each way of breaking it is caught, the byte counts are ISSUE
45's arithmetic, the configuration is held to its ``.published.json``,
and each reader reads what it says — never over 100 % when the kernel
runs at its roof, and NOTHING (None) from a program that lacks what it
reads, as the parent of the PR that brought it does. JAX is imported
inside the tests only."""

import json
import math
import os

import numpy as np
import pytest

from benchmark.lib import checkpoint, layer_metrics, models, reference, roofline
from benchmark.lib import manifest as M

from . import helpers as H

MDIR = os.path.join(H.ROOT, "benchmark", "layer_metrics")
CELL = "deepseekv3_docs_closed"
NAME = "deepseek-v3-ep16-share"
TINY_DS = {
    "architectures": ["DeepseekV3ForCausalLM"], "model_type": "deepseek_v3",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 128,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "experts_first": 8, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "moe_layer_freq": 1,
    "num_nextn_predict_layers": 0, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0,
                     "original_max_position_embeddings": 64},
    "hidden_act": "silu", "max_position_embeddings": 4096,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "bos_token_id": 510, "eos_token_id": 511,
    "assumed": {"served_bytes_per_param": {"dense": 2, "experts": 2},
                "kv_bytes_per_value": 2, "kv_row_pad_values": 112,
                "kv_page_tokens": 256, "kv_pool_pages": 64},
}
IDS = [list(range(7, 77)), [500, 3, 3, 9, 250, 17, 101, 44, 44, 2] * 9]
TOL = 2e-3


def _real_config():
    with open(os.path.join(H.ROOT, "benchmark", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("deepseek_v3"))
    with H.using_models(H.MODELS):
        checkpoint.write_hf_checkpoint(
            d, checkpoint.hf_config(TINY_DS), seed=3, threads=2)
    return d


def test_model_file_loads_and_names_what_the_loader_reads():
    mod = models.load("deepseek_v3", H.MODELS)
    assert mod.ATTENTION_KERNELS == ("latent_paged_attention",)
    assert mod.EXPERT_KERNELS == ("ragged-dot",)
    for fn in ("expert_bytes", "expert_layers", "layer_params", "route",
               "moe"):
        assert callable(getattr(mod, fn))
    names = [t[1] for t in mod.tensors(TINY_DS)]
    assert len(names) == len(set(names))
    # layer 0 dense; layers 1-2 hold published experts 8..11 under a
    # router (and a bias) as wide as the 16 published
    assert "model.layers.0.mlp.gate_proj.weight" in names
    assert "model.layers.0.mlp.gate.weight" not in names
    shape = {t[1]: (t[2], t[3]) for t in mod.tensors(TINY_DS)}
    assert shape["model.layers.1.mlp.gate.weight"] == ((16, 64), "BF16")
    assert shape["model.layers.1.mlp.gate.e_score_correction_bias"] == (
        (16,), "F32")
    held = sorted({int(n.split("experts.")[1].split(".")[0])
                   for n in names if ".mlp.experts." in n})
    assert held == [8, 9, 10, 11]
    assert shape["model.layers.2.self_attn.kv_a_proj_with_mqa.weight"][0] \
        == (128 + 16, 64)
    assert shape["model.layers.2.self_attn.kv_b_proj.weight"][0] == (
        4 * 32, 128)
    # no multi-token-prediction layer is written
    assert not [n for n in names if n.startswith("model.layers.3.")]


def test_reference_matches_the_program(ckpt):
    import jax.numpy as jnp

    from localai_tfp_tpu.models.hf_loader import load_params
    from localai_tfp_tpu.models.transformer import KVCache, forward_hidden

    hf = checkpoint.hf_config(TINY_DS)
    with H.using_models(H.MODELS):
        want = reference.forward_hidden(ckpt, hf, IDS)
    spec, params = load_params(ckpt, dtype=jnp.float32)
    assert (spec.n_experts, spec.n_held, spec.experts_first) == (16, 4, 8)
    for ids, w in zip(IDS, want):
        cache = KVCache.create(spec, 1, 128, jnp.float32)
        got, _ = forward_hidden(
            spec, params, jnp.asarray([ids], jnp.int32),
            jnp.zeros((1,), jnp.int32), cache, jnp.zeros((1,), jnp.int32))
        assert reference.rel_l2(np.asarray(got[0]).mean(0),
                                w.mean(0)) < TOL / 20


@pytest.mark.parametrize("mutate", [
    {"zero_layer": 1}, {"drop_kr": True}, {"drop_mscale": True},
    {"unnormed_c": True}, {"rope_half": True}, {"drop_bias": True},
    {"no_groups": True}, {"drop_shared": True}, {"drop_route_scale": True},
], ids=lambda m: next(iter(m)))
def test_tolerance_catches_a_broken_model(ckpt, mutate):
    hf = checkpoint.hf_config(TINY_DS)
    with H.using_models(H.MODELS):
        good = reference.pooled(ckpt, hf, IDS)
        bad = reference.pooled(ckpt, hf, IDS, mutate)
    assert max(reference.rel_l2(b, g) for b, g in zip(bad, good)) > TOL


def test_bytes_are_the_issues_arithmetic():
    config = _real_config()
    mod = models.of(config)
    p = mod.param_counts(config)
    assert p["attn"] == (7168 * 1536 + 1536 * 24576 + 7168 * 576
                         + 512 * 32768 + 16384 * 7168)
    assert round(p["attn"] / 1e6, 1) == 187.1
    assert p["expert"] == 3 * 7168 * 2048 and round(
        p["expert"] / 1e6, 2) == 44.04
    lay = mod.layer_params(config)
    assert round(lay["expert"] / 1e6, 1) == 937.6
    assert round(lay["dense"] / 1e6, 1) == 583.5
    total = sum(int(np.prod(t[2])) for t in mod.tensors(config))
    assert round(total / 1e9, 3) == 5.503
    assert mod.expert_bytes(config) == 3 * 7168 * 2048 * 2
    assert mod.expert_layers(config) == 5
    assert mod.kv_bytes_per_token(config, layers=1) == 1152
    assert mod.kv_bytes_per_token(config) == 6912
    assert roofline.kv_bytes_per_token(config) == 6912
    # at 16 rows of top-8 over 256, 16 held: 6.4 experts a layer-step
    assert 6.3 < mod.experts_touched(config, 16) < 6.5
    assert 6.4e9 < mod.decode_weight_bytes(config, 16) < 6.7e9
    # the stated padding: the engine's row is 640 lanes
    from localai_tfp_tpu.models.llm_spec import spec_from_hf_config
    spec = spec_from_hf_config(checkpoint.hf_config(config))
    assert spec.latent_row - spec.latent_width == \
        config["assumed"]["kv_row_pad_values"] == 64
    assert spec.latent_row * 2 * 6 == 7680
    serving = config["serving"]
    assert config["assumed"]["kv_pool_pages"] == serving["max_batch_slots"] \
        * serving["context_size"] // config["assumed"]["kv_page_tokens"]


def test_configuration_is_held_to_what_was_published():
    config = _real_config()
    with open(os.path.join(H.ROOT, "benchmark", "configs",
                           NAME + ".published.json")) as f:
        src = json.load(f)
    assert src["source"] == config["source"] == \
        "https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json"
    reduced = {"num_hidden_layers": (61, 6), "first_k_dense_replace": (3, 1),
               "n_routed_experts": (256, 16), "vocab_size": (129280, 16160),
               "num_nextn_predict_layers": (1, 0)}
    assert config["reduced"] == list(reduced)
    assert config["published"] == {k: v[0] for k, v in reduced.items()}
    for key, want in src["config"].items():
        if key in reduced:
            assert (want, config[key]) == reduced[key], key
        else:
            assert config[key] == want, key
    # the share, stated beside the published count in keys of this
    # repo's own that reach the served config.json
    hf = checkpoint.hf_config(config)
    assert (hf["n_routed_experts_published"], hf["experts_first"],
            hf["n_routed_experts"], hf["n_group"], hf["topk_group"]) == (
        256, 0, 16, 8, 4)
    for key in ("source", "reduced", "assumed", "deployment", "expect"):
        assert key in config and key not in hf
    assert config["expect"] == {"attention_path": "latent_paged_kernel"}
    assert config["serving"] == {
        "backend": "jax-llm", "quantization": "none",
        "kv_cache_dtype": "bfloat16", "context_size": 8192,
        "max_batch_slots": 16, "embeddings": True}
    assert "16 chips" in config["deployment"]


def test_the_limit_lies_between_the_served_reading_and_its_control():
    """``parity_tol`` against the readings its reason gives (the chip's,
    tools/mla_parity.py --probe): over the served precision's largest,
    under the int8-row control's smallest, with room on both sides."""
    import re

    config = _real_config()
    reason = config["parity_tol_reason"]
    parts = reason.split("int8:")
    served = max(float(x) for x in re.findall(r"(\d\.\d+e-\d)", parts[0])[:4])
    control = min(float(x) for x in re.findall(r"(\d\.\d+e-\d)",
                                               parts[1])[:4])
    assert served * 1.05 < config["parity_tol"] < control / 1.05


def test_parity_prompts_fit_the_reference_into_set_up(tmp_path):
    checkpoint.build_bpe_tokenizer(str(tmp_path), 16160)
    from tokenizers import Tokenizer

    tk = Tokenizer.from_file(os.path.join(str(tmp_path), "tokenizer.json"))
    lens = [len(tk.encode(t, add_special_tokens=False).ids) + 1
            for t in _real_config()["parity_prompts"]]
    # long enough that near-tie selections average out, short enough
    # that the host's float32 reference stays under 150 s of set-up (it
    # took 219.7 s for 6903 tokens on the chip's host, 31.8 ms a token:
    # my chip run, PR 45, call 2): under 4.4 k tokens in all, two
    # 1024-token passes each
    assert 1024 < min(lens) and max(lens) <= 2048 and sum(lens) < 4400


@pytest.mark.parametrize("part", ["steps", "probe"])
def test_the_parity_tool_at_toy_widths(part, tmp_path, capsys, monkeypatch):
    from tools import mla_parity

    monkeypatch.setenv("LOCALAI_DECODE_KERNEL", "1")
    rc = mla_parity.main(["--tiny", "--" + part, "--scratch", str(tmp_path)])
    lines = capsys.readouterr().out.splitlines()
    out = json.loads(next(ln for ln in lines if ln.startswith("{")))
    verdicts = [ln for ln in lines if ln.startswith("VERDICT")]
    if part == "steps":
        assert rc == 0 and verdicts[0] == "VERDICT steps served: correct"
        assert len(verdicts) == 6 and all(
            v.endswith("not correct") for v in verdicts[1:])
        for path in ("prompt_path", "decode_path"):
            assert out[path]["served"]["max"] < out["tol"] / 100
            for k in ("int8_row", "fp8_row", "drop_kr", "drop_mscale",
                      "unnormed_c"):
                assert out[path][k]["median"] > out["tol"]
        assert out["jnp_reference"] < 2e-5
    else:
        assert out["tol"] == _real_config()["parity_tol"]
        assert max(out["fp8_row"]) > max(out["served"])
        assert out["int8_row"] != out["served"]


def test_the_cells_traffic_does_what_its_reason_says():
    from benchmark.lib import traffic

    man = M.load(H.ROOT)
    cell = M.cell(man, CELL)
    # ISSUE 45's one fallback: the existing mix as it stands, no mix
    # file of the cell's own (its first traffic, prompts 4096-4608,
    # spread too widely for the bound: PERF.md section 6)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "docs_closed", 1)
    assert not os.path.exists(M.traffic_path(H.ROOT, "docs4k_closed"))
    over = M.cell_overrides(H.ROOT, CELL)
    assert over["request"] == {"temperature": 1.0, "seed": 20260945,
                               "ignore_eos": True}
    config = _real_config()
    plain = traffic.load_mix(M.traffic_path(H.ROOT, cell["traffic"]), {})
    assert plain["request"]["temperature"] == 0
    mix = traffic.load_mix(M.traffic_path(H.ROOT, cell["traffic"]), over)
    assert mix["request"]["temperature"] == 1.0
    serving = config["serving"]
    prompt, out = mix["prompt_tokens"], mix["output_tokens"]
    assert (prompt["min"], prompt["max"]) == (2048, 2560)  # 5-6 steps of 512
    assert out == {"dist": "fixed", "value": 256}
    assert prompt["max"] + out["value"] <= serving["context_size"]
    assert mix["loop"] == "closed" and mix["clients"] == 32 \
        == 2 * serving["max_batch_slots"]
    assert mix["shared_prefix_tokens"] == 0
    assert (mix["warm_episode_s"], mix["drain_s"]) == (90, 40)
    # the same multiset of sizes whatever the seed
    a = traffic.schedule(mix, 3, man["run_seconds"])["requests"]
    b = traffic.schedule(mix, 2**31 + 5, man["run_seconds"])["requests"]
    assert sorted(r["prompt_tokens"] for r in a) == sorted(
        r["prompt_tokens"] for r in b)
    e2e = {m["name"] for m in M.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"tpot_p50_ms", "setup_s"}
    per = {m["name"] for m in M.metrics_of(man, "per_layer", CELL)}
    assert {"load_s", "warmup_s", "attn_kernel_share",
            "attn_kernel_roofline", "attn_kernel_roofline_counted",
            "expert_layer_share", "expert_layer_roofline",
            "expert_load_max_over_mean", "expert_held_assign_share",
            "kv_row_bytes_per_token"} <= per
    assert not per & {"decode_hbm_roofline", "attn_window_read_share",
                      "linear_attn_share", "linear_state_roofline",
                      "full_attn_roofline_counted", "state_snapshot_share",
                      "tier_host_ms_per_spill", "kv_spill_mb_per_request"}


# ------------------------------------------------------ the two readers

US = 1_000
LATENT = ("%latent_paged_attention.28 = f32[16,1,128,512]{3,2,1,0} "
          "custom-call(s32[16]{0} %broadcast.1)")
RAGGED_DOT = ("%ragged-dot-none.2 = bf16[128,7168]{1,0} custom-call("
              "bf16[128,2048]{1,0} %x)")
DENSE = "%fusion.431 = bf16[16,18432]{1,0} fusion(bf16[7168,18432]{1,0} %p)"
ROWS, CTX, LAYERS = 16.0, 4500.0, 6
FLOP_FLOOR_US = ROWS * CTX * 2 * 128 * 1088 / 197e12 * 1e6  # a layer-step
BYTE_FLOOR_US = ROWS * CTX * 1152 / 819e9 * 1e6
TOUCHED = 6.0
EXPERT_FLOOR_US = TOUCHED * 3 * 7168 * 2048 * 2 / 819e9 * 1e6


def _capture(kernel_us, expert_us=None):
    """4 decode token-steps of 6 layers (the latent kernel, then — in
    the 5 expert layers — a grouped matmul, then a dense op of 300 us)."""
    ops, t = [], 0
    for _ in range(4):
        for layer in range(LAYERS):
            dur = math.ceil(kernel_us * US)
            ops.append([LATENT, t, dur])
            t += dur
            if layer and expert_us:
                d2 = math.ceil(expert_us * US)
                ops.append([RAGGED_DOT, t, d2])
                t += d2
            ops.append([DENSE, t, 300 * US])
            t += 300 * US
    return {"other_planes": [], "planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_dispatch_decodek(7)", 0, t]]},
        {"name": "XLA Ops", "events": ops}]}]}


def _scrape(steps, share=True):
    out = {
        "engine_decode_steps_total": [({"model": "m"}, steps)],
        "engine_attn_context_tokens_total": [
            ({"model": "m", "kind": "decodek"}, steps * ROWS * CTX),
            ({"model": "m", "kind": "mixed"}, 12345.0)],
        "engine_expert_layer_steps_total": [
            ({"model": "m", "kind": "decodek"}, steps * 5)],
        "engine_experts_touched_total": [
            ({"model": "m", "kind": "decodek"}, steps * 5 * TOUCHED)],
    }
    if share:
        out["engine_expert_assignments_total"] = [
            ({"model": "m", "where": "held"}, steps * 40.0),
            ({"model": "m", "where": "absent"}, steps * 600.0)]
    return out


def _run(config, before, after, row_bytes=7680.0):
    polls = [{"engine_kv_pages_in_use_count": [({"model": "m"}, 160.0)]}]
    if row_bytes:
        polls[0]["engine_kv_row_bytes"] = [({"model": "m"}, row_bytes)]
    return {"config": config, "seconds": 51.0, "polls": polls,
            "profile": {"before": before, "after": after, "t_before": 47.5,
                        "duration": 3.0},
            "metrics_before": before, "metrics_after": after,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_readers_read_the_kernel_and_the_counters():
    config = _real_config()
    trace = _capture(2 * FLOP_FLOOR_US, 2 * EXPERT_FLOOR_US)
    run = _run(config, _scrape(100.0), _scrape(104.0))
    ev = lambda name: layer_metrics.evaluate(MDIR, name, trace, run)  # noqa: E731
    # the latent kernel's calls against the HBM roof at 1152 B a token
    # (the ridge: their FLOPs take 1.005 x as long at the MXU's peak)
    assert ev("attn_kernel_roofline_counted") == pytest.approx(
        50.0 * BYTE_FLOOR_US / FLOP_FLOOR_US, rel=1e-3)
    assert ev("expert_held_assign_share") == pytest.approx(6.25)
    assert ev("kv_row_bytes_per_token") == 7680.0
    # the accepted readers find the held experts through the model file
    assert ev("expert_layer_roofline") == pytest.approx(50.0, rel=1e-3)
    assert 0 < ev("attn_kernel_share") < 100 and ev("expert_layer_share") > 0


def test_a_share_of_a_roof_cannot_pass_it():
    """The kernel at exactly the time its FLOPs take — the longer of
    its two floors (the ridge: 242 FLOP/B against the chip's 240.5) —
    reads just under 100 % of the HBM roof, and the expert layer at the
    time its bytes take 100 %, not more."""
    config = _real_config()
    trace = _capture(FLOP_FLOOR_US, EXPERT_FLOOR_US)
    run = _run(config, _scrape(100.0), _scrape(104.0))
    b = layer_metrics.evaluate(MDIR, "attn_kernel_roofline_counted", trace,
                               run)
    assert 98.0 < b <= 100.0, b
    e = layer_metrics.evaluate(MDIR, "expert_layer_roofline", trace, run)
    assert 99.0 < e <= 100.0 + 1e-6, e


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent of the PR that brought them has neither the counter
    nor the gauge."""
    config = _real_config()
    trace = _capture(2 * FLOP_FLOOR_US)
    bare = _run(config, _scrape(100.0, share=False),
                _scrape(104.0, share=False), row_bytes=None)
    for name in ("expert_held_assign_share", "kv_row_bytes_per_token"):
        assert layer_metrics.evaluate(MDIR, name, trace, bare) is None
    run = _run(config, _scrape(100.0), _scrape(104.0))
    for name in ("expert_held_assign_share", "kv_row_bytes_per_token"):
        assert layer_metrics.evaluate(MDIR, name, None, dict(
            run, metrics_before=None, metrics_after=None, profile=None,
            polls=[])) is None


def test_the_two_readers_are_listed_for_the_cell_alone():
    by = {m["name"]: m for m in M.load(H.ROOT)["per_layer"]}
    for name, layer, source in (
            ("expert_held_assign_share", "expert layer", "program_counter"),
            ("kv_row_bytes_per_token", "KV pool", "program_counter")):
        assert layer_metrics.find(MDIR, name)
        assert (by[name]["moves"], by[name]["layer"], by[name]["source"]) \
            == ("tpot_p50_ms", layer, source)
        assert by[name]["workloads"][0] == CELL
        assert not {"mistral7b_batch_closed", "trinitymini_docs_closed",
                    "olmohybrid_docs_closed"} & set(by[name]["workloads"])
    assert CELL not in by["decode_hbm_roofline"]["workloads"]


# ------------------------------------------- the harness, rehearsed


def test_rehearsal_of_the_cell_on_the_cpu(tmp_path_factory):
    """The whole harness against a tiny ``deepseek_v3`` configuration
    that holds a share (experts 8..11 of 16), past the device gate: the
    checkpoint the model file describes loads in the server,
    /v1/embeddings agrees with the plain reference, the repeated long
    prompt reuses latent pages (the cache probe's clause of
    ``correct``), and what the new readers read is on /metrics."""
    import time

    from benchmark import run as B
    from benchmark.lib.children import CHILDREN

    root = H.copy_benchmark(str(tmp_path_factory.mktemp("checkout")))
    harness = {k: H.TINY[k] for k in (
        "source", "chips", "mesh", "reduced", "serving", "deployment",
        "weights_seed", "parity_prompts", "parity_tol",
        "parity_tol_reason")}
    config = dict(TINY_DS, **harness)
    config["parity_tol"] = 0.25  # bfloat16 at a width of 64
    H.add_cell(root, config_name="tiny_ds", config=config,
               mix_name="tiny_closed_ds", mix=H.TINY_CLOSED,
               cell_name="tiny_ds_closed", join=None)
    cpu = {"platform": "cpu", "attention_path": "paged_xla_gather",
           "kernel_ineligible": "platform cpu: Mosaic compiles on tpu only"}
    t0 = time.perf_counter()
    try:
        res = B.run_cell(root, "tiny_ds_closed", 2**31 + 45, 4.0, True,
                         t0, expect=cpu, probe=False)
    finally:
        CHILDREN.stop_all()
        models.use(H.MODELS)
    assert res["failed_clauses"] == [] and res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    got = res["metrics"]
    # 256 lanes x 2 B x 3 layers: the cache stays latent
    assert got["kv_row_bytes_per_token"]["value"] == 256 * 2 * 3
    assert 0 < got["expert_held_assign_share"]["value"] < 100
    assert got["expert_load_max_over_mean"]["value"] >= 1.0
    # no chip here: nothing that reads a device trace is reported
    for name in ("attn_kernel_roofline_counted", "expert_layer_roofline",
                 "decode_step_dev_ms"):
        assert name not in got
