"""The ``afmoe`` model file (benchmark/models/afmoe.py), the configuration
``trinity-mini-pp4-stage`` and the four per-layer readers beside it (data
a later ``benchmark`` PR lists: the cell missed its noise gate):
its tensor table loads through models/hf_loader.py, its plain reference
agrees with the program, each way of breaking it is caught, its byte
counts are the issue's arithmetic, and each reader reads what it says —
and NOTHING (None) from a program that lacks what it reads, as the
parent of the PR that brought it does. JAX is imported inside the tests
only."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import checkpoint, layer_metrics, models, reference, roofline
from benchmark.lib import trace as T

from . import helpers as H

MDIR = os.path.join(H.ROOT, "benchmark", "layer_metrics")
TINY_AFMOE = {
    "architectures": ["AfmoeForCausalLM"], "model_type": "afmoe",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 4, "num_dense_layers": 1, "vocab_size": 512,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "sliding_window": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "mup_enabled": True, "rope_theta": 10000, "rope_scaling": None,
    "rms_norm_eps": 1e-05, "max_position_embeddings": 4096,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "assumed": {"served_bytes_per_param": {"dense": 2, "experts": 2},
                "kv_bytes_per_value": 2, "kv_page_tokens": 256},
}
IDS = [list(range(7, 47)), [500, 3, 3, 9, 250, 17, 101, 44, 44, 2] * 6]
TOL = 0.01


def _real_config():
    with open(os.path.join(H.ROOT, "benchmark", "configs",
                           "trinity-mini-pp4-stage.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("afmoe"))
    with H.using_models(H.MODELS):
        checkpoint.write_hf_checkpoint(
            d, checkpoint.hf_config(TINY_AFMOE), seed=3, threads=2)
    return d


def test_model_file_loads_and_names_what_the_loader_reads():
    mod = models.load("afmoe", H.MODELS)
    assert all(hasattr(mod, k) for k in models.NEEDS)
    assert "afmoe" in models.known(H.MODELS)
    rows = mod.tensors(TINY_AFMOE)
    names = {n: (shape, dt, init) for _s, n, shape, dt, init in rows}
    assert len(names) == len(rows)
    lp = "model.layers.{}."
    # a leading dense layer, an expert layer: what each holds
    assert names[lp.format(0) + "mlp.up_proj.weight"][0] == (128, 64)
    assert lp.format(0) + "mlp.router.gate.weight" not in names
    assert names[lp.format(1) + "mlp.router.gate.weight"][0] == (8, 64)
    assert names[lp.format(1) + "mlp.expert_bias"] == ((8,), "F32", "embed")
    assert names[lp.format(3) + "mlp.experts.7.down_proj.weight"][0] == (64, 32)
    assert names[lp.format(2) + "mlp.shared_experts.gate_proj.weight"][0] == (
        32, 64)
    assert lp.format(1) + "mlp.up_proj.weight" not in names
    for i in range(4):
        assert names[lp.format(i) + "self_attn.gate_proj.weight"][0] == (64, 64)
        assert names[lp.format(i) + "self_attn.q_norm.weight"][0] == (16,)
        for n in ("input_layernorm", "post_attention_layernorm",
                  "pre_mlp_layernorm", "post_mlp_layernorm"):
            assert names[lp.format(i) + n + ".weight"][2] == "ones"
    assert {s for s, *_ in rows} == set(range(5))


def test_reference_matches_the_program(ckpt):
    import jax.numpy as jnp

    from localai_tfp_tpu.models.hf_loader import load_params
    from localai_tfp_tpu.models.transformer import KVCache, forward_hidden

    config = checkpoint.hf_config(TINY_AFMOE)
    spec, params = load_params(ckpt, dtype=jnp.float32)
    assert (spec.n_layers, spec.n_dense_layers, spec.n_experts) == (4, 1, 8)
    with H.using_models(H.MODELS):
        want = reference.pooled(ckpt, config, IDS)
    for ids, w in zip(IDS, want):
        hidden, _ = forward_hidden(
            spec, params, jnp.asarray([ids], jnp.int32),
            jnp.zeros((1,), jnp.int32),
            KVCache.create(spec, 1, 128, jnp.float32),
            jnp.zeros((1,), jnp.int32))
        got = np.asarray(hidden[0], np.float32).mean(axis=0)
        assert reference.rel_l2(got, w) < 2e-4  # float32 both sides


@pytest.mark.parametrize("mutate", [
    {"zero_layer": 0}, {"zero_layer": 2}, {"rope_on_full": True},
    {"drop_bias": True}, {"bias_in_weight": True}, {"drop_gate": True},
    {"window": 4096}], ids=lambda m: next(iter(m)))
def test_tolerance_catches_a_broken_model(ckpt, mutate):
    config = checkpoint.hf_config(TINY_AFMOE)
    with H.using_models(H.MODELS):
        want = reference.pooled(ckpt, config, IDS)
        broken = reference.pooled(ckpt, config, IDS, mutate)
    worst = max(reference.rel_l2(b, w) for b, w in zip(broken, want))
    assert worst > TOL, (mutate, worst)


def test_only_the_selected_experts_are_read(ckpt):
    """The reference evaluates the experts the routing picked: a
    sequence of 3 tokens over 8 experts top-2 reads at most 6 of a
    layer's experts."""
    read = []

    class Spy(reference.Shards):
        def get(self, name):
            read.append(name)
            return super().get(name)

    mod = models.load("afmoe", H.MODELS)
    mod.forward_hidden(Spy(ckpt), checkpoint.hf_config(TINY_AFMOE),
                       [[5, 6, 7]])
    experts = {n.split(".experts.")[1].split(".")[0] for n in read
               if ".layers.1.mlp.experts." in n}
    assert 2 <= len(experts) <= 6
    assert any(".layers.1.mlp.shared_experts." in n for n in read)
    assert not any(".layers.0.mlp.experts." in n for n in read)


def test_grouped_selection_and_other_scores_are_refused(ckpt):
    mod = models.load("afmoe", H.MODELS)
    sh = reference.Shards(ckpt)
    for bad in ({"n_group": 2}, {"score_func": "softmax"}):
        with pytest.raises(NotImplementedError):
            mod.forward_hidden(sh, dict(checkpoint.hf_config(TINY_AFMOE),
                                        **bad), [[1, 2]])


def test_bytes_a_decode_step_has_to_read_are_the_issues_arithmetic():
    config = _real_config()
    mod = models.load("afmoe", H.MODELS)
    p = mod.param_counts(config)
    assert p["attn"] == 8 * 27_262_976  # q, k, v, o and the output gate
    assert p["dense_mlp"] == 2 * 37_748_736
    assert p["expert"] == 6_291_456 and p["head"] == 200192 * 2048
    assert mod.expert_layers(config) == 6
    assert mod.expert_bytes(config) == 12_582_912
    # 16 rows of top-8 over 128 touch 82.4 experts a layer
    assert mod.experts_touched(config, 16) == pytest.approx(82.4, abs=0.1)
    assert mod.experts_touched(config, 1) == pytest.approx(8.0)
    # under uniform routing a 16-row step reads 7.7 GB, 80 % of it
    # routed experts (ISSUE 38's sizing) ...
    once = (p["attn"] + p["dense_mlp"] + p["shared"] + p["router"]
            + p["head"]) * 2
    routed = 6 * mod.experts_touched(config, 16) * 12_582_912
    assert once + routed == pytest.approx(7.7e9, rel=0.01)
    assert routed / (once + routed) == pytest.approx(0.80, abs=0.01)
    # ... which is what decode_weight_bytes counts, as ISSUE 38 defined it
    assert roofline.decode_weight_bytes(config, 16) == pytest.approx(
        once + routed)
    assert roofline.decode_weight_bytes(config, 1) == pytest.approx(
        once + 6 * 8 * 12_582_912)
    # the whole stage as held: 5.985 B parameters, 11.97 GB in bfloat16
    held = (p["attn"] + p["dense_mlp"] + p["shared"] + p["router"]
            + 6 * 128 * p["expert"] + 2 * p["head"])
    assert held == pytest.approx(5.985e9, rel=0.002)
    # K and V of 4 heads x 128, bfloat16, 8 layers; the pool as assumed
    assert roofline.kv_bytes_per_token(config) == 2 * 512 * 2 * 8
    assert roofline.kv_bytes_per_token(config, layers=1) == 2048
    pool = config["assumed"]["kv_pool_pages"] * 256 \
        * roofline.kv_bytes_per_token(config)
    assert pool == pytest.approx(1.07e9, rel=0.01)


def test_configuration_states_its_cut_and_what_it_assumed():
    config = _real_config()
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert config["serving"]["quantization"] == "none"
    assert config["serving"]["kv_cache_dtype"] == "bfloat16"
    assert len(config["assumed"]["recalled_not_read"]) >= 8
    assert "four pipeline stages" in config["deployment"]
    assert len(config["parity_prompts"]) == 4
    assert 0 < config["parity_tol"] < 0.03


def test_parity_prompts_cross_the_window(tmp_path):
    checkpoint.build_bpe_tokenizer(str(tmp_path), 200192)
    from tokenizers import Tokenizer

    tk = Tokenizer.from_file(os.path.join(str(tmp_path), "tokenizer.json"))
    lens = [len(tk.encode(t, add_special_tokens=False).ids)
            for t in _real_config()["parity_prompts"]]
    # all four are long: a pooled state of few tokens is decided by
    # which near-tie of the router flipped, not by the precision
    assert min(lens) >= 2304 and max(lens) + 1 <= 4096


def test_the_cells_traffic_does_what_its_reason_says():
    """What the cell exists for, held as ranges against the
    configuration it runs (never one size by equality): every context
    is past the window from the first decoded token and fits a slot,
    the loop keeps every slot busy, and the requests are sampled under
    one fixed seed."""
    from benchmark.lib import manifest as M
    from benchmark.lib import traffic

    config = _real_config()
    mix = traffic.load_mix(
        M.traffic_path(H.ROOT, "docs_closed"),
        M.cell_overrides(H.ROOT, "trinitymini_docs_closed"))
    serving = config["serving"]
    prompt, out = mix["prompt_tokens"], mix["output_tokens"]
    assert prompt["min"] >= config["sliding_window"]
    assert out["dist"] == "fixed"  # every seed the same work
    assert prompt["max"] + out["value"] <= serving["context_size"]
    assert mix["loop"] == "closed"
    assert mix["clients"] >= serving["max_batch_slots"]
    req = mix["request"]
    assert req["temperature"] > 0 and "seed" in req and req["ignore_eos"]
    assert mix["endpoint"] == "/v1/chat/completions"


# ------------------------------------------------ the four readers

MS = 1_000_000
KERNEL = ("%ragged_paged_attention.13 = f32[16,4,8,128]{3,2,1,0} "
          "custom-call(s32[16]{0} %broadcast.1)")
RAGGED = ("%ragged-dot-none.3 = bf16[128,1024]{1,0} custom-call(s32[1]{0} "
          "%get-tuple-element.1, bf16[128,2048]{1,0} %x)")
RAGGED_META = ("%ragged-dot-metadata.1 = (s32[129]{0}, s32[128]{0}) "
               "custom-call(s32[128]{0} %gs)")
COMBINE = ("%fusion.91 = f32[16,2048]{1,0} fusion(bf16[16,8,2048]{2,1,0} "
           "%gather.4, f32[16,8]{1,0} %w)")
DENSE = "%fusion.431 = bf16[16,6144]{1,0} fusion(bf16[2048,6144]{1,0} %p)"
LAYERS, EXPERT_LAYERS = 8, 6


def _program(name, t0, steps):
    """One decode-only or mixed program of ``steps`` token-steps: a
    layer = kernel 1 ms + dense 1 ms; an expert layer adds set-up
    0.1 ms + 3 grouped matmuls of 1 ms + the combine 0.5 ms."""
    ops, t = [], t0
    for _ in range(steps):
        for layer in range(LAYERS):
            ops += [[KERNEL, t, 1 * MS], [DENSE, t + 1 * MS, 1 * MS]]
            t += 2 * MS
            if layer >= LAYERS - EXPERT_LAYERS:
                ops.append([RAGGED_META, t, MS // 10])
                ops += [[RAGGED, t + MS // 10 + i * MS, 1 * MS]
                        for i in range(3)]
                ops.append([COMBINE, t + MS // 10 + 3 * MS, MS // 2])
                t += 3 * MS + MS // 10 + MS // 2
    return [f"{name}(7)", t0, t - t0], ops, t


@pytest.fixture(scope="module")
def capture():
    m1, o1, t = _program("jit_dispatch_decodek", 0, 4)
    m2, o2, t = _program("jit_dispatch_mixed", t + 5 * MS, 1)
    return {"other_planes": [], "planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [m1, m2]},
        {"name": "XLA Ops", "events": o1 + o2}]}]}


def _scrape(touched, layer_steps, tokens=None, read=None, held=None):
    out = {
        "engine_experts_touched_total": [
            ({"model": "m", "kind": "decodek"}, touched),
            ({"model": "m", "kind": "mixed"}, 999.0)],
        "engine_expert_layer_steps_total": [
            ({"model": "m", "kind": "decodek"}, layer_steps),
            ({"model": "m", "kind": "mixed"}, 7.0)],
    }
    if tokens is not None:
        out["engine_expert_tokens_total"] = [
            ({"model": "m", "expert": str(e)}, v)
            for e, v in enumerate(tokens)]
    if read is not None:
        out["engine_attn_context_tokens_total"] = [
            ({"model": "m", "kind": "decodek"}, read)]
        out["engine_attn_context_held_tokens_total"] = [
            ({"model": "m", "kind": "decodek"}, held)]
    return out


def test_expert_readers_read_the_grouped_matmuls_and_the_counters(capture):
    config = _real_config()
    before = _scrape(1000.0, 60.0, [10.0] * 128, 100.0, 200.0)
    after = _scrape(1000.0 + 24 * 82.0, 60.0 + 24, [10.0 + 3] * 127 + [19.0],
                    100.0 + 850.0, 200.0 + 1000.0)
    run = {"config": config, "seconds": 51.0,
           "profile": {"before": before, "after": after, "t_before": 47.5,
                       "duration": 3.0},
           "metrics_before": before, "metrics_after": after,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    ev = lambda n: layer_metrics.evaluate(MDIR, n, capture, run)  # noqa
    # 5 token-steps: 8 layers x 2 ms + 6 x (3.6 ms), of which the
    # grouped matmuls with their set-up and the combine are 6 x 3.6
    busy = 5 * (16 + 6 * 3.6)
    assert ev("expert_layer_share") == pytest.approx(100 * 5 * 6 * 3.6 / busy)
    # decode-only: 4 steps x 6 layers, each 82 experts of 12.58 MB at
    # 819 GB/s = 1.26 ms, against 3.1 ms of ragged-dot ops
    floor = 82 * 12_582_912 / 819e9
    assert ev("expert_layer_roofline") == pytest.approx(
        100 * floor / 3.1e-3, rel=1e-6)
    assert 0 < ev("expert_layer_roofline") < 100
    # one expert took 9 where the others took 3
    assert ev("expert_load_max_over_mean") == pytest.approx(
        9 / ((127 * 3 + 9) / 128))
    assert ev("attn_window_read_share") == pytest.approx(85.0)


def test_readers_find_nothing_in_a_program_without_the_counters(capture):
    """The parent of the PR that brought them: no expert counters, no
    held-context counter, no grouped matmul in its capture."""
    config = _real_config()
    empty = {"engine_attn_context_tokens_total": [
        ({"model": "m", "kind": "decodek"}, 5.0)]}
    run = {"config": config, "seconds": 51.0,
           "profile": {"before": empty, "after": empty, "t_before": 47.5,
                       "duration": 3.0},
           "metrics_before": empty, "metrics_after": empty,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in ("expert_layer_roofline", "expert_load_max_over_mean",
                 "attn_window_read_share"):
        assert layer_metrics.evaluate(MDIR, name, capture, run) is None
    bare = {"other_planes": [], "planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_dispatch_decodek(1)", 0,
                                            4 * MS]]},
        {"name": "XLA Ops", "events": [[KERNEL, 0, 1 * MS],
                                       [DENSE, 1 * MS, 3 * MS]]}]}]}
    assert layer_metrics.evaluate(MDIR, "expert_layer_share", bare,
                                  run) is None
    # and a type without experts has none to name
    with open(os.path.join(H.ROOT, "benchmark", "configs",
                           "mistral-7b-instruct-v0.3.json")) as f:
        dense = json.load(f)
    for name in ("expert_layer_share", "expert_layer_roofline"):
        assert layer_metrics.evaluate(MDIR, name, capture,
                                      dict(run, config=dense)) is None
    for name in ("expert_layer_share", "expert_layer_roofline",
                 "expert_load_max_over_mean", "attn_window_read_share"):
        assert layer_metrics.evaluate(MDIR, name, None, dict(
            run, metrics_before=None, metrics_after=None,
            profile=None)) is None


def test_the_four_readers_are_found_by_name():
    """Each is a file found by its name, and the manifest entry that
    lists it moves ``tpot_p50_ms`` in the cell PR 38 brought."""
    from benchmark.lib import manifest as M

    by = {m["name"]: m for m in M.load(H.ROOT)["per_layer"]}
    for name in ("expert_layer_share", "expert_layer_roofline",
                 "expert_load_max_over_mean", "attn_window_read_share"):
        assert T is not None and layer_metrics.find(MDIR, name)
        assert by[name]["moves"] == "tpot_p50_ms"
        assert "trinitymini_docs_closed" in by[name]["workloads"]


# ------------------------------------------- the harness, rehearsed


def test_rehearsal_of_an_afmoe_cell_on_the_cpu(tmp_path_factory):
    """The whole harness against a tiny ``afmoe`` configuration, past
    the device gate: the checkpoint the model file describes loads in
    the server, /v1/embeddings agrees with the plain reference, the
    cell's own file overrides the mix's request (sampled, seeded), and
    the counters the new readers read are on /metrics."""
    import time

    from benchmark import run as B
    from benchmark.lib.children import CHILDREN

    root = H.copy_benchmark(str(tmp_path_factory.mktemp("checkout")))
    harness = {k: H.TINY[k] for k in (
        "source", "chips", "mesh", "reduced", "serving", "deployment",
        "weights_seed", "parity_prompts", "parity_tol",
        "parity_tol_reason")}
    config = dict(TINY_AFMOE, **harness)
    config["assumed"] = dict(H.TINY["assumed"])
    H.add_cell(root, config_name="tiny_afmoe", config=config,
               mix_name="tiny_closed_afmoe", mix=H.TINY_CLOSED,
               cell_name="tiny_afmoe_closed", join=None)
    # the four readers, listed as the PR that admits the cell lists them
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["per_layer"] += [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": "tpot_p50_ms",
         "workloads": ["tiny_afmoe_closed"]}
        for name, unit, better, source, layer in (
            ("expert_layer_share", "%", "lower", "device_trace",
             "expert layer"),
            ("expert_layer_roofline", "%", "higher", "device_trace",
             "expert layer"),
            ("expert_load_max_over_mean", "ratio", "lower",
             "program_counter", "expert layer"),
            ("attn_window_read_share", "%", "lower", "program_counter",
             "attention kernel"))
        if name not in {m["name"] for m in man["per_layer"]}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f, indent=1)
    os.makedirs(os.path.join(root, "benchmark", "cells"), exist_ok=True)
    with open(os.path.join(root, "benchmark", "cells",
                           "tiny_afmoe_closed.json"), "w") as f:
        json.dump({"request": {"temperature": 1.0, "seed": 20260938,
                               "ignore_eos": True}}, f)
    cpu = {"platform": "cpu", "attention_path": "paged_xla_gather",
           "kernel_ineligible": "platform cpu: Mosaic compiles on tpu only"}
    t0 = time.perf_counter()
    try:
        cell = B.Cell(root, "tiny_afmoe_closed", 2**31 + 11, True, "t-afmoe",
                      cpu, False)
        assert cell.mix["request"]["temperature"] == 1.0
        res = B.run_cell(root, "tiny_afmoe_closed", 2**31 + 11, 4.0, True,
                         t0, expect=cpu, probe=False)
    finally:
        CHILDREN.stop_all()
        models.use(H.MODELS)
    assert res["failed_clauses"] == [] and res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    got = res["metrics"]
    # near-uniform routing of a few hundred tokens over 8 experts
    assert 1.0 <= got["expert_load_max_over_mean"]["value"] < 4.0
    # prompts of 8-40 tokens + 8 out against a window of 16
    assert 40.0 < got["attn_window_read_share"]["value"] < 100.0
    # no chip here: nothing that reads a device trace is reported
    for name in ("expert_layer_share", "expert_layer_roofline",
                 "decode_step_dev_ms"):
        assert name not in got
