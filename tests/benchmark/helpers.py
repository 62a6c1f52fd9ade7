"""Shared pieces of the benchmark's tests: a tiny configuration, a tiny
mix, and a temp copy of the benchmark as a checkout of its own. Nothing
here imports JAX."""

from __future__ import annotations

import contextlib
import json
import os
import shutil

from benchmark.lib import models as _models

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# where BENCHMARK.json and benchmark/'s data files are read from: the
# repo, or a temp copy with one more cell in it (BM_TESTS_ROOT, set by
# test_bm_manifest.py's N-cell proof, which runs these tests there)
ROOT = os.environ.get("BM_TESTS_ROOT") or REPO
# the model files are data of that root too: the loader looks there
MODELS = os.path.join(ROOT, "benchmark", "models")
_models.use(MODELS)

PARITY_PROMPTS = [
    "A paged cache hands out attention memory page by page.",
    "Continuous batching admits a request as soon as a slot is free.",
]

TINY = {
    "architectures": ["MistralForCausalLM"], "model_type": "mistral",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-05, "sliding_window": None,
    "max_position_embeddings": 4096, "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "bos_token_id": 510, "eos_token_id": 511,
    "source": "tests/benchmark", "chips": 1, "mesh": None, "reduced": [],
    "serving": {"backend": "jax-llm", "context_size": 4096,
                "max_batch_slots": 4, "embeddings": True},
    "assumed": {"served_bytes_per_param": {"dense": 2, "experts": 2},
                "kv_bytes_per_value": 2, "kv_page_tokens": 256,
                "kv_pool_pages": 64},
    "deployment": "a toy for the CPU rehearsal", "weights_seed": 0,
    "parity_prompts": PARITY_PROMPTS, "parity_tol": 0.05,
    "parity_tol_reason": "bf16 activations against float32",
}
TINY_MOE = dict(TINY, model_type="mixtral",
                architectures=["MixtralForCausalLM"],
                num_local_experts=4, num_experts_per_tok=2)

# model types the harness does not know, each one file under data/models/
# that a test copies into a temp benchmark (or points the loader at)
FIXTURE_MODELS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "data", "models")
# a type the program loads and benchmark/lib has never heard of: layers
# of two kinds (layer 0 a plain MLP), q/k/v biases, a router named
# mlp.gate, a shared expert (data/models/qwen2_moe.py)
TINY_QWEN_MOE = dict(
    {k: v for k, v in TINY.items() if k != "head_dim"},
    model_type="qwen2_moe", architectures=["Qwen2MoeForCausalLM"],
    num_hidden_layers=3, mlp_only_layers=[0], decoder_sparse_step=1,
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=128, norm_topk_prob=False)
# a chip's share of an expert layer, as a configuration file states it:
# 4 experts held of 32 published, ids 8-11 (data/models/held_experts.py)
TINY_HELD = {
    "model_type": "held_experts", "hidden_size": 64, "kv_lora_rank": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 2, "first_k_dense_replace": 1, "vocab_size": 512,
    "n_routed_experts": 4, "n_routed_experts_published": 32,
    "expert_id_base": 8,
}

_REQ = {"temperature": 0, "ignore_eos": True}
_NOTES = "toy sizes for the CPU tests: nothing here stands for a deployment"
TINY_OPEN = {
    "loop": "open", "rate_rps": 3,
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                      "min": 8, "max": 100},
    "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "preroll_s": 1, "drain_s": 60,
    "endpoint": "/v1/chat/completions", "request": _REQ, "notes": _NOTES,
}
TINY_CLOSED = {
    "loop": "closed", "clients": 3,
    "prompt_tokens": {"dist": "uniform", "min": 8, "max": 40},
    "output_tokens": {"dist": "fixed", "value": 8},
    "preroll_s": 8, "warm_episode_s": 2, "ramp_s": 0.5, "drain_s": 60,
    "endpoint": "/v1/chat/completions", "request": _REQ, "notes": _NOTES,
}


def copy_benchmark(dst: str) -> str:
    """A temp checkout: BENCHMARK.json + benchmark/ copied (no cache),
    the program linked. -> its root."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(dst, "benchmark"),
        ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "localai_tfp_tpu"),
               os.path.join(dst, "localai_tfp_tpu"))
    return dst


def add_model_file(root: str, model_type: str,
                   as_type: "str | None" = None) -> None:
    """What a later PR does for a new ``model_type``: one new file (the
    fixture's, under the name ``as_type`` where given)."""
    dst = os.path.join(root, "benchmark", "models",
                       (as_type or model_type) + ".py")
    assert not os.path.exists(dst)
    shutil.copy(os.path.join(FIXTURE_MODELS, model_type + ".py"), dst)


@contextlib.contextmanager
def using_models(models_dir: str):
    """The loader pointed at another ``models/`` for a test's length."""
    before = _models.use(models_dir)
    try:
        yield _models
    finally:
        _models.use(before)


def snapshot(root: str) -> dict:
    """Every file under the copy's benchmark/, by content."""
    out = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        for fn in files:
            p = os.path.join(d, fn)
            with open(p, "rb") as f:
                out[p] = f.read()
    return out


def edited(before: dict) -> list:
    """The files of a ``snapshot`` that no longer read as they did."""
    out = []
    for p, blob in before.items():
        with open(p, "rb") as f:
            if f.read() != blob:
                out.append(p)
    return out


def add_cell(root: str, *, config_name: str, config: dict, mix_name: str,
             mix: dict, cell_name: str, join: "list | None") -> None:
    """What a later PR does to add a cell: new files, appended entries
    (``join``: the metrics with a ``workloads`` list the cell joins;
    None = every one of them, as a cell that reports ``tpot_p50_ms``
    under the same layers does)."""
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs", config_name + ".json"), "w") as f:
        json.dump(config, f)
    # the source's own numbers, beside the configuration (a toy is its
    # own source)
    with open(os.path.join(bdir, "configs",
                           config_name + ".published.json"), "w") as f:
        json.dump({"source": config["source"], "config": {
            k: v for k, v in config.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}}, f)
    with open(os.path.join(bdir, "traffic", mix_name + ".json"), "w") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["configs"].append({
        "name": config_name, "source": "tests/benchmark",
        "file": f"benchmark/configs/{config_name}.json", "reduced": [],
        "why": "toy"})
    man["workloads"].append({
        "name": cell_name, "config": config_name, "traffic": mix_name,
        "chips": 1, "why": "toy"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m and (join is None or m["name"] in join):
            m["workloads"].append(cell_name)
    with open(path, "w") as f:
        json.dump(man, f)
