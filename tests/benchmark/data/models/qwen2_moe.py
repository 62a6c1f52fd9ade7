"""model_type ``qwen2_moe`` — a FIXTURE of the tests, never a
configuration of BENCHMARK.json: a type the program loads
(models/llm_spec.py, models/hf_loader.py) and ``benchmark/lib`` has
never heard of, brought into a temp copy of the benchmark as this one
new file. Its checkpoint needs what a table of one layer kind could not
say: layers of two kinds by index (``mlp_only_layers``: a plain MLP;
the others a mixture), 1-D tensors that are no layer norm (the q, k, v
biases), a router under a name of its own (``mlp.gate``), a shared
expert and its [1, D] gate.

Forward pass after HF ``modeling_qwen2_moe`` (Qwen1.5-MoE): GQA with
q/k/v biases; a sparse layer is softmax over all experts, the top k
kept (renormalised only where ``norm_topk_prob``), plus the shared
expert scaled by sigmoid(x . g). Departures: none in the mathematics.

``mutate`` (tests only): ``zero_layer``, ``rope_theta``, and
``drop_shared`` (the shared expert left out).
"""

import numpy as np

from benchmark.lib import reference as R
from benchmark.lib import roofline

# two prefixes: the program's kernel, and a second name that only the
# tests' synthetic capture carries (the tuple is data of this file)
ATTENTION_KERNELS = ("ragged_paged_attention", "toy_latent_attention")


def _dims(config: dict) -> dict:
    d_head = config["hidden_size"] // config["num_attention_heads"]
    return {"d": config["hidden_size"], "f": config["intermediate_size"],
            "fm": config["moe_intermediate_size"],
            "fs": config["shared_expert_intermediate_size"],
            "q": config["num_attention_heads"] * d_head,
            "kv": config["num_key_value_heads"] * d_head,
            "dh": d_head, "e": config["num_experts"],
            "k": config["num_experts_per_tok"],
            "v": config["vocab_size"], "L": config["num_hidden_layers"]}


def _dense_layers(config: dict) -> set:
    step = int(config.get("decoder_sparse_step") or 1)
    only = set(config.get("mlp_only_layers") or [])
    return {i for i in range(config["num_hidden_layers"])
            if i in only or (i + 1) % step != 0}


def _mlp(prefix: str, width: int, d: int) -> list:
    return [(prefix + "gate_proj.weight", (width, d)),
            (prefix + "up_proj.weight", (width, d)),
            (prefix + "down_proj.weight", (d, width))]


def tensors(config: dict) -> list:
    m, dense, out = _dims(config), _dense_layers(config), []
    for i in range(m["L"]):
        lp = f"model.layers.{i}."
        rows = [(f"self_attn.{p}_proj.weight", shape) for p, shape in (
            ("q", (m["q"], m["d"])), ("k", (m["kv"], m["d"])),
            ("v", (m["kv"], m["d"])), ("o", (m["d"], m["q"])))]
        rows += [(f"self_attn.{p}_proj.bias", (n,)) for p, n in (
            ("q", m["q"]), ("k", m["kv"]), ("v", m["kv"]))]
        if i in dense:
            rows += _mlp("mlp.", m["f"], m["d"])
        else:
            rows.append(("mlp.gate.weight", (m["e"], m["d"])))
            for e in range(m["e"]):
                rows += _mlp(f"mlp.experts.{e}.", m["fm"], m["d"])
            rows += _mlp("mlp.shared_expert.", m["fs"], m["d"])
            rows.append(("mlp.shared_expert_gate.weight", (1, m["d"])))
        out += [(i, lp + n, shape, "BF16", "matrix") for n, shape in rows]
        out += [(i, lp + n + ".weight", (m["d"],), "BF16", "ones")
                for n in ("input_layernorm", "post_attention_layernorm")]
    return out + [
        (m["L"], "model.embed_tokens.weight", (m["v"], m["d"]), "BF16",
         "embed"),
        (m["L"], "model.norm.weight", (m["d"],), "BF16", "ones"),
        (m["L"], "lm_head.weight", (m["v"], m["d"]), "BF16", "matrix")]


def forward_hidden(shards, config: dict, ids_list: list,
                   mutate: "dict | None" = None) -> list:
    mutate = mutate or {}
    m, dense = _dims(config), _dense_layers(config)
    eps = float(config["rms_norm_eps"])
    theta = float(mutate.get("rope_theta", config["rope_theta"]))
    embed = shards.get("model.embed_tokens.weight")
    xs = [embed[np.asarray(ids)] for ids in ids_list]
    for i in range(m["L"]):
        if i == mutate.get("zero_layer"):
            continue
        g = lambda n: shards.get(f"model.layers.{i}." + n)  # noqa: E731
        wq, wk, wv, wo = (g(f"self_attn.{p}_proj.weight") for p in "qkvo")
        bias = tuple(g(f"self_attn.{p}_proj.bias") for p in "qkv")
        ln1 = g("input_layernorm.weight")
        ln2 = g("post_attention_layernorm.weight")
        xs = [x + R.attention(R.rms_norm(x, ln1, eps), wq, wk, wv, wo,
                              config["num_attention_heads"],
                              config["num_key_value_heads"], m["dh"], theta,
                              bias=bias) for x in xs]

        def mlp(prefix):
            return tuple(g(f"{prefix}{p}_proj.weight")
                         for p in ("gate", "up", "down"))

        if i in dense:
            w = mlp("mlp.")
            xs = [x + R.swiglu(R.rms_norm(x, ln2, eps), *w) for x in xs]
            continue
        lens = [x.shape[0] for x in xs]
        flat = np.concatenate([R.rms_norm(x, ln2, eps) for x in xs])
        y = R.moe(flat, g("mlp.gate.weight"),
                  lambda e: mlp(f"mlp.experts.{e}."), m["k"],
                  renormalise=bool(config.get("norm_topk_prob", False)))
        if not mutate.get("drop_shared"):
            gate = flat @ g("mlp.shared_expert_gate.weight")[0]
            y = y + R.swiglu(flat, *mlp("mlp.shared_expert.")) \
                / (1.0 + np.exp(-gate))[:, None]
        xs = [x + p for x, p in zip(xs, np.split(y, np.cumsum(lens)[:-1]))]
    norm = shards.get("model.norm.weight")
    return [R.rms_norm(x, norm, eps) for x in xs]


def param_counts(config: dict) -> dict:
    m = _dims(config)
    n_dense = len(_dense_layers(config))
    n_sparse = m["L"] - n_dense
    return {
        "attn": m["L"] * (2 * m["d"] * m["q"] + 2 * m["d"] * m["kv"]),
        "mlp": n_dense * 3 * m["d"] * m["f"],
        "shared": n_sparse * (3 * m["d"] * m["fs"] + m["d"]),
        "experts": n_sparse * m["e"] * 3 * m["d"] * m["fm"],
        "router": n_sparse * m["e"] * m["d"],
        "head": m["v"] * m["d"],
    }


def decode_weight_bytes(config: dict, rows: float = 1.0) -> float:
    m, p = _dims(config), param_counts(config)
    served = config["assumed"]["served_bytes_per_param"]
    share = roofline.distinct_touched(m["e"], m["k"], rows) / m["e"]
    return (p["attn"] + p["mlp"] + p["shared"] + p["head"]) \
        * served["dense"] + p["experts"] * share * served["experts"] \
        + p["router"] * 4


def kv_bytes_per_token(config: dict, layers: "int | None" = None) -> float:
    m = _dims(config)
    return 2 * m["kv"] * config["assumed"]["kv_bytes_per_value"] \
        * (m["L"] if layers is None else layers)
