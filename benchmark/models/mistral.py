"""model_type ``mistral``: a dense decoder of GQA attention and SwiGLU
MLPs (Mistral-7B: arXiv:2310.06825; HF ``modeling_mistral``).

Forward pass as published: token embedding, per layer RMSNorm -> GQA
attention with rotate-half RoPE (theta from the config) under a causal
mask -> residual -> RMSNorm -> SwiGLU MLP -> residual, then the final
RMSNorm. No kernels, no cache, no batching tricks, no quantization.
Departures from the published description: none in the mathematics.

``mutate`` exists for the tests only: it shows that the parity
tolerance catches a zeroed layer and a wrong rope base.

Bytes per parameter as SERVED come from the configuration file
(``assumed.served_bytes_per_param``), because the program's
``quantization`` decides them, not the published config.
"""

import numpy as np

from benchmark.lib import reference as R

ATTENTION_KERNELS = ("ragged_paged_attention",)


def dims(config: dict) -> dict:
    """The sizes every matrix of the model is made of, by role."""
    d_head = config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])
    return {
        "d": config["hidden_size"], "f": config["intermediate_size"],
        "q": config["num_attention_heads"] * d_head,
        "kv": config["num_key_value_heads"] * d_head,
        "v": config["vocab_size"], "L": config["num_hidden_layers"],
    }


def attn_tensors(config: dict, i: int) -> list:
    m, lp = dims(config), f"model.layers.{i}.self_attn."
    return [(i, lp + f"{p}_proj.weight", shape, "BF16", "matrix")
            for p, shape in (("q", (m["q"], m["d"])), ("k", (m["kv"], m["d"])),
                             ("v", (m["kv"], m["d"])), ("o", (m["d"], m["q"])))]


def norm_tensors(config: dict, i: int) -> list:
    return [(i, f"model.layers.{i}.{n}.weight", (config["hidden_size"],),
             "BF16", "ones")
            for n in ("input_layernorm", "post_attention_layernorm")]


def global_tensors(config: dict) -> list:
    m = dims(config)
    return [(m["L"], "model.embed_tokens.weight", (m["v"], m["d"]), "BF16",
             "embed"),
            (m["L"], "model.norm.weight", (m["d"],), "BF16", "ones"),
            (m["L"], "lm_head.weight", (m["v"], m["d"]), "BF16", "matrix")]


def tensors(config: dict) -> list:
    m, out = dims(config), []
    for i in range(m["L"]):
        lp = f"model.layers.{i}.mlp."
        out += attn_tensors(config, i)
        out += [(i, lp + "gate_proj.weight", (m["f"], m["d"]), "BF16", "matrix"),
                (i, lp + "up_proj.weight", (m["f"], m["d"]), "BF16", "matrix"),
                (i, lp + "down_proj.weight", (m["d"], m["f"]), "BF16", "matrix")]
        out += norm_tensors(config, i)
    return out + global_tensors(config)


def forward_hidden(shards, config: dict, ids_list: list,
                   mutate: "dict | None" = None, mlp=None) -> list:
    """Final hidden states (after the last norm) of each id sequence:
    -> list of [T_i, D] float32. Weights are read once per layer for
    all sequences. ``mlp(g, xs_normed) -> list`` replaces the SwiGLU MLP
    (a type that differs from this one in its MLP alone)."""
    mutate = mutate or {}
    n_heads = config["num_attention_heads"]
    n_kv = config["num_key_value_heads"]
    d_head = config.get("head_dim") or config["hidden_size"] // n_heads
    eps = float(config["rms_norm_eps"])
    theta = float(mutate.get("rope_theta", config["rope_theta"]))
    if config.get("sliding_window"):
        raise NotImplementedError("the reference has no sliding window")
    embed = shards.get("model.embed_tokens.weight")
    xs = [embed[np.asarray(ids)] for ids in ids_list]
    del embed
    for i in range(config["num_hidden_layers"]):
        if i == mutate.get("zero_layer"):
            continue
        lp = f"model.layers.{i}."
        g = lambda n: shards.get(lp + n)  # noqa: E731
        wq, wk, wv, wo = (g(f"self_attn.{p}_proj.weight")
                          for p in "qkvo")
        ln1 = g("input_layernorm.weight")
        ln2 = g("post_attention_layernorm.weight")
        xs = [x + R.attention(R.rms_norm(x, ln1, eps), wq, wk, wv, wo,
                              n_heads, n_kv, d_head, theta) for x in xs]
        del wq, wk, wv, wo
        normed = [R.rms_norm(x, ln2, eps) for x in xs]
        if mlp is not None:
            xs = [x + y for x, y in zip(xs, mlp(g, normed))]
            continue
        w_gate, w_up, w_down = (g(f"mlp.{p}_proj.weight")
                                for p in ("gate", "up", "down"))
        xs = [x + R.swiglu(n, w_gate, w_up, w_down)
              for x, n in zip(xs, normed)]
        del w_gate, w_up, w_down
    norm = shards.get("model.norm.weight")
    return [R.rms_norm(x, norm, eps) for x in xs]


def param_counts(config: dict) -> dict:
    """Matrix parameters by group: attention, MLP, head (the output
    projection; the embedding table is gathered, not read whole)."""
    m = dims(config)
    return {
        "attn": m["L"] * (2 * m["d"] * m["q"] + 2 * m["d"] * m["kv"]),
        "mlp": 3 * m["d"] * m["f"] * m["L"],
        "head": m["v"] * m["d"],
    }


def decode_weight_bytes(config: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step HAS to read: every matrix once,
    whatever the rows."""
    p = param_counts(config)
    return (p["attn"] + p["mlp"] + p["head"]) \
        * config["assumed"]["served_bytes_per_param"]["dense"]


def kv_bytes_per_token(config: dict, layers: "int | None" = None) -> float:
    """K and V bytes one cached token holds (data only; the int8
    cache's per-row scales are left out, so the share reads low rather
    than high)."""
    m = dims(config)
    n_layers = m["L"] if layers is None else layers
    return 2 * m["kv"] * config["assumed"]["kv_bytes_per_value"] * n_layers
