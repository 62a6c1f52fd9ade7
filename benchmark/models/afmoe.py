"""model_type ``afmoe``: arcee-ai Trinity (Trinity-Mini 26B-A3B,
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json; HF
``modeling_afmoe``). A decoder whose layers differ in two ways at once:
window and full attention by ``layer_types``, and the first
``num_dense_layers`` layers a dense SwiGLU MLP where the rest route
over ``num_experts`` small experts.

The forward pass, from the published config; what the config cannot
show is marked (+) and is the public ``modeling_afmoe.py`` of
``transformers`` as the writer of ISSUE 38 recalled it (no network
where this was written) — the configuration file lists the same items
under ``assumed``, and models/llm_spec.py + models/hf_loader.py of the
program read exactly these names and make exactly these choices:

  x0 = Embed[ids] * sqrt(hidden_size)                    (mup_enabled)
  per layer l:
    h = RMSNorm_in(x)
    q, k, v = W_q h, W_k h, W_v h;  g = W_g h            (+ self_attn.gate_proj)
    q, k = RMSNorm_q(q), RMSNorm_k(k)  per head          (+ q_norm, k_norm)
    sliding layers: rotate-half RoPE over the whole head, theta from the
      config; full layers: NO positional encoding        (+)
    a = softmax(q k^T / sqrt(head_dim) + mask) v; mask: j <= i, and on
      sliding layers j > i - sliding_window; GQA
    x = x + RMSNorm_post_attn(W_o (a * sigmoid(g)))      (+ four norms a layer)
    m = RMSNorm_pre_mlp(x)
    l < num_dense_layers:  y = W_down(silu(W_gate m) * W_up m)
    else: s = sigmoid(W_r m) [E] in float32; sel = top-k of (s + b);
          w = s[sel];  w = w / (sum w + 1e-20) (route_norm);
          w = route_scale * w;  y = sum_sel w_e E_e(m) + S(m)
          (b = mlp.expert_bias decides the selection, never the weight;
          S = mlp.shared_experts, always on, no gate of its own)
    x = x + RMSNorm_post_mlp(y)
  hidden = RMSNorm_f(x_L)

Plain float32 numpy from lib/reference.py's parts; only the selected
experts are evaluated. ``n_group`` = ``topk_group`` = 1: no grouped
selection (anything else raises). Departures from the source: none
known beyond the (+) items being recalled, not read.

``mutate`` (tests only) breaks it one way at a time: ``zero_layer``,
``rope_on_full`` (rotary on a full layer too), ``drop_bias`` (selection
without the bias), ``bias_in_weight`` (the bias added to the weight),
``drop_gate`` (no attention output gate), ``window`` (another window).
"""

import numpy as np

from benchmark.lib import reference as R
from benchmark.lib import roofline

ATTENTION_KERNELS = ("ragged_paged_attention",)
# op-name prefixes of the expert layer's grouped matmuls in a capture:
# XLA's own ragged-dot kernel (lax.ragged_dot), and its set-up op
EXPERT_KERNELS = ("ragged-dot",)


def dims(config: dict) -> dict:
    """The sizes every matrix of the model is made of, by role."""
    d_head = config["head_dim"]
    return {
        "d": config["hidden_size"], "f": config["intermediate_size"],
        "fe": config["moe_intermediate_size"],
        "fs": config["moe_intermediate_size"] * config["num_shared_experts"],
        "q": config["num_attention_heads"] * d_head,
        "kv": config["num_key_value_heads"] * d_head, "dh": d_head,
        "v": config["vocab_size"], "L": config["num_hidden_layers"],
        "Ld": min(config["num_dense_layers"], config["num_hidden_layers"]),
        "E": config["num_experts"], "k": config["num_experts_per_tok"],
    }


def tensors(config: dict) -> list:
    m, out = dims(config), []

    def swiglu(i, base, width):
        return [(i, f"{base}.{p}_proj.weight", shape, "BF16", "matrix")
                for p, shape in (("gate", (width, m["d"])),
                                 ("up", (width, m["d"])),
                                 ("down", (m["d"], width)))]

    for i in range(m["L"]):
        lp = f"model.layers.{i}."
        out += [(i, lp + f"self_attn.{p}_proj.weight", shape, "BF16",
                 "matrix")
                for p, shape in (("q", (m["q"], m["d"])),
                                 ("k", (m["kv"], m["d"])),
                                 ("v", (m["kv"], m["d"])),
                                 ("o", (m["d"], m["q"])),
                                 ("gate", (m["q"], m["d"])))]
        out += [(i, lp + f"self_attn.{n}_norm.weight", (m["dh"],), "BF16",
                 "ones") for n in ("q", "k")]
        if i < m["Ld"]:
            out += swiglu(i, lp + "mlp", m["f"])
        else:
            out.append((i, lp + "mlp.router.gate.weight", (m["E"], m["d"]),
                        "BF16", "matrix"))
            # drawn, not zeros: selection and weight then differ
            out.append((i, lp + "mlp.expert_bias", (m["E"],), "F32",
                        "embed"))
            out += swiglu(i, lp + "mlp.shared_experts", m["fs"])
            for e in range(m["E"]):
                out += swiglu(i, lp + f"mlp.experts.{e}", m["fe"])
        out += [(i, lp + f"{n}.weight", (m["d"],), "BF16", "ones")
                for n in ("input_layernorm", "post_attention_layernorm",
                          "pre_mlp_layernorm", "post_mlp_layernorm")]
    return out + [
        (m["L"], "model.embed_tokens.weight", (m["v"], m["d"]), "BF16",
         "embed"),
        (m["L"], "model.norm.weight", (m["d"],), "BF16", "ones"),
        (m["L"], "lm_head.weight", (m["v"], m["d"]), "BF16", "matrix")]


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def attention(x, g, config: dict, sliding: bool, mutate: dict):
    """One layer's attention branch before its post-norm; x: [T, D]
    normed input, g(name) -> the layer's tensor."""
    m, eps = dims(config), float(config["rms_norm_eps"])
    T = x.shape[0]
    n_heads, n_kv = config["num_attention_heads"], \
        config["num_key_value_heads"]
    q = (x @ g("self_attn.q_proj.weight").T).reshape(T, n_heads, m["dh"])
    k = (x @ g("self_attn.k_proj.weight").T).reshape(T, n_kv, m["dh"])
    v = (x @ g("self_attn.v_proj.weight").T).reshape(T, n_kv, m["dh"])
    q = R.rms_norm(q, g("self_attn.q_norm.weight"), eps)
    k = R.rms_norm(k, g("self_attn.k_norm.weight"), eps)
    if sliding or mutate.get("rope_on_full"):
        theta = float(config["rope_theta"])
        q, k = R.rope(q, theta), R.rope(k, theta)
    k = np.repeat(k, n_heads // n_kv, axis=1)
    v = np.repeat(v, n_heads // n_kv, axis=1)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    mask = j <= i
    if sliding:
        mask &= j > i - int(mutate.get("window", config["sliding_window"]))
    out = np.empty((T, n_heads, m["dh"]), np.float32)
    for h in range(n_heads):  # a head at a time: [T, T] floats, not H of them
        logits = (q[:, h] @ k[:, h].T) / np.sqrt(np.float32(m["dh"]))
        logits = np.where(mask, logits, -np.inf)
        logits -= logits.max(axis=-1, keepdims=True)
        p = np.exp(logits)
        out[:, h] = (p / p.sum(axis=-1, keepdims=True)) @ v[:, h]
    out = out.reshape(T, m["q"])
    if not mutate.get("drop_gate"):
        out = out * sigmoid(x @ g("self_attn.gate_proj.weight").T)
    return out @ g("self_attn.o_proj.weight").T


def moe(x, g, config: dict, mutate: dict):
    """The expert MLP of one layer over x [T, D]: routed experts (only
    the selected ones evaluated) + the shared expert."""
    m = dims(config)
    s = sigmoid(x @ g("mlp.router.gate.weight").T)  # [T, E] float32
    bias = g("mlp.expert_bias")
    choose = s if mutate.get("drop_bias") else s + bias
    top = np.argsort(-choose, axis=-1, kind="stable")[:, :m["k"]]
    w = np.take_along_axis(s + bias if mutate.get("bias_in_weight") else s,
                           top, axis=-1)
    if config["route_norm"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    w = w * np.float32(config["route_scale"])
    out = np.zeros_like(x)
    for e in range(m["E"]):
        rows, slot = np.nonzero(top == e)
        if rows.size == 0:
            continue
        b = f"mlp.experts.{e}."
        out[rows] += R.swiglu(
            x[rows], g(b + "gate_proj.weight"), g(b + "up_proj.weight"),
            g(b + "down_proj.weight")) * w[rows, slot][:, None]
    b = "mlp.shared_experts."
    return out + R.swiglu(x, g(b + "gate_proj.weight"),
                          g(b + "up_proj.weight"), g(b + "down_proj.weight"))


def forward_hidden(shards, config: dict, ids_list: list,
                   mutate: "dict | None" = None) -> list:
    """Final hidden states (after the last norm) of each id sequence:
    -> list of [T_i, D] float32. Weights are read once per layer for
    all sequences (the expert MLP over all of them at once)."""
    mutate = mutate or {}
    m, eps = dims(config), float(config["rms_norm_eps"])
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise NotImplementedError("grouped expert selection")
    if config["score_func"] != "sigmoid":
        raise NotImplementedError(f"score_func {config['score_func']!r}")
    embed = shards.get("model.embed_tokens.weight")
    scale = np.float32(np.sqrt(m["d"]) if config["mup_enabled"] else 1.0)
    xs = [embed[np.asarray(ids)] * scale for ids in ids_list]
    del embed
    cuts = np.cumsum([x.shape[0] for x in xs])[:-1]
    for i in range(m["L"]):
        if i == mutate.get("zero_layer"):
            continue
        lp = f"model.layers.{i}."
        g = lambda n: shards.get(lp + n)  # noqa: E731
        sliding = config["layer_types"][i] == "sliding_attention"
        ln1 = g("input_layernorm.weight")
        post_attn = g("post_attention_layernorm.weight")
        xs = [x + R.rms_norm(
            attention(R.rms_norm(x, ln1, eps), g, config, sliding, mutate),
            post_attn, eps) for x in xs]
        normed = np.concatenate(
            [R.rms_norm(x, g("pre_mlp_layernorm.weight"), eps) for x in xs])
        if i < m["Ld"]:
            y = R.swiglu(normed, g("mlp.gate_proj.weight"),
                         g("mlp.up_proj.weight"), g("mlp.down_proj.weight"))
        else:
            y = moe(normed, g, config, mutate)
        y = R.rms_norm(y, g("post_mlp_layernorm.weight"), eps)
        xs = [x + dy for x, dy in zip(xs, np.split(y, cuts))]
    norm = shards.get("model.norm.weight")
    return [R.rms_norm(x, norm, eps) for x in xs]


def param_counts(config: dict) -> dict:
    """Matrix parameters by group: attention (with its output gate),
    the dense layers' MLPs, one expert, the shared experts, routers,
    head (the embedding table is gathered, not read whole)."""
    m = dims(config)
    n_expert_layers = m["L"] - m["Ld"]
    return {
        "attn": m["L"] * (3 * m["d"] * m["q"] + 2 * m["d"] * m["kv"]),
        "dense_mlp": m["Ld"] * 3 * m["d"] * m["f"],
        "expert": 3 * m["d"] * m["fe"],
        "shared": n_expert_layers * 3 * m["d"] * m["fs"],
        "router": n_expert_layers * m["d"] * m["E"],
        "head": m["v"] * m["d"],
    }


def expert_layers(config: dict) -> int:
    m = dims(config)
    return m["L"] - m["Ld"]


def expert_bytes(config: dict) -> float:
    """Bytes of ONE routed expert as served: its three matrices."""
    return param_counts(config)["expert"] \
        * config["assumed"]["served_bytes_per_param"]["experts"]


def experts_touched(config: dict, rows: float) -> float:
    """Distinct experts one layer reads for ``rows`` tokens when the
    routing is uniform and independent, as a load-balanced trained
    model's is: 82.4 of 128 at 16 rows of top-8."""
    m = dims(config)
    return roofline.distinct_touched(m["E"], m["k"], rows)


def decode_weight_bytes(config: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step of ``rows`` tokens has to read
    under uniform routing: attention with its gate, the dense layers'
    MLPs, the shared experts, the routers and the head once; of each
    expert layer ``experts_touched`` experts (7.7 GB at 16 rows). A
    random-weight checkpoint routes its rows together and touches far
    fewer (PERF.md section 6 PR 38), so no cell with such a checkpoint
    lists ``decode_hbm_roofline``: ``expert_layer_roofline`` has the
    expert layer's share from the experts COUNTED."""
    served = config["assumed"]["served_bytes_per_param"]
    p = param_counts(config)
    return (p["attn"] + p["dense_mlp"] + p["shared"] + p["router"]
            + p["head"]) * served["dense"] \
        + expert_layers(config) * experts_touched(config, rows) \
        * expert_bytes(config)


def kv_bytes_per_token(config: dict, layers: "int | None" = None) -> float:
    """K and V bytes one cached token holds (every layer holds every
    position: the pool has one page table, windows or not)."""
    m = dims(config)
    n_layers = m["L"] if layers is None else layers
    return 2 * m["kv"] * config["assumed"]["kv_bytes_per_value"] * n_layers
