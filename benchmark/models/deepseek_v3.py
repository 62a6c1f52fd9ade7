"""model_type ``deepseek_v3``: DeepSeek-V3
(https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json;
HF ``modeling_deepseek_v3.py``). Latent attention — the cache holds ONE
normed latent of ``kv_lora_rank`` values and ONE rotary key of
``qk_rope_head_dim`` values a token a layer — and ``noaux_tc`` routing
over ``n_routed_experts`` small experts in groups, of which a chip of an
expert-parallel deployment holds a contiguous SHARE.

The forward pass as published, in its EXPANDED form (d = hidden_size,
H heads, d_n = qk_nope_head_dim, d_r = qk_rope_head_dim, d_v =
v_head_dim, r_q = q_lora_rank, r_kv = kv_lora_rank):

  x0 = Embed[ids]
  per layer l:
    h = RMSNorm_in(x)
    c_q = RMSNorm(h W_qa) [r_q];  [q_n | q_r]_h = c_q W_qb   (H x (d_n + d_r))
    [c | k_r] = h W_kva (r_kv | d_r);  c = RMSNorm(c)
    [k_n | v]_h = c W_kvb                                   (H x (d_n + d_v))
    rotary on q_r and on the ONE k_r all heads share, pairs (2i, 2i+1)
      interleaved (the checkpoint's layout); YaRN frequencies (theta,
      factor, original_max_position_embeddings, beta_fast / beta_slow);
      cos / sin NOT scaled: mscale == mscale_all_dim
    a_h = softmax(((q_n k_n^T + q_r k_r^T) s) + causal mask) v_h, float32,
      s = (d_n + d_r)^-1/2 (0.1 mscale_all_dim ln(factor) + 1)^2
    x = x + W_o concat_h(a_h)
    m = RMSNorm_post_attn(x)
    l < first_k_dense_replace:  y = W_down(silu(W_gate m) * W_up m)
    else: s = sigmoid(m W_r) [E] in float32 over ALL published experts;
          s' = s + b (e_score_correction_bias); n_group groups, a group's
          score the sum of its two largest s'; s' outside the topk_group
          best groups set to 0; sel = top-k of that; w = s[sel] /
          (sum + 1e-20) (norm_topk_prob) * routed_scaling_factor;
          y = sum_sel w_e E_e(m) + S(m)       (S = mlp.shared_experts)
    x = x + y
  hidden = RMSNorm_f(x_L)

Departures from the published description, each on purpose:

- THE SHARE. ``n_routed_experts`` in a configuration file is the number
  of experts HELD here, ``n_routed_experts_published`` the router's width
  (and the groups'), ``experts_first`` the first held published id (keys
  of this repo beside the published ones; absent: all held). The router
  scores, groups and picks over all published experts; the sum runs over
  the picked experts that are held (``mlp.experts.{e}`` with e the
  PUBLISHED id) plus the shared expert — what ONE chip of the deployment
  adds before the exchange that this repo does not have. With all experts
  held it is the published layer.
- The multi-token-prediction module (``num_nextn_predict_layers``) is
  NOT built: the next-token logits do not depend on it, and HF's
  modeling file drops its weights at load.
- Ties in a top-k go to the lower index (numpy's stable sort here,
  ``lax.top_k`` in the program); torch.topk leaves them unspecified.
- ``e_score_correction_bias`` is drawn with rms 0.02, not zeros as a
  fresh checkpoint would have (the configuration's ``assumed``).

``mutate`` (tests and tools only) breaks it one way at a time:
``zero_layer``, ``drop_kr`` (k_r left out of the score), ``drop_mscale``
(mscale^2 left out of s), ``unnormed_c`` (c used without its norm),
``rope_half`` (rotate-half on the checkpoint's interleaved layout),
``drop_bias`` (selection without the bias), ``bias_in_weight``,
``no_groups`` (a plain top-k over all experts), ``drop_shared``,
``drop_route_scale``.
"""

import math

import numpy as np

from benchmark.lib import reference as R
from benchmark.lib import roofline

# the absorbed decode kernel over the paged latent arena
# (localai_tfp_tpu/ops/ragged_paged_attention.py, ``v_lanes``)
ATTENTION_KERNELS = ("latent_paged_attention",)
EXPERT_KERNELS = ("ragged-dot",)


def dims(config: dict) -> dict:
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    held = config["n_routed_experts"]
    return {
        "d": config["hidden_size"], "f": config["intermediate_size"],
        "fe": config["moe_intermediate_size"],
        "fs": config["moe_intermediate_size"] * config["n_shared_experts"],
        "H": config["num_attention_heads"], "dn": dn, "dr": dr,
        "dv": config["v_head_dim"], "rq": config["q_lora_rank"],
        "rkv": config["kv_lora_rank"],
        "v": config["vocab_size"], "L": config["num_hidden_layers"],
        "Ld": min(config["first_k_dense_replace"],
                  config["num_hidden_layers"]),
        "held": held,
        "E": int(config.get("n_routed_experts_published") or held),
        "first": int(config.get("experts_first") or 0),
        "k": config["num_experts_per_tok"],
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
        sa = lp + "self_attn."
        out += [
            (i, sa + "q_a_proj.weight", (m["rq"], m["d"]), "BF16", "matrix"),
            (i, sa + "q_a_layernorm.weight", (m["rq"],), "BF16", "ones"),
            (i, sa + "q_b_proj.weight",
             (m["H"] * (m["dn"] + m["dr"]), m["rq"]), "BF16", "matrix"),
            (i, sa + "kv_a_proj_with_mqa.weight",
             (m["rkv"] + m["dr"], m["d"]), "BF16", "matrix"),
            (i, sa + "kv_a_layernorm.weight", (m["rkv"],), "BF16", "ones"),
            (i, sa + "kv_b_proj.weight",
             (m["H"] * (m["dn"] + m["dv"]), m["rkv"]), "BF16", "matrix"),
            (i, sa + "o_proj.weight", (m["d"], m["H"] * m["dv"]), "BF16",
             "matrix"),
        ]
        if i < m["Ld"]:
            out += swiglu(i, lp + "mlp", m["f"])
        else:
            # the router is as wide as the PUBLISHED expert count
            out.append((i, lp + "mlp.gate.weight", (m["E"], m["d"]),
                        "BF16", "matrix"))
            out.append((i, lp + "mlp.gate.e_score_correction_bias",
                        (m["E"],), "F32", "embed"))
            out += swiglu(i, lp + "mlp.shared_experts", m["fs"])
            for e in range(m["first"], m["first"] + m["held"]):
                out += swiglu(i, lp + f"mlp.experts.{e}", m["fe"])
        out += [(i, lp + f"{n}.weight", (m["d"],), "BF16", "ones")
                for n in ("input_layernorm", "post_attention_layernorm")]
    return out + [
        (m["L"], "model.embed_tokens.weight", (m["v"], m["d"]), "BF16",
         "embed"),
        (m["L"], "model.norm.weight", (m["d"],), "BF16", "ones"),
        (m["L"], "lm_head.weight", (m["v"], m["d"]), "BF16", "matrix")]


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(config: dict) -> np.ndarray:
    """Rotary inverse frequencies [d_r / 2] with the configuration's
    YaRN block (HF ``_compute_yarn_parameters``); plain when it has no
    ``rope_scaling``."""
    dr, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    extra = 1.0 / (base ** (np.arange(0, dr, 2, dtype=np.float32) / dr))
    sc = config.get("rope_scaling")
    if not sc:
        return extra
    factor = float(sc["factor"])
    orig = float(sc["original_max_position_embeddings"])

    def corr_dim(rot):
        return dr * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr_dim(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(corr_dim(float(sc["beta_slow"]))), dr - 1)
    ramp = np.clip((np.arange(dr // 2, dtype=np.float32) - low)
                   / max(high - low, 1), 0.0, 1.0)
    return (extra / factor) * ramp + extra * (1.0 - ramp)


def softmax_scale(config: dict, mutate: dict) -> float:
    s = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    sc = config.get("rope_scaling") or {}
    if sc.get("mscale_all_dim") and not mutate.get("drop_mscale"):
        s *= yarn_mscale(float(sc["factor"]),
                         float(sc["mscale_all_dim"])) ** 2
    return s


def rope(x: np.ndarray, inv: np.ndarray, half: bool = False) -> np.ndarray:
    """x [T, H, d_r], positions 0..T-1; pairs (2i, 2i+1) as the
    checkpoint keeps them (``half``: the rotate-half pairing instead, a
    mutation)."""
    T = x.shape[0]
    ang = np.arange(T, dtype=np.float32)[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    if half:
        n = x.shape[-1] // 2
        a, b = x[..., :n], x[..., n:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    out = np.empty_like(x)
    a, b = x[..., 0::2], x[..., 1::2]
    out[..., 0::2] = a * cos - b * sin
    out[..., 1::2] = b * cos + a * sin
    return out


def attention(x, g, config: dict, mutate: dict):
    """One layer's latent attention, expanded; x: [T, D] normed input."""
    m, eps = dims(config), float(config["rms_norm_eps"])
    T, H = x.shape[0], m["H"]
    cq = R.rms_norm(x @ g("self_attn.q_a_proj.weight").T,
                    g("self_attn.q_a_layernorm.weight"), eps)
    q = (cq @ g("self_attn.q_b_proj.weight").T).reshape(
        T, H, m["dn"] + m["dr"])
    qn, qr = q[..., :m["dn"]], q[..., m["dn"]:]
    kva = x @ g("self_attn.kv_a_proj_with_mqa.weight").T
    c, kr = kva[:, :m["rkv"]], kva[:, m["rkv"]:]
    if not mutate.get("unnormed_c"):
        c = R.rms_norm(c, g("self_attn.kv_a_layernorm.weight"), eps)
    kv = (c @ g("self_attn.kv_b_proj.weight").T).reshape(
        T, H, m["dn"] + m["dv"])
    kn, v = kv[..., :m["dn"]], kv[..., m["dn"]:]
    inv = rope_inv_freq(config)
    half = bool(mutate.get("rope_half"))
    qr = rope(qr, inv, half)
    kr = rope(kr[:, None, :], inv, half)[:, 0]
    s = np.float32(softmax_scale(config, mutate))
    mask = np.arange(T)[None, :] <= np.arange(T)[:, None]
    out = np.empty((T, H, m["dv"]), np.float32)
    for h in range(H):  # a head at a time: [T, T] floats, not H of them
        logits = qn[:, h] @ kn[:, h].T
        if not mutate.get("drop_kr"):
            logits = logits + qr[:, h] @ kr.T
        logits = np.where(mask, logits * s, -np.inf)
        logits -= logits.max(axis=-1, keepdims=True)
        p = np.exp(logits)
        out[:, h] = (p / p.sum(axis=-1, keepdims=True)) @ v[:, h]
    return out.reshape(T, H * m["dv"]) @ g("self_attn.o_proj.weight").T


def route(x, g, config: dict, mutate: dict):
    """``noaux_tc`` over ALL published experts: x [T, D] -> (published
    expert ids [T, k], weights [T, k])."""
    m = dims(config)
    s = sigmoid(x @ g("mlp.gate.weight").T)  # [T, E] float32
    bias = g("mlp.gate.e_score_correction_bias")
    choose = s if mutate.get("drop_bias") else s + bias
    G = int(config.get("n_group") or 1)
    if G > 1 and not mutate.get("no_groups"):
        grouped = choose.reshape(-1, G, m["E"] // G)
        top2 = -np.sort(-grouped, axis=-1)[..., :2]
        keep = np.argsort(-top2.sum(-1), axis=-1, kind="stable")[
            :, :int(config["topk_group"])]
        kept = np.zeros(grouped.shape[:2], bool)
        np.put_along_axis(kept, keep, True, axis=1)
        choose = np.where(kept[:, :, None], grouped, 0.0).reshape(
            -1, m["E"])
    top = np.argsort(-choose, axis=-1, kind="stable")[:, :m["k"]]
    w = np.take_along_axis(s + bias if mutate.get("bias_in_weight") else s,
                           top, axis=-1)
    if config.get("norm_topk_prob", True):
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    if not mutate.get("drop_route_scale"):
        w = w * np.float32(config["routed_scaling_factor"])
    return top, w


def moe(x, g, config: dict, mutate: dict, held=None):
    """The expert MLP of one layer over x [T, D]: the picked experts
    that are HELD (``held``: a (first, count) range of published ids,
    default the configuration's) + the shared expert."""
    m = dims(config)
    first, n = held or (m["first"], m["held"])
    top, w = route(x, g, config, mutate)
    out = np.zeros_like(x)
    for e in range(first, first + n):
        rows, slot = np.nonzero(top == e)
        if rows.size == 0:
            continue
        b = f"mlp.experts.{e}."
        out[rows] += R.swiglu(
            x[rows], g(b + "gate_proj.weight"), g(b + "up_proj.weight"),
            g(b + "down_proj.weight")) * w[rows, slot][:, None]
    if mutate.get("drop_shared"):
        return out
    b = "mlp.shared_experts."
    return out + R.swiglu(x, g(b + "gate_proj.weight"),
                          g(b + "up_proj.weight"), g(b + "down_proj.weight"))


def forward_hidden(shards, config: dict, ids_list: list,
                   mutate: "dict | None" = None) -> list:
    """Final hidden states (after the last norm) of each id sequence:
    -> list of [T_i, D] float32. Weights are read once per layer for
    all sequences (the MLP over all of them at once)."""
    mutate = mutate or {}
    m, eps = dims(config), float(config["rms_norm_eps"])
    if config.get("topk_method", "noaux_tc") != "noaux_tc" \
            or config.get("scoring_func", "sigmoid") != "sigmoid":
        raise NotImplementedError("only noaux_tc with sigmoid scores")
    embed = shards.get("model.embed_tokens.weight")
    xs = [embed[np.asarray(ids)] for ids in ids_list]
    del embed
    cuts = np.cumsum([x.shape[0] for x in xs])[:-1]
    for i in range(m["L"]):
        if i == mutate.get("zero_layer"):
            continue
        lp = f"model.layers.{i}."
        g = lambda n: shards.get(lp + n)  # noqa: E731
        ln1 = g("input_layernorm.weight")
        xs = [x + attention(R.rms_norm(x, ln1, eps), g, config, mutate)
              for x in xs]
        normed = np.concatenate(
            [R.rms_norm(x, g("post_attention_layernorm.weight"), eps)
             for x in xs])
        if i < m["Ld"]:
            y = R.swiglu(normed, g("mlp.gate_proj.weight"),
                         g("mlp.up_proj.weight"), g("mlp.down_proj.weight"))
        else:
            y = moe(normed, g, config, mutate)
        xs = [x + dy for x, dy in zip(xs, np.split(y, cuts))]
    norm = shards.get("model.norm.weight")
    return [R.rms_norm(x, norm, eps) for x in xs]


def param_counts(config: dict) -> dict:
    """Matrix parameters by group. ``attn`` is ONE layer's latent
    attention: q_a, q_b, kv_a, kv_b, o (187.1 M at the published
    widths); ``expert`` ONE routed expert (44.04 M)."""
    m = dims(config)
    attn = (m["d"] * m["rq"] + m["rq"] * m["H"] * (m["dn"] + m["dr"])
            + m["d"] * (m["rkv"] + m["dr"])
            + m["rkv"] * m["H"] * (m["dn"] + m["dv"])
            + m["H"] * m["dv"] * m["d"])
    return {
        "attn": attn,
        "dense_mlp": 3 * m["d"] * m["f"],
        "expert": 3 * m["d"] * m["fe"],
        "shared": 3 * m["d"] * m["fs"],
        "router": m["d"] * m["E"],
        "head": m["v"] * m["d"],
    }


def layer_params(config: dict) -> dict:
    """Parameters of one dense layer and of one expert layer as held
    here (937.6 M and 583.5 M for the benchmark's configuration)."""
    p, m = param_counts(config), dims(config)
    return {"dense": p["attn"] + p["dense_mlp"],
            "expert": p["attn"] + m["held"] * p["expert"] + p["shared"]
            + p["router"]}


def expert_layers(config: dict) -> int:
    m = dims(config)
    return m["L"] - m["Ld"]


def expert_bytes(config: dict) -> float:
    """Bytes of ONE routed expert as served: its three matrices."""
    return param_counts(config)["expert"] \
        * config["assumed"]["served_bytes_per_param"]["experts"]


def experts_touched(config: dict, rows: float) -> float:
    """Distinct HELD experts one layer reads for ``rows`` tokens under
    uniform, independent routing over all published experts: the held
    fraction of ``distinct_touched`` (6.4 of 16 at 16 rows of top-8
    over 256)."""
    m = dims(config)
    return roofline.distinct_touched(m["E"], m["k"], rows) \
        * m["held"] / m["E"]


def decode_weight_bytes(config: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step of ``rows`` tokens has to read
    under uniform routing: every layer's latent attention, the dense
    layers' MLPs, the shared experts, the routers and the head once; of
    each expert layer ``experts_touched`` of the held experts. Served
    rows of a seeded checkpoint do not route uniformly (PERF.md section
    6 PR 38), so the cell does not list ``decode_hbm_roofline``."""
    served = config["assumed"]["served_bytes_per_param"]
    p, m = param_counts(config), dims(config)
    n_e = expert_layers(config)
    return (m["L"] * p["attn"] + m["Ld"] * p["dense_mlp"]
            + n_e * (p["shared"] + p["router"]) + p["head"]) \
        * served["dense"] \
        + n_e * experts_touched(config, rows) * expert_bytes(config)


def kv_bytes_per_token(config: dict, layers: "int | None" = None) -> float:
    """Bytes of DATA one cached token holds: the latent row [c | k_r],
    (kv_lora_rank + qk_rope_head_dim) values a layer — 1152 B at the
    published widths. The zero lanes the arena pads a row with
    (``assumed.kv_row_pad_values``) are not data and not counted."""
    m = dims(config)
    n_layers = m["L"] if layers is None else layers
    return (m["rkv"] + m["dr"]) * config["assumed"]["kv_bytes_per_value"] \
        * n_layers
