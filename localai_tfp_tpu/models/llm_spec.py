"""Architecture spec for decoder-only LLMs.

One spec dataclass drives a single stacked-scan transformer implementation
(models/transformer.py) across the model families the reference serves via
its llama.cpp / vLLM / transformers backends (ref: backend/cpp/llama
grpc-server.cpp LoadModel; backend/python/vllm/backend.py:92-128;
backend/python/transformers/backend.py:68-200). Instead of per-family
modeling code, family differences are expressed as data: norm type, MLP
gating, rotary fraction, biases, residual topology.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Optional


@dataclass(frozen=True, eq=False)  # eq=False: identity hash, so a spec can
# be a `jax.jit` static argument despite dict-typed fields. The engine holds
# exactly one spec object per loaded model, so identity-based jit caching is
# the behavior we want.
class LLMSpec:
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    max_position: int = 4096

    # rotary
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0  # phi uses partial rotary
    rope_scaling: Optional[dict] = None  # llama3 / yarn / linear scaling block

    # norm
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-5
    norm_weight_plus_one: bool = False  # gemma convention

    # mlp
    gated_mlp: bool = True  # llama-style gate*up; False => single up (phi)
    hidden_act: str = "silu"  # silu | gelu | gelu_tanh
    # mixture-of-experts (mixtral, qwen2_moe): 0 = dense MLP
    n_experts: int = 0
    experts_per_token: int = 2
    moe_d_ff: int = 0  # expert intermediate size; 0 = d_ff (mixtral)
    # qwen2_moe: always-on shared expert, scaled by sigmoid(router·x)
    moe_shared_expert: bool = False
    moe_shared_d_ff: int = 0  # shared expert intermediate size; 0 = d_ff
    # True (mixtral): renormalize the top-k router weights to sum to 1.
    # False (qwen2_moe norm_topk_prob=false): keep raw softmax-over-all-E
    # probabilities for the selected experts.
    moe_norm_topk: bool = True
    # qwen2_moe decoder_sparse_step / mlp_only_layers: these layer indices
    # use a plain dense MLP (stored in the shared-expert slots, gate
    # forced to 1, expert weights zeroed) instead of the sparse mixture
    moe_dense_layers: tuple[int, ...] = ()
    # afmoe routing: scores are sigmoid(router·x) instead of a softmax;
    # a per-expert selection bias (param "router_bias") is added to the
    # scores for the top-k CHOICE only, never to the weight; the
    # combined routed output is scaled by moe_route_scale
    moe_score_func: str = "softmax"  # softmax | sigmoid
    moe_select_bias: bool = False
    moe_route_scale: float = 1.0
    # False (afmoe): the shared expert has no gate of its own (always 1)
    moe_shared_gated: bool = True
    # afmoe num_dense_layers: the first n layers carry a plain dense MLP
    # of width d_ff and live in a stack of their own ("dense.*" leaves,
    # [n, ...]), scanned before the expert stack ([n_layers - n, ...])
    n_dense_layers: int = 0

    # biases
    qkv_bias: bool = False  # qwen2, phi
    o_bias: bool = False  # phi
    mlp_bias: bool = False  # phi
    lm_head_bias: bool = False  # phi

    # topology
    parallel_residual: bool = False  # phi: x + attn(ln(x)) + mlp(ln(x))
    tie_word_embeddings: bool = False
    final_norm: bool = True
    qk_norm: bool = False  # qwen3: per-head RMSNorm on q/k before rope
    sandwich_norms: bool = False  # gemma2/3: post-attn + pre/post-ffw norms
    # afmoe: attention output times sigmoid(W_g h) before the o projection
    attn_output_gate: bool = False

    # scaling oddities
    embedding_multiplier: float = 1.0  # gemma: sqrt(d_model)
    logit_softcap: float = 0.0  # gemma2
    attn_logit_softcap: float = 0.0  # gemma2
    query_pre_attn_scalar: Optional[float] = None  # gemma2 attention scale

    # sliding window attention (mistral); None = full causal
    sliding_window: Optional[int] = None
    # gemma2/3: every Nth layer is GLOBAL (full attention), the rest use
    # sliding_window; 0 = uniform window on all layers
    sliding_window_pattern: int = 0
    # explicit per-layer kinds ("sliding_attention"/"full_attention") —
    # HF layer_types; wins over the pattern when present
    layer_types: Optional[tuple[str, ...]] = None
    # gemma3: sliding layers rope on a separate (local) base frequency
    rope_local_base_freq: float = 0.0
    # afmoe: rotary on sliding layers only; full layers carry no
    # positional encoding
    rope_sliding_only: bool = False

    # olmo_hybrid: layers of kind "linear_attention" (layer_types) mix
    # tokens by the gated delta rule (ops/gated_delta.py) — a recurrent
    # state per slot instead of KV pages; 0 heads = the type has none.
    # The layers come in equal periods: ``linear_period`` linear layers,
    # then one full-attention layer (models/transformer.py scans periods)
    linear_heads: int = 0
    linear_d_k: int = 0
    linear_d_v: int = 0
    linear_conv: int = 4  # taps of the causal depthwise convolution
    linear_neg_eigval: bool = False  # beta in (0, 2) instead of (0, 1)
    # False (olmo_hybrid, the Olmo-3 block): no norm BEFORE a sub-layer,
    # only on its output (the sandwich post-norms)
    pre_norm: bool = True
    # olmo: q/k RMSNorm over the whole projection, before the head split
    qk_norm_flat: bool = False

    # deepseek_v3: latent attention. ``kv_lora_rank`` > 0 switches the
    # token mixer (models/transformer.py ``_latent_mixer``): queries go
    # through a normed ``q_lora_rank`` bottleneck, every head's key is a
    # ``qk_nope_dim`` part up-projected from ONE normed latent of
    # ``kv_lora_rank`` values a token plus ONE rotary key of
    # ``qk_rope_dim`` values shared by all heads, values are
    # ``v_head_dim`` wide, and the cache holds the latent row alone
    # (``latent_row``). ``d_head`` is the query/key head size,
    # qk_nope_dim + qk_rope_dim
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # the softmax scale's multiplier (deepseek's YaRN: mscale^2 sits in
    # the scale, not on cos/sin); see transformer.rope_attn_scale
    attn_scale_mult: float = 1.0
    # group-limited expert selection (deepseek_v3 ``noaux_tc``): the
    # experts in ``moe_n_group`` equal groups, a group scored by the sum
    # of its two best biased scores, the top-k taken inside the best
    # ``moe_topk_group`` groups; 1 / 1 = no grouping
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # THE SHARE: an expert layer that holds a contiguous range of the
    # published experts — ``n_experts`` stays the published count (the
    # router's width, the groups), ``experts_held`` (0 = all) of them
    # from published id ``experts_first`` are on this chip. The router
    # scores and picks over all; an assignment to an absent expert
    # reads nothing and adds nothing (the chip that holds it adds it)
    experts_held: int = 0
    experts_first: int = 0

    extra: dict = field(default_factory=dict)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.d_head

    @property
    def kv_dim(self) -> int:
        """Values of one cached row (a layer, a token, K or V)."""
        if self.kv_lora_rank:
            return self.latent_row
        return self.n_kv_heads * self.d_head

    @property
    def latent_width(self) -> int:
        """Values of a latent cache row: [c | k_r]."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_row(self) -> int:
        """The latent row as stored: padded with zeros to whole
        128-lane vectors (576 -> 640; the device's tiled layout pads
        the minor dim to 128 lanes whether the shape says so or not)."""
        return -(-self.latent_width // 128) * 128

    @property
    def o_dim(self) -> int:
        """Width of the heads' output, the o projection's input."""
        return self.n_heads * (self.v_head_dim or self.d_head)

    @property
    def n_held(self) -> int:
        """Experts a layer holds on this chip."""
        return self.experts_held or self.n_experts

    @property
    def rotary_dim(self) -> int:
        if self.kv_lora_rank:
            return self.qk_rope_dim
        rd = int(self.d_head * self.rotary_pct)
        return rd - (rd % 2)

    @property
    def n_linear_layers(self) -> int:
        if not self.linear_heads:
            return 0
        return sum(t == "linear_attention" for t in self.layer_types)

    @property
    def n_kv_layers(self) -> int:
        """Layers that hold K/V: the cache's leading dim."""
        return self.n_layers - self.n_linear_layers

    @property
    def linear_period(self) -> int:
        """Linear layers before each full-attention layer."""
        return self.n_linear_layers // max(1, self.n_kv_layers)

    @property
    def linear_conv_dim(self) -> int:
        return self.linear_heads * (2 * self.linear_d_k + self.linear_d_v)

    @property
    def model_type(self) -> str:
        return str(self.extra.get("model_type") or "")


def spec_from_hf_config(cfg: dict[str, Any]) -> LLMSpec:
    """Map a HuggingFace ``config.json`` dict onto an LLMSpec.

    Covers: llama / llama3 / mistral / qwen2 / qwen2.5 / phi / phi3 /
    gemma / gemma2 / tinyllama-class checkpoints (the families the
    reference's GGUF-introspection defaults table recognizes —
    ref: core/config/gguf.go:36-123).
    """
    mt = (cfg.get("model_type") or "").lower()
    if mt == "gemma3" and isinstance(cfg.get("text_config"), dict):
        # multimodal gemma3 checkpoints nest the text params; the vision
        # tower is not served here, only the language model
        cfg = {**cfg["text_config"], "model_type": "gemma3_text"}
        mt = "gemma3_text"
    elif mt == "llava" and isinstance(cfg.get("text_config"), dict):
        # plain-llava wrappers nest a standard text config (usually
        # llama/mistral); the CLIP tower loads via load_multimodal.
        # llava_next (anyres grids) / vipllava (multi-layer features)
        # need different vision semantics — refuse rather than serve
        # silently-wrong image embeddings.
        cfg = dict(cfg["text_config"])
        mt = (cfg.get("model_type") or "llama").lower()
    d_model = cfg.get("hidden_size") or cfg.get("n_embd") or 2048
    n_heads = cfg.get("num_attention_heads") or cfg.get("n_head") or 16
    n_kv = cfg.get("num_key_value_heads") or n_heads
    d_head = cfg.get("head_dim") or d_model // n_heads
    n_layers = cfg.get("num_hidden_layers") or cfg.get("n_layer") or 24
    d_ff = cfg.get("intermediate_size") or cfg.get("n_inner") or 4 * d_model
    act = (cfg.get("hidden_act") or cfg.get("activation_function") or "silu").lower()
    if act in ("gelu_new", "gelu_pytorch_tanh", "gelu_fast"):
        act = "gelu_tanh"

    kw: dict[str, Any] = dict(
        vocab_size=cfg.get("vocab_size", 32000),
        d_model=d_model,
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        d_head=d_head,
        d_ff=d_ff,
        max_position=cfg.get("max_position_embeddings", 4096),
        rope_theta=float(cfg.get("rope_theta") or 10000.0),
        rope_scaling=cfg.get("rope_scaling"),
        norm_eps=float(
            cfg.get("rms_norm_eps")
            or cfg.get("layer_norm_eps")
            or cfg.get("layer_norm_epsilon")
            or 1e-5
        ),
        hidden_act=act,
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        sliding_window=cfg.get("sliding_window"),
    )

    if mt in ("llama", "mistral", ""):
        pass
    elif mt == "mixtral":
        kw.update(
            n_experts=int(cfg.get("num_local_experts") or 8),
            experts_per_token=int(cfg.get("num_experts_per_tok") or 2),
        )
    elif mt in ("qwen2", "qwen2_5"):
        kw["qkv_bias"] = True
    elif mt == "qwen3":
        kw["qk_norm"] = True  # per-head RMSNorm on q/k before rope
    elif mt == "qwen2_moe":
        # qwen1.5/qwen2 MoE (HF Qwen2MoeForCausalLM): top-k sparse experts
        # + an always-on shared expert gated by sigmoid(x·g); layers listed
        # in mlp_only_layers (or off the decoder_sparse_step grid) fall
        # back to a plain dense MLP
        step = int(cfg.get("decoder_sparse_step") or 1)
        mlp_only = {int(x) for x in (cfg.get("mlp_only_layers") or [])}
        dense_layers = tuple(sorted(
            layer for layer in range(n_layers)
            if layer in mlp_only or (step > 0 and (layer + 1) % step != 0)
        ))
        kw.update(
            qkv_bias=True,
            n_experts=int(cfg.get("num_experts") or 60),
            experts_per_token=int(cfg.get("num_experts_per_tok") or 4),
            moe_d_ff=int(cfg.get("moe_intermediate_size") or d_ff),
            moe_shared_expert=True,
            moe_shared_d_ff=int(
                cfg.get("shared_expert_intermediate_size") or d_ff),
            moe_norm_topk=bool(cfg.get("norm_topk_prob", False)),
            moe_dense_layers=dense_layers,
        )
    elif mt == "qwen3_moe":
        # qwen3 MoE: per-head q/k RMSNorm (no qkv bias) + top-k sparse
        # experts with renormalized weights and NO shared expert
        step = int(cfg.get("decoder_sparse_step") or 1)
        mlp_only = {int(x) for x in (cfg.get("mlp_only_layers") or [])}
        dense_layers = tuple(sorted(
            layer for layer in range(n_layers)
            if layer in mlp_only or (step > 0 and (layer + 1) % step != 0)
        ))
        if dense_layers:
            # without a shared expert there is no slot to park a dense
            # MLP in the stacked scan; no released checkpoint uses this
            raise NotImplementedError(
                "qwen3_moe with dense (mlp_only/off-step) layers is not "
                "supported yet")
        kw.update(
            qk_norm=True,
            n_experts=int(cfg.get("num_experts") or 128),
            experts_per_token=int(cfg.get("num_experts_per_tok") or 8),
            moe_d_ff=int(cfg.get("moe_intermediate_size") or d_ff),
            # released qwen3-MoE checkpoints set norm_topk_prob=true in
            # config.json, but the HF CLASS default for an omitted key is
            # False — mirror that so omitted-key configs stay bit-parity
            moe_norm_topk=bool(cfg.get("norm_topk_prob", False)),
        )
    elif mt == "afmoe":
        # arcee Trinity (HF AfmoeForCausalLM): sigmoid-scored top-k
        # experts chosen under a selection bias, weights renormalised
        # and scaled; one always-on shared expert without a gate; the
        # first num_dense_layers layers dense; four norms a layer; a
        # sigmoid gate on the attention output; q/k norms; rotary on
        # the sliding layers only; muP embedding scale
        n_shared = int(cfg.get("num_shared_experts") or 0)
        moe_ff = int(cfg.get("moe_intermediate_size") or d_ff)
        kw.update(
            moe_n_group=int(cfg.get("n_group") or 1),
            moe_topk_group=int(cfg.get("topk_group") or 1),
            qk_norm=True,
            sandwich_norms=True,
            attn_output_gate=True,
            rope_sliding_only=True,
            embedding_multiplier=(float(d_model) ** 0.5
                                  if cfg.get("mup_enabled") else 1.0),
            n_experts=int(cfg.get("num_experts") or 0),
            experts_per_token=int(cfg.get("num_experts_per_tok") or 1),
            moe_d_ff=moe_ff,
            moe_shared_expert=n_shared > 0,
            moe_shared_d_ff=moe_ff * max(n_shared, 1),
            moe_shared_gated=False,
            moe_norm_topk=bool(cfg.get("route_norm", True)),
            moe_score_func=str(cfg.get("score_func") or "sigmoid"),
            moe_select_bias=True,
            moe_route_scale=float(cfg.get("route_scale") or 1.0),
            n_dense_layers=min(int(cfg.get("num_dense_layers") or 0),
                               n_layers),
        )
    elif mt == "deepseek_v3":
        # DeepSeek-V3 (HF DeepseekV3ForCausalLM): latent attention and
        # ``noaux_tc`` routing — sigmoid scores, a selection bias, the
        # top-k inside the best groups, weights renormalised and scaled;
        # one always-on shared expert; the first first_k_dense_replace
        # layers dense. The multi-token-prediction module
        # (num_nextn_predict_layers) is NOT built: next-token logits do
        # not depend on it and HF's own modeling file drops it at load.
        if str(cfg.get("topk_method") or "noaux_tc") != "noaux_tc" \
                or str(cfg.get("scoring_func") or "sigmoid") != "sigmoid":
            raise NotImplementedError(
                "deepseek_v3: only topk_method noaux_tc with sigmoid "
                "scores is supported")
        if int(cfg.get("moe_layer_freq") or 1) != 1:
            raise NotImplementedError("deepseek_v3: moe_layer_freq != 1")
        n_routed = int(cfg.get("n_routed_experts") or 0)
        # keys of this repo beside the published ones: how many experts
        # the PUBLISHED model routes over when this chip holds a share
        # (n_routed_experts of them, from published id experts_first)
        published = int(cfg.get("n_routed_experts_published") or n_routed)
        first = int(cfg.get("experts_first") or 0)
        if published < n_routed or first < 0 or first + n_routed > published:
            raise ValueError(
                f"deepseek_v3: experts {first}..{first + n_routed} are not "
                f"a range of the {published} published")
        moe_ff = int(cfg.get("moe_intermediate_size") or d_ff)
        n_shared = int(cfg.get("n_shared_experts") or 0)
        nope = int(cfg.get("qk_nope_head_dim") or 128)
        rope_d = int(cfg.get("qk_rope_head_dim") or 64)
        sc = cfg.get("rope_scaling") or {}
        mult = 1.0
        if (sc.get("rope_type") or sc.get("type") or "").lower() == "yarn" \
                and sc.get("mscale_all_dim"):
            # softmax_scale * mscale^2 (modeling_deepseek_v3)
            m = 0.1 * float(sc["mscale_all_dim"]) * math.log(
                float(sc.get("factor", 1.0))) + 1.0 \
                if float(sc.get("factor", 1.0)) > 1 else 1.0
            mult = m * m
        kw.update(
            n_kv_heads=1,
            d_head=nope + rope_d,
            kv_lora_rank=int(cfg.get("kv_lora_rank") or 512),
            q_lora_rank=int(cfg.get("q_lora_rank") or 0),
            qk_nope_dim=nope,
            qk_rope_dim=rope_d,
            v_head_dim=int(cfg.get("v_head_dim") or 128),
            rotary_pct=rope_d / (nope + rope_d),
            attn_scale_mult=mult,
            n_experts=published,
            experts_held=n_routed if n_routed < published else 0,
            experts_first=first,
            experts_per_token=int(cfg.get("num_experts_per_tok") or 8),
            moe_d_ff=moe_ff,
            moe_shared_expert=n_shared > 0,
            moe_shared_d_ff=moe_ff * max(n_shared, 1),
            moe_shared_gated=False,
            moe_norm_topk=bool(cfg.get("norm_topk_prob", True)),
            moe_score_func="sigmoid",
            moe_select_bias=True,
            moe_route_scale=float(cfg.get("routed_scaling_factor") or 1.0),
            moe_n_group=int(cfg.get("n_group") or 1),
            moe_topk_group=int(cfg.get("topk_group") or 1),
            n_dense_layers=min(int(cfg.get("first_k_dense_replace") or 0),
                               n_layers),
        )
        if not kw["q_lora_rank"]:
            raise NotImplementedError(
                "deepseek_v3 without q_lora_rank (a plain q projection) "
                "is not supported yet")
        if not n_routed:
            raise NotImplementedError("deepseek_v3 without routed experts")
    elif mt == "olmo_hybrid":
        # allenai Olmo-Hybrid: periods of ``linear_attention`` layers
        # (gated delta rule) closed by one ``full_attention`` layer; the
        # Olmo-3 block (norms on the sub-layers' OUTPUT only); q/k norms
        # over the whole projection; no rotary embedding
        # (``rope_theta: null`` — the recurrent layers carry position)
        types = list(cfg.get("layer_types") or [])
        if len(types) != n_layers or set(types) - {
                "linear_attention", "full_attention"}:
            raise NotImplementedError(
                "olmo_hybrid needs layer_types of linear_attention / "
                f"full_attention for each of its {n_layers} layers")
        n_full = types.count("full_attention")
        per = (n_layers - n_full) // max(1, n_full)
        if not n_full or per < 1 or types != (["linear_attention"] * per
                                   + ["full_attention"]) * n_full:
            raise NotImplementedError(
                "olmo_hybrid layer_types must be equal periods of linear "
                "layers closed by one full layer: the layer scan runs "
                "over periods")
        rope = (cfg.get("rope_parameters") or {}).get("rope_theta") \
            or cfg.get("rope_theta")
        kw.update(
            pre_norm=False,
            sandwich_norms=True,
            qk_norm=True,
            qk_norm_flat=True,
            rotary_pct=1.0 if rope else 0.0,
            rope_theta=float(rope or 10000.0),
            linear_heads=int(cfg.get("linear_num_value_heads") or n_heads),
            linear_d_k=int(cfg.get("linear_key_head_dim") or d_head),
            linear_d_v=int(cfg.get("linear_value_head_dim") or d_head),
            linear_conv=int(cfg.get("linear_conv_kernel_dim") or 4),
            linear_neg_eigval=bool(cfg.get("linear_allow_neg_eigval")),
        )
        if int(cfg.get("linear_num_key_heads")
               or kw["linear_heads"]) != kw["linear_heads"]:
            raise NotImplementedError(
                "olmo_hybrid with fewer linear key heads than value "
                "heads is not supported yet")
    elif mt == "phi":
        kw.update(
            norm_type="layernorm",
            gated_mlp=False,
            hidden_act="gelu_tanh",
            qkv_bias=True,
            o_bias=True,
            mlp_bias=True,
            lm_head_bias=True,
            parallel_residual=True,
            rotary_pct=float(cfg.get("partial_rotary_factor", 0.4)),
        )
    elif mt == "phi3":
        pass  # llama-topology with fused proj names (handled in hf_loader)
    elif mt == "gemma":
        kw.update(
            norm_weight_plus_one=True,
            hidden_act="gelu_tanh",
            embedding_multiplier=float(d_model) ** 0.5,
            tie_word_embeddings=True,
        )
    elif mt == "gemma2":
        kw.update(
            norm_weight_plus_one=True,
            hidden_act="gelu_tanh",
            embedding_multiplier=float(d_model) ** 0.5,
            tie_word_embeddings=True,
            sandwich_norms=True,
            attn_logit_softcap=float(cfg.get("attn_logit_softcapping")
                                     or 0.0),
            logit_softcap=float(cfg.get("final_logit_softcapping") or 0.0),
            query_pre_attn_scalar=float(
                cfg.get("query_pre_attn_scalar") or d_head),
            # every other layer is sliding, odd layers are global
            sliding_window_pattern=2,
        )
    elif mt in ("gemma3", "gemma3_text"):
        kw.update(
            norm_weight_plus_one=True,
            hidden_act="gelu_tanh",
            embedding_multiplier=float(d_model) ** 0.5,
            tie_word_embeddings=True,
            sandwich_norms=True,
            qk_norm=True,
            query_pre_attn_scalar=float(
                cfg.get("query_pre_attn_scalar") or d_head),
            rope_local_base_freq=float(
                cfg.get("rope_local_base_freq") or 10000.0),
            sliding_window_pattern=int(
                cfg.get("sliding_window_pattern") or 6),
            norm_eps=float(cfg.get("rms_norm_eps") or 1e-6),
        )
    else:
        raise NotImplementedError(f"unknown model_type '{mt}'")
    if isinstance(cfg.get("layer_types"), list):
        kw["layer_types"] = tuple(cfg["layer_types"])
    sc = kw.get("rope_scaling") or {}
    rtype = (sc.get("rope_type") or sc.get("type") or "").lower()
    if rtype not in ("", "default", "linear", "llama3", "yarn"):
        raise NotImplementedError(
            f"rope_scaling type '{rtype}' is not supported yet"
        )
    kw["extra"] = {"model_type": mt}
    return LLMSpec(**kw)


def tiny_spec(vocab_size: int = 256, **over: Any) -> LLMSpec:
    """A small spec for tests: runs on CPU in milliseconds."""
    kw: dict[str, Any] = dict(
        vocab_size=vocab_size,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_head=16,
        d_ff=128,
        max_position=512,
    )
    kw.update(over)
    return LLMSpec(**kw)
