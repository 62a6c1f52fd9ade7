"""Load HuggingFace checkpoints into the stacked-scan parameter layout.

Capability counterpart of the reference's model-file loading
(ref: backend/cpp/llama grpc-server.cpp LoadModel :2467 for GGUF;
backend/python/transformers/backend.py:68-200 for HF checkpoints). Here the
on-disk format is HF safetensors; weights are transposed into right-matmul
layout ([in, out]) and stacked on a leading layer axis so the scan body sees
one [L, ...] leaf per projection.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Optional

import jax.numpy as jnp
import numpy as np

from .llm_spec import LLMSpec, spec_from_hf_config
from .transformer import (
    _NON_LAYER_KEYS, DENSE_STACK, LINEAR_STACK, Params,
)


# leaves kept in float32 whatever the serving dtype (an expert layer's
# selection bias decides near-ties between router scores)
F32_LEAVES = ("router_bias", LINEAR_STACK + "a_log",
              LINEAR_STACK + "dt_bias")


def load_hf_state(model_dir: str) -> tuple[dict, Callable[[str], np.ndarray], list[str]]:
    """Return (config dict, tensor getter, tensor names) for a local HF dir."""
    cfg_path = os.path.join(model_dir, "config.json")
    with open(cfg_path) as f:
        config = json.load(f)

    st_files = sorted(
        os.path.join(model_dir, f)
        for f in os.listdir(model_dir)
        if f.endswith(".safetensors") and not f.startswith(".")
    )
    if st_files:
        from safetensors import safe_open

        handles = [safe_open(p, framework="np") for p in st_files]
        index: dict[str, Any] = {}
        for h in handles:
            for name in h.keys():
                index[name] = h

        def get(name: str) -> np.ndarray:
            return index[name].get_tensor(name)

        return config, get, list(index)

    # fallback: pytorch .bin shards via torch (cpu)
    import torch

    state: dict[str, Any] = {}
    for f in sorted(os.listdir(model_dir)):
        if f.endswith(".bin") and "training" not in f:
            state.update(torch.load(os.path.join(model_dir, f), map_location="cpu",
                                    weights_only=True))

    def get_bin(name: str) -> np.ndarray:
        t = state[name].to(torch.float32)
        return t.numpy()

    return config, get_bin, list(state)


def _cast(a: np.ndarray, dtype) -> jnp.ndarray:
    x = jnp.asarray(a)
    return x.astype(dtype)


def _swap_last_two(a):
    return jnp.swapaxes(a, -1, -2)


_jit_swap_last_two = None  # built lazily: jax.jit at import time would
# initialize backends before the caller's platform env is settled


def _jitted_swap():
    global _jit_swap_last_two
    if _jit_swap_last_two is None:
        import jax

        # ONE jitted function reused across leaves/loads so equal shapes
        # share a compiled program (a per-call lambda would retrace every
        # leaf); donated so the load holds one stack-sized transient
        _jit_swap_last_two = jax.jit(_swap_last_two, donate_argnums=0)
    return _jit_swap_last_two


class DeferredT:
    """A parameter leaf held as the RAW host array ([..., out, in] torch
    layout, on-disk dtype) whose transpose/cast is deferred to the
    consumer. ``load_params(..., defer_transpose=True)`` returns these
    for every transposed leaf so the loader can stream them to the
    accelerator and run cast+transpose(+quantize) as ONE fused XLA op
    there — the host-staged eager pipeline (numpy strided copy, CPU
    swapaxes, eager quantize) measured ~10 min for an 8B where the
    device path is tens of seconds.

    The leaf may also be LAZY: constructed with a ``thunk`` instead of
    a materialized array, the disk read itself is deferred until
    ``materialize()``/``raw``. The streaming committer
    (``staging.commit_deferred``) materializes lazy leaves on a reader
    thread pool while earlier leaves transfer to the device, so host IO
    and the host->device link overlap instead of serializing — and the
    host never holds the whole raw tree (only the prefetch window),
    where the eager path staged all ~16 GB of an 8B checkpoint at
    once."""

    __slots__ = ("_raw", "_thunk")

    def __init__(self, raw: Optional[np.ndarray] = None,
                 thunk: Optional[Callable[[], np.ndarray]] = None) -> None:
        if (raw is None) == (thunk is None):
            raise ValueError("DeferredT takes exactly one of raw/thunk")
        self._raw = raw
        self._thunk = thunk

    @property
    def materialized(self) -> bool:
        return self._raw is not None

    def materialize(self) -> np.ndarray:
        """Run the deferred read (idempotent); returns the raw array."""
        if self._raw is None:
            self._raw = np.asarray(self._thunk())
            self._thunk = None
        return self._raw

    @property
    def raw(self) -> np.ndarray:
        return self.materialize()


def load_multimodal(model_dir: str, dtype: Any = jnp.bfloat16,
                    state: Optional[tuple] = None):
    """Load the vision tower of a multimodal checkpoint (gemma3 SigLIP).

    Returns (VisionSpec, VisionParams, mm_info) or None for text-only
    checkpoints. mm_info carries the image-token protocol ids from the
    outer HF config: boi/eoi/image token indices and tokens-per-image
    (ref: the reference's mmproj path — grpc-server.cpp :1476-1502 llava
    embedding; config `mmproj` backend_config.go)."""
    import dataclasses

    from .vision import (
        load_clip_vision_params,
        load_vision_params,
        vision_spec_from_hf,
    )

    config, get, names = state or load_hf_state(model_dir)
    vcfg = config.get("vision_config")
    if not isinstance(vcfg, dict):
        return None
    tcfg = config.get("text_config") or {}
    text_d = int(tcfg.get("hidden_size") or config.get("hidden_size") or 0)
    clip = any(n.endswith("embeddings.class_embedding") for n in names)
    if clip:
        # CLIP/LLaVA family: one soft token per patch, no pooling, no
        # boi/eoi protocol tokens — the <image> placeholder alone is
        # replaced (HF LlavaForConditionalGeneration semantics)
        vspec = vision_spec_from_hf(vcfg, 0, text_d)
        vspec = dataclasses.replace(
            vspec, family="clip", mm_tokens=vspec.n_patches,
            eps=float(vcfg.get("layer_norm_eps") or 1e-5),
        )
        vparams = load_clip_vision_params(get, names, dtype, vspec)
        if vparams is None:
            return None
        mm_info = {
            "boi_token": None,
            "eoi_token": None,
            "image_token": int(config.get("image_token_index") or 32000),
            "mm_tokens": vspec.mm_tokens,
            "image_size": vspec.image_size,
            "family": "clip",
        }
        return vspec, vparams, mm_info
    mm_tokens = int(config.get("mm_tokens_per_image") or 256)
    vspec = vision_spec_from_hf(vcfg, mm_tokens, text_d)
    vparams = load_vision_params(get, names, dtype, vspec)
    if vparams is None:
        return None
    mm_info = {
        "boi_token": int(config.get("boi_token_index") or 255999),
        "eoi_token": int(config.get("eoi_token_index") or 256000),
        "image_token": int(config.get("image_token_index") or 262144),
        "mm_tokens": mm_tokens,
        "image_size": vspec.image_size,
        "family": "siglip",
    }
    return vspec, vparams, mm_info


def load_params(
    model_dir: str,
    dtype: Any = jnp.bfloat16,
    spec_override: Optional[LLMSpec] = None,
    state: Optional[tuple] = None,  # pre-read load_hf_state result, so a
    # caller loading text + vision opens the checkpoint index once
    defer_transpose: bool = False,  # transposed leaves come back as
    # LAZY DeferredT leaves (the read itself deferred); see DeferredT
    phases: Optional[Any] = None,  # LoadPhases accumulator: eager reads
    # bill read_s here; lazy leaves bill at materialization
) -> tuple[LLMSpec, Params]:
    """Load an HF checkpoint directory -> (spec, stacked params)."""
    config, get, names = state or load_hf_state(model_dir)
    if phases is not None:
        _get_raw = get

        def get(name: str) -> np.ndarray:  # noqa: F811
            with phases.timed("read_s"):
                return _get_raw(name)

    spec = spec_override or spec_from_hf_config(config)
    mt = (config.get("model_type") or "").lower()
    L = spec.n_layers

    def t(name: str) -> np.ndarray:
        """Weight in the checkpoint's torch [out, in] layout, untransposed.

        The [in, out] layout the models consume is produced AFTER
        stacking by one XLA transpose per stacked tensor (``stack_t`` /
        ``tcast``): a numpy ``ascontiguousarray(w.T)`` per projection is
        a single-threaded strided copy (~60-250 MB/s) that cost minutes
        on an 8B load, while XLA's transpose is multithreaded and
        cache-blocked (seconds for the whole tree)."""
        return get(name)

    def tcast(x):
        """Cast then swap the last two axes ([..., out, in] -> [..., in,
        out]) on the jax backend (host-staged CPU or device) — or hand
        a LAZY leaf to the consumer under ``defer_transpose`` (the read
        runs when the streaming committer materializes it, overlapped
        with earlier leaves' device transfers). ``x`` may be an array
        or a zero-arg thunk producing one. The transpose donates its
        input so an on-device (non-staged) load holds one stack-sized
        transient, not two."""
        if defer_transpose:
            if callable(x):
                return DeferredT(thunk=lambda: np.asarray(x()))
            return DeferredT(np.asarray(x))
        if callable(x):
            x = x()
        return _jitted_swap()(_cast(x, dtype))

    p: dict[str, Any] = {}
    prefix = ""
    for cand in ("language_model.model.", "model.language_model.",
                 "model."):
        if f"{cand}embed_tokens.weight" in names:
            prefix = cand
            break
    p["embed"] = _cast(get(f"{prefix}embed_tokens.weight"), dtype)

    def stack(fn: Callable[[int], np.ndarray]) -> jnp.ndarray:
        return _cast(np.stack([fn(i) for i in range(L)]), dtype)

    def stack_t(fn: Callable[[int], np.ndarray]):
        """Stack raw [out, in]-layout layers (contiguous memcpy), then
        transpose the trailing axes once in XLA — see ``t``. Passed as
        a thunk so the defer path can postpone the whole read+stack."""
        return tcast(lambda: np.stack([fn(i) for i in range(L)]))

    lp = f"{prefix}layers." + "{i}."
    if mt == "phi":
        p["wq"] = stack_t(lambda i: t(lp.format(i=i) + "self_attn.q_proj.weight"))
        p["wk"] = stack_t(lambda i: t(lp.format(i=i) + "self_attn.k_proj.weight"))
        p["wv"] = stack_t(lambda i: t(lp.format(i=i) + "self_attn.v_proj.weight"))
        p["wo"] = stack_t(lambda i: t(lp.format(i=i) + "self_attn.dense.weight"))
        p["bq"] = stack(lambda i: get(lp.format(i=i) + "self_attn.q_proj.bias"))
        p["bk"] = stack(lambda i: get(lp.format(i=i) + "self_attn.k_proj.bias"))
        p["bv"] = stack(lambda i: get(lp.format(i=i) + "self_attn.v_proj.bias"))
        p["bo"] = stack(lambda i: get(lp.format(i=i) + "self_attn.dense.bias"))
        p["w_up"] = stack_t(lambda i: t(lp.format(i=i) + "mlp.fc1.weight"))
        p["b_up"] = stack(lambda i: get(lp.format(i=i) + "mlp.fc1.bias"))
        p["w_down"] = stack_t(lambda i: t(lp.format(i=i) + "mlp.fc2.weight"))
        p["b_down"] = stack(lambda i: get(lp.format(i=i) + "mlp.fc2.bias"))
        p["ln1_w"] = stack(lambda i: get(lp.format(i=i) + "input_layernorm.weight"))
        p["ln1_b"] = stack(lambda i: get(lp.format(i=i) + "input_layernorm.bias"))
        p["final_norm_w"] = _cast(get(f"{prefix}final_layernorm.weight"), dtype)
        p["final_norm_b"] = _cast(get(f"{prefix}final_layernorm.bias"), dtype)
        p["lm_head"] = tcast(lambda: t("lm_head.weight"))
        p["lm_head_b"] = _cast(get("lm_head.bias"), dtype)
        return spec, p

    if mt == "afmoe":
        return spec, {**p, **_load_afmoe(spec, get, prefix, dtype, tcast)}
    if mt == "olmo_hybrid":
        return spec, {**p, **_load_olmo_hybrid(spec, get, prefix, dtype,
                                               tcast)}
    if mt == "deepseek_v3":
        return spec, {**p, **_load_deepseek_v3(spec, get, prefix, dtype,
                                               tcast)}

    fused_qkv = lp.format(i=0) + "self_attn.qkv_proj.weight" in names  # phi3
    fused_gate = lp.format(i=0) + "mlp.gate_up_proj.weight" in names

    if fused_qkv:
        qd, kvd = spec.q_dim, spec.kv_dim

        def split_qkv(i, part):
            w = get(lp.format(i=i) + "self_attn.qkv_proj.weight")  # [q+2kv, D]
            q, k, v = w[:qd], w[qd : qd + kvd], w[qd + kvd :]
            return {"q": q, "k": k, "v": v}[part]  # raw [out, in]

        p["wq"] = stack_t(lambda i: split_qkv(i, "q"))
        p["wk"] = stack_t(lambda i: split_qkv(i, "k"))
        p["wv"] = stack_t(lambda i: split_qkv(i, "v"))
    else:
        p["wq"] = stack_t(lambda i: t(lp.format(i=i) + "self_attn.q_proj.weight"))
        p["wk"] = stack_t(lambda i: t(lp.format(i=i) + "self_attn.k_proj.weight"))
        p["wv"] = stack_t(lambda i: t(lp.format(i=i) + "self_attn.v_proj.weight"))
        if spec.qkv_bias:
            p["bq"] = stack(lambda i: get(lp.format(i=i) + "self_attn.q_proj.bias"))
            p["bk"] = stack(lambda i: get(lp.format(i=i) + "self_attn.k_proj.bias"))
            p["bv"] = stack(lambda i: get(lp.format(i=i) + "self_attn.v_proj.bias"))
    p["wo"] = stack_t(lambda i: t(lp.format(i=i) + "self_attn.o_proj.weight"))

    if spec.n_experts and mt in ("qwen2_moe", "qwen3_moe"):
        # qwen-family MoE: mlp.gate [E,D] router + mlp.experts.{e}.gate/
        # up/down. qwen2_moe adds an always-on mlp.shared_expert (scaled
        # by mlp.shared_expert_gate [1,D]); its mlp_only/off-step layers
        # carry a plain dense MLP, which lands in the shared slots with
        # zeroed expert/router weights (the _dense_only flag in
        # transformer.py forces their gate to 1). qwen3_moe has neither.
        E, D = spec.n_experts, spec.d_model
        Fm = spec.moe_d_ff or spec.d_ff
        Fs = spec.moe_shared_d_ff or spec.d_ff
        dense_set = set(spec.moe_dense_layers)
        if dense_set and Fs != spec.d_ff:
            raise NotImplementedError(
                "qwen2_moe with dense layers requires "
                "shared_expert_intermediate_size == intermediate_size"
            )

        def experts(i, name):
            # raw torch [E, out, in]; stack_t transposes the trailing axes
            if i in dense_set:
                shape = (E, D, Fm) if name == "down_proj" else (E, Fm, D)
                return np.zeros(shape, np.float32)
            return np.stack([
                get(lp.format(i=i) + f"mlp.experts.{e}.{name}.weight")
                for e in range(E)
            ])

        def shared(i, name):
            base = "mlp." if i in dense_set else "mlp.shared_expert."
            return t(lp.format(i=i) + base + f"{name}.weight")

        p["router"] = stack_t(
            lambda i: np.zeros((E, D), np.float32) if i in dense_set
            else t(lp.format(i=i) + "mlp.gate.weight"))
        p["moe_gate"] = stack_t(lambda i: experts(i, "gate_proj"))
        p["moe_up"] = stack_t(lambda i: experts(i, "up_proj"))
        p["moe_down"] = stack_t(lambda i: experts(i, "down_proj"))
        if spec.moe_shared_expert:
            p["shared_gate"] = stack_t(lambda i: shared(i, "gate_proj"))
            p["shared_up"] = stack_t(lambda i: shared(i, "up_proj"))
            p["shared_down"] = stack_t(lambda i: shared(i, "down_proj"))
            p["shared_router"] = stack(
                lambda i: np.zeros((D,), np.float32) if i in dense_set
                else get(lp.format(i=i)
                         + "mlp.shared_expert_gate.weight")[0])
    elif spec.n_experts:
        # mixtral: block_sparse_moe.gate [E,D] router + per-expert
        # w1 (gate) / w3 (up) / w2 (down), stacked [L, E, in, out]
        E = spec.n_experts

        def experts(i, name):
            # raw torch [E, out, in]; stack_t transposes the trailing axes
            return np.stack([
                get(lp.format(i=i)
                    + f"block_sparse_moe.experts.{e}.{name}.weight")
                for e in range(E)
            ])

        p["router"] = stack_t(
            lambda i: t(lp.format(i=i) + "block_sparse_moe.gate.weight"))
        p["moe_gate"] = stack_t(lambda i: experts(i, "w1"))
        p["moe_up"] = stack_t(lambda i: experts(i, "w3"))
        p["moe_down"] = stack_t(lambda i: experts(i, "w2"))
    elif fused_gate:
        F = spec.d_ff

        def split_gate(i, part):
            w = get(lp.format(i=i) + "mlp.gate_up_proj.weight")  # [2F, D]
            g, u = w[:F], w[F:]
            return g if part == "g" else u  # raw [out, in]

        p["w_gate"] = stack_t(lambda i: split_gate(i, "g"))
        p["w_up"] = stack_t(lambda i: split_gate(i, "u"))
    else:
        if spec.gated_mlp:
            p["w_gate"] = stack_t(lambda i: t(lp.format(i=i) + "mlp.gate_proj.weight"))
        p["w_up"] = stack_t(lambda i: t(lp.format(i=i) + "mlp.up_proj.weight"))
    if not spec.n_experts:
        p["w_down"] = stack_t(lambda i: t(lp.format(i=i) + "mlp.down_proj.weight"))

    if spec.qk_norm:  # qwen3 per-head q/k norms
        p["q_norm_w"] = stack(
            lambda i: get(lp.format(i=i) + "self_attn.q_norm.weight"))
        p["k_norm_w"] = stack(
            lambda i: get(lp.format(i=i) + "self_attn.k_norm.weight"))

    p["ln1_w"] = stack(lambda i: get(lp.format(i=i) + "input_layernorm.weight"))
    if spec.sandwich_norms:
        # gemma2: post_attention_layernorm is the POST-attn sandwich norm;
        # the pre-FFW norm has its own name
        p["ln_post_attn_w"] = stack(
            lambda i: get(lp.format(i=i) + "post_attention_layernorm.weight"))
        p["ln2_w"] = stack(
            lambda i: get(lp.format(i=i) + "pre_feedforward_layernorm.weight"))
        p["ln_post_ffw_w"] = stack(
            lambda i: get(lp.format(i=i) + "post_feedforward_layernorm.weight"))
    else:
        p["ln2_w"] = stack(
            lambda i: get(lp.format(i=i) + "post_attention_layernorm.weight")
        )
    p["final_norm_w"] = _cast(get(f"{prefix}norm.weight"), dtype)
    if not spec.tie_word_embeddings:
        # multimodal wrappers nest the head (llava: language_model.lm_head)
        for head in ("lm_head.weight", "language_model.lm_head.weight"):
            if head in names:
                p["lm_head"] = tcast(lambda head=head: t(head))
                break
        else:  # checkpoint ties despite config
            object.__setattr__(spec, "tie_word_embeddings", True)

    return spec, p


def _load_afmoe(spec: LLMSpec, get, prefix: str, dtype, tcast) -> dict:
    """The leaves of an ``afmoe`` checkpoint (arcee Trinity; HF
    AfmoeForCausalLM) beside the embedding: TWO stacks — the first
    ``num_dense_layers`` layers (a dense SwiGLU MLP, leaves under
    ``DENSE_STACK``) and the expert layers (router ``mlp.router.gate``
    [E, D], selection bias ``mlp.expert_bias`` [E] kept in f32,
    ``mlp.experts.{e}``, one always-on ``mlp.shared_experts``) — each
    layer with four norms, q/k norms and the attention output gate
    ``self_attn.gate_proj``; final norm and an untied head."""
    E, L, Ld = spec.n_experts, spec.n_layers, spec.n_dense_layers

    def name(i, tail):
        return f"{prefix}layers.{i}.{tail}.weight"

    def stack_of(layers, experts: bool) -> dict:
        def vec(tail):
            return _cast(np.stack([get(name(i, tail)) for i in layers]),
                         dtype)

        def mat(tail):
            return tcast(lambda: np.stack(
                [get(name(i, tail)) for i in layers]))

        def expert_mats(proj):
            return tcast(lambda: np.stack([np.stack(
                [get(name(i, f"mlp.experts.{e}.{proj}")) for e in range(E)])
                for i in layers]))

        out = {
            "wq": mat("self_attn.q_proj"), "wk": mat("self_attn.k_proj"),
            "wv": mat("self_attn.v_proj"), "wo": mat("self_attn.o_proj"),
            "w_attn_gate": mat("self_attn.gate_proj"),
            "q_norm_w": vec("self_attn.q_norm"),
            "k_norm_w": vec("self_attn.k_norm"),
            "ln1_w": vec("input_layernorm"),
            "ln_post_attn_w": vec("post_attention_layernorm"),
            "ln2_w": vec("pre_mlp_layernorm"),
            "ln_post_ffw_w": vec("post_mlp_layernorm"),
        }
        if not experts:
            out.update(w_gate=mat("mlp.gate_proj"), w_up=mat("mlp.up_proj"),
                       w_down=mat("mlp.down_proj"))
            return out
        out.update(
            router=mat("mlp.router.gate"),
            # the bias decides the selection in f32, whatever the dtype
            router_bias=jnp.asarray(np.stack(
                [np.asarray(get(f"{prefix}layers.{i}.mlp.expert_bias"),
                            np.float32) for i in layers])),
            moe_gate=expert_mats("gate_proj"), moe_up=expert_mats("up_proj"),
            moe_down=expert_mats("down_proj"))
        if spec.moe_shared_expert:
            out.update(shared_gate=mat("mlp.shared_experts.gate_proj"),
                       shared_up=mat("mlp.shared_experts.up_proj"),
                       shared_down=mat("mlp.shared_experts.down_proj"))
        return out

    p = stack_of(range(Ld, L), True)
    if Ld:
        p.update({DENSE_STACK + k: v
                  for k, v in stack_of(range(Ld), False).items()})
    p["final_norm_w"] = _cast(get(f"{prefix}norm.weight"), dtype)
    p["lm_head"] = tcast(lambda: get("lm_head.weight"))
    return p


def _load_deepseek_v3(spec: LLMSpec, get, prefix: str, dtype,
                      tcast) -> dict:
    """The leaves of a ``deepseek_v3`` checkpoint (HF
    DeepseekV3ForCausalLM) beside the embedding: TWO stacks — the first
    ``first_k_dense_replace`` layers (a dense SwiGLU MLP, leaves under
    ``DENSE_STACK``) and the expert layers (router ``mlp.gate.weight``
    [E published, D], ``mlp.gate.e_score_correction_bias`` [E] kept in
    f32, ``mlp.experts.{e}`` for the PUBLISHED ids e this chip holds —
    ``spec.experts_first`` .. + ``spec.n_held`` — and
    ``mlp.shared_experts``), each layer with latent attention:
    ``self_attn.{q_a_proj, q_a_layernorm, q_b_proj, kv_a_proj_with_mqa,
    kv_a_layernorm, kv_b_proj, o_proj}``. ``kv_b_proj`` is kept as the
    two halves the absorbed form contracts with (``wkv_b_k`` [H, d_n,
    r], ``wkv_b_v`` [H, r, d_v]). The rotary columns of ``q_b_proj``
    and ``kv_a_proj_with_mqa`` are moved from the checkpoint's
    interleaved pairs (2i, 2i+1) to the rotate-half layout (i, i + d/2)
    the program rotates — the same permutation on q and k, so every
    score is unchanged. The multi-token-prediction module
    (``model.layers.{num_hidden_layers}`` ..) is not read."""
    L, Ld = spec.n_layers, spec.n_dense_layers
    H, dn, dr = spec.n_heads, spec.qk_nope_dim, spec.qk_rope_dim
    dv, r = spec.v_head_dim, spec.kv_lora_rank
    held = range(spec.experts_first, spec.experts_first + spec.n_held)
    half = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])

    def name(i, tail):
        return f"{prefix}layers.{i}.{tail}.weight"

    def q_b(i):  # [H * (dn + dr), rq], rotary rows de-interleaved
        w = np.asarray(get(name(i, "self_attn.q_b_proj")))
        w = w.reshape(H, dn + dr, -1)
        return np.concatenate([w[:, :dn], w[:, dn + half]], axis=1).reshape(
            H * (dn + dr), -1)

    def kv_a(i):  # [r + dr, D]
        w = np.asarray(get(name(i, "self_attn.kv_a_proj_with_mqa")))
        return np.concatenate([w[:r], w[r + half]], axis=0)

    def kv_b(i):  # [H, dn + dv, r]
        return np.asarray(get(name(i, "self_attn.kv_b_proj"))).reshape(
            H, dn + dv, r)

    def stack_of(layers, experts: bool) -> dict:
        def vec(tail):
            return _cast(np.stack([get(name(i, tail)) for i in layers]),
                         dtype)

        def mat(tail, fn=None):
            return tcast(lambda: np.stack(
                [fn(i) if fn else get(name(i, tail)) for i in layers]))

        def expert_mats(proj):
            return tcast(lambda: np.stack([np.stack(
                [get(name(i, f"mlp.experts.{e}.{proj}")) for e in held])
                for i in layers]))

        kvb = np.stack([kv_b(i) for i in layers])
        out = {
            "wq_a": mat("self_attn.q_a_proj"),
            "q_a_norm_w": vec("self_attn.q_a_layernorm"),
            "wq_b": mat("", q_b), "wkv_a": mat("", kv_a),
            "kv_a_norm_w": vec("self_attn.kv_a_layernorm"),
            # [n, H, dn, r] as stored; [n, H, r, dv] transposed
            "wkv_b_k": _cast(kvb[:, :, :dn], dtype),
            "wkv_b_v": _cast(kvb[:, :, dn:].transpose(0, 1, 3, 2), dtype),
            "wo": mat("self_attn.o_proj"),
            "ln1_w": vec("input_layernorm"),
            "ln2_w": vec("post_attention_layernorm"),
        }
        if not experts:
            out.update(w_gate=mat("mlp.gate_proj"), w_up=mat("mlp.up_proj"),
                       w_down=mat("mlp.down_proj"))
            return out
        out.update(
            router=mat("mlp.gate"),
            # the bias decides the selection in f32, whatever the dtype
            router_bias=jnp.asarray(np.stack([np.asarray(get(
                f"{prefix}layers.{i}.mlp.gate.e_score_correction_bias"),
                np.float32) for i in layers])),
            moe_gate=expert_mats("gate_proj"), moe_up=expert_mats("up_proj"),
            moe_down=expert_mats("down_proj"))
        if spec.moe_shared_expert:
            out.update(shared_gate=mat("mlp.shared_experts.gate_proj"),
                       shared_up=mat("mlp.shared_experts.up_proj"),
                       shared_down=mat("mlp.shared_experts.down_proj"))
        return out

    p = stack_of(range(Ld, L), True)
    if Ld:
        p.update({DENSE_STACK + k: v
                  for k, v in stack_of(range(Ld), False).items()})
    p["final_norm_w"] = _cast(get(f"{prefix}norm.weight"), dtype)
    p["lm_head"] = tcast(lambda: get("lm_head.weight"))
    return p


def _load_olmo_hybrid(spec: LLMSpec, get, prefix: str, dtype,
                      tcast) -> dict:
    """The leaves of an ``olmo_hybrid`` checkpoint beside the embedding:
    TWO stacks that share no mixer weights — the full-attention layers
    (``self_attn.{q,k,v,o}_proj``, q/k norms over the whole projection)
    and, under ``LINEAR_STACK``, the linear-attention layers
    (``linear_attn``: the gated delta rule's q/k/v/g/a/b/o projections,
    the three depthwise ``*_conv1d`` kernels joined in q, k, v order,
    ``A_log`` and ``dt_bias`` kept in f32, ``o_norm``) — each layer with
    its SwiGLU MLP and Olmo-3's two post-norms; final norm, untied
    head. Names as ``flash-linear-attention``'s GatedDeltaNet and HF
    Olmo-3 have them (benchmark/models/olmo_hybrid.py lists them)."""
    kinds = spec.layer_types

    def name(i, tail):
        return f"{prefix}layers.{i}.{tail}"

    def stack_of(layers, linear: bool) -> dict:
        def vec(tail, dt=dtype):
            return _cast(np.stack([get(name(i, tail)) for i in layers]), dt)

        def mat(tail):
            return tcast(lambda: np.stack(
                [get(name(i, tail + ".weight")) for i in layers]))

        out = {
            "w_gate": mat("mlp.gate_proj"), "w_up": mat("mlp.up_proj"),
            "w_down": mat("mlp.down_proj"),
            "ln_post_attn_w": vec("post_attention_layernorm.weight"),
            "ln_post_ffw_w": vec("post_feedforward_layernorm.weight"),
        }
        if not linear:
            out.update({"w" + p: mat(f"self_attn.{p}_proj") for p in "qkvo"})
            out.update(q_norm_w=vec("self_attn.q_norm.weight"),
                       k_norm_w=vec("self_attn.k_norm.weight"))
            return out
        out.update({"w" + p: mat(f"linear_attn.{p}_proj")
                    for p in "qkvgabo"})
        out.update(
            conv_w=_cast(np.stack([np.concatenate(
                [np.asarray(get(name(i, f"linear_attn.{p}_conv1d.weight")))
                 [:, 0, :] for p in "qkv"]) for i in layers]), dtype),
            a_log=vec("linear_attn.A_log", jnp.float32),
            dt_bias=vec("linear_attn.dt_bias", jnp.float32),
            o_norm_w=vec("linear_attn.o_norm.weight"))
        return out

    L = spec.n_layers
    p = stack_of([i for i in range(L) if kinds[i] == "full_attention"],
                 False)
    p.update({LINEAR_STACK + k: v for k, v in stack_of(
        [i for i in range(L) if kinds[i] == "linear_attention"],
        True).items()})
    p["final_norm_w"] = _cast(get(f"{prefix}norm.weight"), dtype)
    p["lm_head"] = tcast(lambda: get("lm_head.weight"))
    return p


def layer_pages(host_tree: dict, n_layers: int):
    """Partition a parameter tree into the weight pager's transfer units.

    The stacked-scan layout makes layer granularity free: every per-layer
    leaf is a single ``[L, ...]`` array, so "page li" is just row ``li``
    of each stacked leaf — no per-tensor bookkeeping, and the promotion
    path can reassemble the stacked tree with one
    ``dynamic_update_index_in_dim`` per leaf per layer
    (engine/weight_pager.py). Returns ``(layered, globals_, page)``:

    - ``layered``: the stacked ``[L, ...]`` leaves (keys not in
      :data:`~localai_tfp_tpu.models.transformer._NON_LAYER_KEYS`),
    - ``globals_``: the unstacked leaves (embeddings, final norm,
      lm head) that travel as one extra "globals" page,
    - ``page(li)``: dict of layer ``li``'s rows, slicing through
      :class:`~localai_tfp_tpu.models.transformer.QTensor` leaves
      (row of ``q`` and of ``scale`` — the int8 planes and their scale
      planes page together so a round trip stays bit-exact).

    Works on host (numpy) and device (jax) trees alike; the pager uses
    it on the host mirror so slicing never touches HBM.
    """
    layered = {k: v for k, v in host_tree.items() if k not in _NON_LAYER_KEYS}
    globals_ = {k: v for k, v in host_tree.items() if k in _NON_LAYER_KEYS}

    def page(li: int) -> dict:
        if not 0 <= li < n_layers:
            raise IndexError(f"layer page {li} outside [0, {n_layers})")
        out = {}
        for k, v in layered.items():
            if hasattr(v, "q"):  # QTensor: slice both planes
                out[k] = type(v)(v.q[li], v.scale[li])
            else:
                out[k] = v[li]
        return out

    return layered, globals_, page
