"""TPU-native decoder-only transformer (pure JAX, stacked-layer scan).

This is the compute core that replaces the reference's llama.cpp engine
(ref: backend/cpp/llama/grpc-server.cpp — llama_decode at :2002 is the
device-boundary call this module corresponds to). Design choices are
TPU-first, not a translation:

- All layers are stacked on a leading axis and executed with ``lax.scan``:
  one compiled layer body regardless of depth => fast compiles, and XLA
  pipelines the weight fetches from HBM.
- One ``forward`` covers prefill (T=chunk) and decode (T=1); shapes are
  static per (batch, T) bucket so XLA never recompiles in the serving hot
  loop (SURVEY.md §7 hard part #1).
- KV cache is a preallocated ``[L, B, S, H_kv, Dh]`` array per k/v; writes
  are per-slot scatters so a continuous-batching scheduler can interleave
  requests at different offsets (the TPU answer to llama.cpp's slot
  ``cache_tokens``, grpc-server.cpp:188-385).
- bfloat16 activations/weights by default; logits in float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import expert_rows as er
from ..ops import grouped_matmul as gm
from . import cache_attention as ca
from .cache_attention import (  # noqa: F401  (their home; imported here)
    _attend, _quantize_rows, latent_absorb_out, latent_absorb_query,
    latent_attend_expanded, latent_scale,
)
from .llm_spec import LLMSpec
from .quant import QTensor as _QTensor
from .quant import mm as _mm  # plain or int8-QTensor matmul

Params = dict[str, jax.Array]

# Leaves of the LEADING dense-MLP layers of an expert model
# (LLMSpec.n_dense_layers) carry this prefix and a leading dim of their
# own: two homogeneous stacks, scanned in turn (``layer_stacks``)
DENSE_STACK = "dense."
# Leaves of the linear-attention layers of a hybrid model
# (LLMSpec.linear_heads) carry this prefix and a leading dim of their
# own, [n_linear_layers, ...]; the unprefixed layer leaves are the
# full-attention layers', [n_kv_layers, ...]. One PERIOD — its linear
# layers, then its full layer — is what the layer scan steps over
LINEAR_STACK = "lin."


@dataclass
class KVCache:
    """Preallocated paged-by-slot KV cache.

    k/v: [n_layers, n_slots, max_seq, n_kv_heads * d_head]. The head dim is
    stored FLAT: kv_dim (>=512 for real models) fills whole 128-lane TPU
    vector registers, where a trailing d_head=64 axis would waste half of
    every register row and (measured on v5e) makes the per-step cache
    update ~6x slower. Heads are re-split only transiently for the
    attention contraction. ``lengths`` is host-side metadata owned by the
    engine; the arrays carry no ragged state so they can be donated through
    jit every step.
    """

    k: jax.Array
    v: jax.Array
    # int8 mode (ref: llama.cpp cache_type_k/v q8 — grpc-server.cpp
    # :2337-2342): per-(layer, slot, position) row scales; None = raw
    k_scale: Any = None  # [L, n_slots, max_seq] f32
    v_scale: Any = None
    # a model with linear-attention layers (LLMSpec.linear_heads): the
    # recurrent state of every such layer, addressed by SLOT whatever
    # the k/v arrays are addressed by (slots, or pages of the pool) —
    # ``state`` [Ll, slots, H / G, d_k, G * d_v] f32 (ops/gated_delta.py
    # ``head_group``), ``conv`` [Ll, slots, taps - 1, conv_dim]: the
    # rows the causal convolution still needs. k/v hold the
    # full-attention layers only (``spec.n_kv_layers``). None otherwise
    state: Any = None
    conv: Any = None
    # a model with latent attention (LLMSpec.kv_lora_rank): ``k`` holds
    # the latent row [c after its norm | k_r after rotary | zeros to a
    # whole lane vector] (``spec.latent_row`` values) and NOTHING else
    # is cached — ``v`` keeps its place in the tree with ZERO lanes
    # ([L, slots, seq, 0]: no bytes), so that every path that moves
    # pages as (k, v) pairs moves latent pages unchanged. int8 rows are
    # refused (the engine says so by name)

    @classmethod
    def create(
        cls,
        spec: LLMSpec,
        n_slots: int,
        max_seq: int,
        dtype: Any = jnp.bfloat16,
        state_slots: Optional[int] = None,  # slots of the recurrent
        # state when ``n_slots`` counts pool pages (None: n_slots)
    ) -> "KVCache":
        shape = (spec.n_kv_layers, n_slots, max_seq, spec.kv_dim)
        quant = dtype in (jnp.int8, "int8", "q8", "q8_0")
        if spec.kv_lora_rank:
            if quant:
                raise NotImplementedError(
                    f"{spec.model_type}: an int8 latent cache row is not "
                    "supported (kv_cache_dtype: int8)")
            return cls(k=jnp.zeros(shape, dtype),
                       v=jnp.zeros((*shape[:3], 0), dtype))
        extra = {}
        if spec.linear_heads:
            from ..ops.gated_delta import state_shape

            ns = n_slots if state_slots is None else state_slots
            Ll = spec.n_linear_layers
            extra = {
                "state": jnp.zeros((Ll, ns, *state_shape(
                    spec.linear_heads, spec.linear_d_k, spec.linear_d_v)),
                    jnp.float32),
                "conv": jnp.zeros(
                    (Ll, ns, spec.linear_conv - 1, spec.linear_conv_dim),
                    jnp.bfloat16 if quant else dtype)}
        if quant:
            sshape = shape[:3]
            return cls(
                k=jnp.zeros(shape, jnp.int8),
                v=jnp.zeros(shape, jnp.int8),
                k_scale=jnp.zeros(sshape, jnp.float32),
                v_scale=jnp.zeros(sshape, jnp.float32),
                **extra,
            )
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   **extra)

    def with_kv(self, k, v, k_scale=None, v_scale=None) -> "KVCache":
        """Other k/v arrays beside the same recurrent state."""
        return KVCache(k=k, v=v, k_scale=k_scale, v_scale=v_scale,
                       state=self.state, conv=self.conv)

    @property
    def quantized(self) -> bool:
        return self.k.dtype == jnp.int8

    @property
    def n_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_seq(self) -> int:
        return self.k.shape[2]


jax.tree_util.register_pytree_node(
    KVCache,
    lambda c: ((c.k, c.v, c.k_scale, c.v_scale, c.state, c.conv), None),
    lambda _, ch: KVCache(*ch),
)


# ---------------------------------------------------------------------------
# paged KV pool views (engine/kv_pool.py owns the host-side allocator)
# ---------------------------------------------------------------------------


def gather_kv_pages(arena: KVCache, phys: jax.Array, page: int) -> KVCache:
    """Materialize a contiguous per-slot window view [L, B, W, F] from a
    paged arena [L, n_pages, page, F] through per-slot page tables
    ``phys [B, W//page]`` (int32 physical page ids; unallocated entries
    point at the trash page, whose garbage is causally masked). The view
    is shape- and value-identical to the dense windowed cache, so the
    forward math — and therefore the sampled token stream — is
    byte-identical on both paths."""
    L = arena.k.shape[0]
    B, wp = phys.shape

    def g4(a):  # (a latent arena's ``v`` has no lanes: its own width)
        return a[:, phys].reshape(L, B, wp * page, a.shape[-1])

    def g3(a):
        return a[:, phys].reshape(a.shape[0], B, wp * page)

    return arena.with_kv(
        g4(arena.k), g4(arena.v),
        g3(arena.k_scale) if arena.quantized else None,
        g3(arena.v_scale) if arena.quantized else None,
    )


def scatter_kv_pages(arena: KVCache, win: KVCache, wb: jax.Array,
                     page: int) -> KVCache:
    """Write a window view back into the arena. ``wb [B, W//page]``
    carries the physical destination per (slot, window-page); entries
    whose page must NOT be written (shared prefix pages, parked rows,
    pages outside the dispatch's write span) point at the trash page —
    duplicate trash indices are fine, the losing garbage is never read.
    The host guarantees every non-trash wb entry is privately owned, so
    no two rows ever scatter to the same live page."""
    L = arena.k.shape[0]
    B, wp = wb.shape

    def s4(a, w):
        return a.at[:, wb].set(w.reshape(L, B, wp, page, a.shape[-1]))

    def s3(a, w):
        return a.at[:, wb].set(w.reshape(a.shape[0], B, wp, page))

    # the recurrent state is the VIEW's: the forward advanced it there
    return win.with_kv(
        s4(arena.k, win.k), s4(arena.v, win.v),
        s3(arena.k_scale, win.k_scale) if arena.quantized else None,
        s3(arena.v_scale, win.v_scale) if arena.quantized else None,
    )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(
    rng: jax.Array, spec: LLMSpec, dtype: Any = jnp.bfloat16
) -> Params:
    """Random-init parameters (tests / bring-up; real weights via hf_loader)."""
    keys = iter(jax.random.split(rng, 16))

    def dense(key, shape, scale=None):
        scale = scale or 1.0 / math.sqrt(shape[-2] if len(shape) > 1 else 1)
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    D, F, V = spec.d_model, spec.d_ff, spec.vocab_size
    Ld = spec.n_dense_layers if spec.n_experts else 0

    def stack(L, experts, keys):
        """One homogeneous stack of L layers: attention, norms, and a
        dense or an expert MLP."""
        if spec.kv_lora_rank:  # latent attention (``_latent_mixer``)
            H, rq, rkv = spec.n_heads, spec.q_lora_rank, spec.kv_lora_rank
            p = {
                "wq_a": dense(next(keys), (L, D, rq)),
                "q_a_norm_w": jnp.ones((L, rq), dtype),
                "wq_b": dense(next(keys), (L, rq, spec.q_dim)),
                "wkv_a": dense(next(keys), (L, D, spec.latent_width)),
                "kv_a_norm_w": jnp.ones((L, rkv), dtype),
                "wkv_b_k": dense(next(keys), (L, H, spec.qk_nope_dim, rkv),
                                 1.0 / math.sqrt(rkv)),
                "wkv_b_v": dense(next(keys), (L, H, rkv, spec.v_head_dim)),
                "wo": dense(next(keys), (L, spec.o_dim, D)),
            }
        else:
            p = {
                "wq": dense(next(keys), (L, D, spec.q_dim)),
                "wk": dense(next(keys), (L, D, spec.kv_dim)),
                "wv": dense(next(keys), (L, D, spec.kv_dim)),
                "wo": dense(next(keys), (L, spec.q_dim, D)),
            }
        if spec.pre_norm:
            p["ln1_w"] = jnp.ones((L, D), dtype)
        if spec.attn_output_gate:
            p["w_attn_gate"] = dense(next(keys), (L, D, spec.q_dim))
        if experts:
            E = spec.n_experts
            Fm = spec.moe_d_ff or F
            p["router"] = dense(next(keys), (L, D, E), 0.02)
            if spec.moe_select_bias:
                p["router_bias"] = (jax.random.normal(
                    next(keys), (L, E), jnp.float32) * 0.02)
            E = spec.n_held  # the router is as wide as the published
            # count; the matrices are those of the experts held
            p["moe_gate"] = dense(next(keys), (L, E, D, Fm))
            p["moe_up"] = dense(next(keys), (L, E, D, Fm))
            p["moe_down"] = dense(next(keys), (L, E, Fm, D))
            if spec.moe_shared_expert:
                Fs = spec.moe_shared_d_ff or F
                p["shared_gate"] = dense(next(keys), (L, D, Fs))
                p["shared_up"] = dense(next(keys), (L, D, Fs))
                p["shared_down"] = dense(next(keys), (L, Fs, D))
                if spec.moe_shared_gated:
                    p["shared_router"] = dense(next(keys), (L, D), 0.02)
        else:
            p["w_up"] = dense(next(keys), (L, D, F))
            p["w_down"] = dense(next(keys), (L, F, D))
            if spec.gated_mlp:
                p["w_gate"] = dense(next(keys), (L, D, F))
        if not spec.parallel_residual and spec.pre_norm:
            p["ln2_w"] = jnp.ones((L, D), dtype)
        if spec.qk_norm:
            qn, kn = ((spec.q_dim, spec.kv_dim) if spec.qk_norm_flat
                      else (spec.d_head, spec.d_head))
            p["q_norm_w"] = jnp.ones((L, qn), dtype)
            p["k_norm_w"] = jnp.ones((L, kn), dtype)
        if spec.sandwich_norms:
            p["ln_post_attn_w"] = jnp.ones((L, D), dtype)
            p["ln_post_ffw_w"] = jnp.ones((L, D), dtype)
        if spec.norm_type == "layernorm":
            p["ln1_b"] = jnp.zeros((L, D), dtype)
            if "ln2_w" in p:
                p["ln2_b"] = jnp.zeros((L, D), dtype)
        if spec.qkv_bias:
            p["bq"] = jnp.zeros((L, spec.q_dim), dtype)
            p["bk"] = jnp.zeros((L, spec.kv_dim), dtype)
            p["bv"] = jnp.zeros((L, spec.kv_dim), dtype)
        if spec.o_bias:
            p["bo"] = jnp.zeros((L, D), dtype)
        if spec.mlp_bias and not experts:
            p["b_up"] = jnp.zeros((L, F), dtype)
            p["b_down"] = jnp.zeros((L, D), dtype)
        return p

    def linear_stack(L, keys):
        """The linear-attention layers of a hybrid model: the gated
        delta rule's projections, its convolution and per-head decay
        parameters, the layer's MLP and post-norms."""
        H, dk, dv = spec.linear_heads, spec.linear_d_k, spec.linear_d_v
        return {
            "wq": dense(next(keys), (L, D, H * dk)),
            "wk": dense(next(keys), (L, D, H * dk)),
            "wv": dense(next(keys), (L, D, H * dv)),
            "wg": dense(next(keys), (L, D, H * dv)),
            "wa": dense(next(keys), (L, D, H)),
            "wb": dense(next(keys), (L, D, H)),
            "wo": dense(next(keys), (L, H * dv, D)),
            "conv_w": dense(next(keys), (L, spec.linear_conv_dim,
                                         spec.linear_conv), 0.5),
            "a_log": jax.random.uniform(next(keys), (L, H), jnp.float32,
                                        -0.25, 0.25),
            "dt_bias": jax.random.uniform(next(keys), (L, H), jnp.float32,
                                          -0.25, 0.25),
            "o_norm_w": jnp.ones((L, dv), dtype),
            "w_up": dense(next(keys), (L, D, F)),
            "w_down": dense(next(keys), (L, F, D)),
            "w_gate": dense(next(keys), (L, D, F)),
            "ln_post_attn_w": jnp.ones((L, D), dtype),
            "ln_post_ffw_w": jnp.ones((L, D), dtype),
        }

    p: Params = {"embed": dense(next(keys), (V, D), 0.02)}
    p.update(stack(spec.n_kv_layers - Ld, bool(spec.n_experts), keys))
    if spec.linear_heads:
        lkeys = iter(jax.random.split(jax.random.fold_in(rng, 2), 16))
        p.update({LINEAR_STACK + k: v for k, v in linear_stack(
            spec.n_linear_layers, lkeys).items()})
    if Ld:
        # keys of its own: what the main stack draws stays what it drew
        dkeys = iter(jax.random.split(jax.random.fold_in(rng, 1), 16))
        p.update({DENSE_STACK + k: v
                  for k, v in stack(Ld, False, dkeys).items()})
    if spec.final_norm:
        p["final_norm_w"] = jnp.ones((D,), dtype)
        if spec.norm_type == "layernorm":
            p["final_norm_b"] = jnp.zeros((D,), dtype)
    if not spec.tie_word_embeddings:
        p["lm_head"] = dense(next(keys), (D, V), 0.02)
    if spec.lm_head_bias:
        p["lm_head_b"] = jnp.zeros((V,), dtype)
    return p


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def _norm(spec: LLMSpec, x: jax.Array, w: jax.Array, b: Optional[jax.Array]):
    xf = x.astype(jnp.float32)
    if spec.norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * lax.rsqrt(var + spec.norm_eps)
    else:
        out = xf * lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + spec.norm_eps
        )
    wf = w.astype(jnp.float32)
    if spec.norm_weight_plus_one:
        wf = wf + 1.0
    out = out * wf
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_inv_freq(spec: LLMSpec) -> jnp.ndarray:
    """Rotary inverse frequencies, including llama3 / linear / yarn scaling
    (ref knobs: rope_scaling none/linear/yarn, core/config/backend_config.go
    :158-164 and grpc-server.cpp:2419-2433)."""
    rd = spec.rotary_dim
    inv = 1.0 / (
        spec.rope_theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    )
    sc = spec.rope_scaling or {}
    rtype = (sc.get("rope_type") or sc.get("type") or "").lower()
    if rtype == "linear":
        inv = inv / float(sc.get("factor", 1.0))
    elif rtype == "llama3":
        factor = float(sc.get("factor", 8.0))
        lo = float(sc.get("low_freq_factor", 1.0))
        hi = float(sc.get("high_freq_factor", 4.0))
        orig = float(sc.get("original_max_position_embeddings", 8192))
        wavelen = 2 * math.pi / inv
        ratio = orig / wavelen
        smooth = jnp.clip((ratio - lo) / (hi - lo), 0.0, 1.0)
        scaled = jnp.where(
            wavelen > orig / lo,  # low-frequency band: fully scaled
            inv / factor,
            jnp.where(
                wavelen < orig / hi,  # high-frequency band: unscaled
                inv,
                (1 - smooth) * inv / factor + smooth * inv,
            ),
        )
        inv = scaled
    elif rtype == "yarn":
        factor = float(sc.get("factor", 1.0))
        orig = float(sc.get("original_max_position_embeddings", 4096))
        beta_fast = float(sc.get("beta_fast", 32.0))
        beta_slow = float(sc.get("beta_slow", 1.0))

        def corr_dim(num_rot):
            return (rd * math.log(orig / (num_rot * 2 * math.pi))) / (
                2 * math.log(spec.rope_theta)
            )

        low = max(math.floor(corr_dim(beta_fast)), 0)
        high = min(math.ceil(corr_dim(beta_slow)), rd - 1)
        ramp = jnp.clip(
            (jnp.arange(rd // 2, dtype=jnp.float32) - low) / max(high - low, 1),
            0.0,
            1.0,
        )
        inv = inv / factor * ramp + inv * (1 - ramp)
    return inv


def rope_attn_scale(spec: LLMSpec) -> float:
    """YaRN attention scaling (mscale): HF multiplies cos/sin by
    ``attention_factor`` — the block's own when it gives one;
    mscale(factor, mscale) / mscale(factor, mscale_all_dim) when it
    gives both (deepseek: equal, so 1.0 — its mscale^2 sits in the
    softmax scale, ``LLMSpec.attn_scale_mult``); 0.1*ln(factor)+1 when
    it gives neither. mscale(f, m) = 0.1*m*ln(f)+1 for f > 1, else 1."""
    sc = spec.rope_scaling or {}
    rtype = (sc.get("rope_type") or sc.get("type") or "").lower()
    if rtype != "yarn":
        return 1.0
    af = sc.get("attention_factor")
    if af is not None:
        return float(af)
    factor = float(sc.get("factor", 1.0))

    def mscale(m: float) -> float:
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    if sc.get("mscale") and sc.get("mscale_all_dim"):
        return mscale(float(sc["mscale"])) / mscale(
            float(sc["mscale_all_dim"]))
    return 0.1 * math.log(factor) + 1.0


def apply_rope(
    x: jax.Array, positions: jax.Array, inv_freq: jax.Array, rotary_dim: int,
    scale: float = 1.0,
) -> jax.Array:
    """HF-convention rotate-half RoPE. x: [B, T, H, Dh]; positions: [B, T].
    ``scale`` is the YaRN mscale applied to cos/sin (1.0 otherwise)."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,T,rd/2]
    cos = jnp.cos(angles)[:, :, None, :] * scale  # [B,T,1,rd/2]
    sin = jnp.sin(angles)[:, :, None, :] * scale
    rot, keep = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = jnp.split(rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), keep], axis=-1)


def _act(spec: LLMSpec, x: jax.Array) -> jax.Array:
    if spec.hidden_act == "silu":
        return jax.nn.silu(x)
    if spec.hidden_act == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.gelu(x, approximate=False)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

_NON_LAYER_KEYS = ("embed", "final_norm_w", "final_norm_b", "lm_head",
                   "lm_head_b")


def _layer_body(spec, x, lp, positions, inv_freq, rope_scale, attn_fn,
                valid=None, experts=None, mixer=None):
    """One transformer layer, shared by the serving (KV-cached), training
    (cache-free) and Pallas-kernel decode paths. ``attn_fn(q, k, v) ->
    (attn [B, T, H*Dh], carry)`` owns both where K/V live and the
    attention contraction. ``valid`` [B, T] bool marks the positions
    that carry a token (None: all): an expert layer routes only those.
    ``experts``: the stack's WHOLE expert matrices and this layer's
    place in them (``_moe_mlp``), where a layer scan calls this.
    ``mixer(h) -> (branch [B, T, D], carry)`` replaces attention and
    its projections altogether (a linear-attention layer,
    ``_linear_mixer``). A model without pre-norms (``spec.pre_norm``
    off: no ``ln1_w`` / ``ln2_w`` leaves) feeds its sub-layers the
    residual stream as it is.
    Returns (x, carry, per-expert token counts [E] i32 | None)."""
    B, T = x.shape[0], x.shape[1]
    h = _norm(spec, x, lp["ln1_w"], lp.get("ln1_b")) if "ln1_w" in lp else x
    if mixer is not None:
        attn, carry = mixer(h)
    elif "wkv_a" in lp:  # latent attention: attn_fn(q_n, q_r, row)
        attn, carry = _latent_mixer(spec, lp, h, positions, inv_freq,
                                    rope_scale, attn_fn)
    else:
        q = _mm(h, lp["wq"])
        k = _mm(h, lp["wk"])
        v = _mm(h, lp["wv"])
        if "bq" in lp:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        if spec.qk_norm_flat:  # olmo: over the projection, then split
            q = _norm(spec, q, lp["q_norm_w"], None)
            k = _norm(spec, k, lp["k_norm_w"], None)
        # the split into heads must not reach the dots: folded into
        # them, the TPU compiler wants each weight head-major and
        # writes the layer's matrix out of its [L, in, out] stack,
        # transposed, on every layer of every step (wq and wk in the
        # decode program, wv too in the mixed one: 13.8 % of the chip
        # in Mistral's cell; tools/step_hlo.py shows it, PR 44). Behind
        # the barrier the dots read their weights in place. Values,
        # gradients and sharding pass through unchanged
        q, k, v = lax.optimization_barrier((q, k, v))
        q = q.reshape(B, T, spec.n_heads, spec.d_head)
        k = k.reshape(B, T, spec.n_kv_heads, spec.d_head)
        v = v.reshape(B, T, spec.n_kv_heads, spec.d_head)
        if "q_norm_w" in lp and not spec.qk_norm_flat:
            # qwen3/gemma3: per-head RMSNorm before rope
            q = _norm(spec, q, lp["q_norm_w"], None)
            k = _norm(spec, k, lp["k_norm_w"], None)
        if spec.rotary_dim:
            inv_f = lp.get("_inv_freq", inv_freq)  # gemma3: dual bases
            qr = apply_rope(q, positions, inv_f, spec.rotary_dim,
                            rope_scale)
            kr = apply_rope(k, positions, inv_f, spec.rotary_dim,
                            rope_scale)
            if "_rope_on" in lp:  # afmoe: no positional encoding on
                on = lp["_rope_on"] > 0  # full layers
                qr, kr = jnp.where(on, qr, q), jnp.where(on, kr, k)
        else:  # olmo_hybrid: no rotary embedding anywhere
            qr, kr = q, k
        attn, carry = attn_fn(qr, kr, v)
        if "w_attn_gate" in lp:  # afmoe: sigmoid gate on the heads' output
            attn = attn * jax.nn.sigmoid(_mm(
                h, lp["w_attn_gate"]).astype(jnp.float32)).astype(attn.dtype)
        attn = _mm(attn, lp["wo"])
        if "bo" in lp:
            attn = attn + lp["bo"]
    if "ln_post_attn_w" in lp:  # gemma2 sandwich: norm the branch output
        attn = _norm(spec, attn, lp["ln_post_attn_w"], None)
    mlp_in = h if spec.parallel_residual else None
    if not spec.parallel_residual:
        x = x + attn
        mlp_in = (_norm(spec, x, lp["ln2_w"], lp.get("ln2_b"))
                  if "ln2_w" in lp else x)
    counts = None
    if "router" in lp:  # mixture of experts
        mlp, counts = _moe_mlp(spec, lp, mlp_in, valid, experts)
    else:
        up = _mm(mlp_in, lp["w_up"])
        if "b_up" in lp:
            up = up + lp["b_up"]
        if spec.gated_mlp:
            up = _act(spec, _mm(mlp_in, lp["w_gate"])) * up
        else:
            up = _act(spec, up)
        mlp = _mm(up, lp["w_down"])
        if "b_down" in lp:
            mlp = mlp + lp["b_down"]
    if "ln_post_ffw_w" in lp:  # gemma2 sandwich
        mlp = _norm(spec, mlp, lp["ln_post_ffw_w"], None)
    out = (x + attn + mlp) if spec.parallel_residual else (x + mlp)
    return out, carry, counts


def _latent_row(spec, c, kr):
    """What a token caches, and nothing else: [c (normed) | k_r (rotated)
    | 0...] up to ``spec.latent_row`` lanes. c [B, T, r], kr [B, T, 1,
    d_r]. (The lower-precision controls of tests and tools/mla_parity.py
    wrap this function.)"""
    B, T, r = c.shape
    dr = kr.shape[-1]
    return jnp.concatenate(
        [c, kr.reshape(B, T, dr),
         jnp.zeros((B, T, spec.latent_row - r - dr), c.dtype)], axis=-1)


def _latent_mixer(spec, lp, h, positions, inv_freq, rope_scale, attn_fn):
    """The token mixer of a latent-attention layer (deepseek_v3) up to
    and after the attention itself:

      c_q = RMSNorm(h W_qa);  [q_n | q_r]_h = c_q W_qb      per head
      [c | k_r] = h W_kva;  c = RMSNorm(c)
      rotary on q_r and on the ONE k_r all heads share
      row = [c | k_r | 0...]            what a token caches, nothing else
      heads = attn_fn(q_n, q_r, row)    [.., H * d_v]
      y = heads W_o

    ``attn_fn`` owns where the rows live and which FORM attends them
    (``latent_attend_expanded`` up-projects cached rows through W_kvb;
    the kernel route absorbs W_kvb into the query and the output and
    reads the rows as they are). Rotary is rotate-half: the loader has
    moved a checkpoint's interleaved pairs (``_load_deepseek_v3``).
    -> (branch [.., D], the cache arrays attn_fn wrote)."""
    B, T = h.shape[0], h.shape[1]
    H, dn, dr = spec.n_heads, spec.qk_nope_dim, spec.qk_rope_dim
    r = spec.kv_lora_rank
    cq = _norm(spec, _mm(h, lp["wq_a"]), lp["q_a_norm_w"], None)
    q = _mm(cq, lp["wq_b"])
    kva = _mm(h, lp["wkv_a"])
    q, kva = lax.optimization_barrier((q, kva))  # as in _layer_body
    q = q.reshape(B, T, H, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]
    c = _norm(spec, kva[..., :r], lp["kv_a_norm_w"], None)
    kr = kva[..., r:].reshape(B, T, 1, dr)
    qr = apply_rope(qr, positions, inv_freq, dr, rope_scale)
    kr = apply_rope(kr, positions, inv_freq, dr, rope_scale)
    heads, carry = attn_fn(qn, qr, _latent_row(spec, c, kr))
    return _mm(heads, lp["wo"]), carry


def _linear_mixer(spec, lp, h, *, groups, st, l_lin):
    """The token mixer of ONE linear-attention layer — the gated delta
    rule (ops/gated_delta.py) — over the rows of every group:

      q~, k~, v~ = W h, each channel through its causal depthwise
      convolution over time (``linear_conv`` taps) and SiLU; per head
      q = q~ / |q~| d_k^-1/2, k = k~ / |k~|; beta = (2) sigmoid(W_b h),
      log alpha = -exp(A_log) softplus(W_a h + dt_bias); the recurrence;
      y = W_o concat_h [RMSNorm(o_h) * SiLU((W_g h)_h)].

    ``st`` = (state [Ll, slots, H / G, d_k, G * d_v] f32, conv [Ll,
    slots, taps - 1, conv_dim]) as the group before left them, this
    layer at ``l_lin``. A row's slot is ``state_slots`` (else
    ``slot_ids``, else its own index). A position that carries no token
    (beyond ``q_lens``, a row not ``live``) moves neither: the state
    keeps its value and the convolution's rows stay those of the last
    real token; a row whose chunk starts at position 0 starts from a
    zero state. One token a row in slot order is the fused step (the
    Pallas kernel on the chip), anything else the chunked form.
    -> (branch [.., D], (state, conv))."""
    from ..ops import gated_delta as gd
    from ..ops.decode_attention import _interpret

    state, conv = st
    H, dk, dv = spec.linear_heads, spec.linear_d_k, spec.linear_d_v
    taps, G = spec.linear_conv, gd.head_group(H, dv)
    f32 = jnp.float32
    ns = state.shape[1]
    w_conv = lp["conv_w"].astype(f32)  # [conv_dim, taps]
    parts = (("wq", 0, H * dk), ("wk", H * dk, H * dk),
             ("wv", 2 * H * dk, H * dv))
    proj = [ca.ungroup(groups, _mm(h, lp[name])) for name, _, _ in parts]
    gate, a_raw, b_raw = (ca.ungroup(groups, _mm(h, lp[k]))
                          for k in ("wg", "wa", "wb"))
    decay = -jnp.exp(lp["a_log"].astype(f32))
    outs = []
    for i, g in enumerate(groups):
        B, T = g.tokens.shape
        ids = (g.state_slots if g.state_slots is not None else
               g.slot_ids if g.slot_ids is not None else
               jnp.arange(B, dtype=jnp.int32))
        rd = jnp.minimum(ids, ns - 1)  # a pad row's sentinel: any slot
        ok = ca.valid_of(g)
        n_ok = jnp.sum(ok, axis=1, dtype=jnp.int32)  # a prefix of T
        fresh = (g.pos0 == 0) & (n_ok > 0)
        c0 = jnp.where(fresh[:, None, None], 0, conv[l_lin, rd])
        mixed, rows = [], []
        for (_, lo, n), x in zip(parts, (pr[i] for pr in proj)):
            xc = jnp.concatenate([c0[..., lo:lo + n], x], axis=1)
            y = sum(xc[:, j:j + T].astype(f32) * w_conv[lo:lo + n, j]
                    for j in range(taps))
            mixed.append(jax.nn.silu(y))
            rows.append(jax.vmap(
                lambda a, m: lax.dynamic_slice_in_dim(a, m, taps - 1, 0)
            )(xc, n_ok))
        conv = conv.at[l_lin, ids].set(
            jnp.concatenate(rows, axis=-1).astype(conv.dtype), mode="drop")
        q, k, v = (m.reshape(B, T, H, -1) for m in mixed)
        q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * (dk ** -0.5)
        k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        beta = jax.nn.sigmoid(b_raw[i].astype(f32)) * (
            2.0 if spec.linear_neg_eigval else 1.0)
        log_a = decay * jax.nn.softplus(
            a_raw[i].astype(f32) + lp["dt_bias"].astype(f32))
        beta = jnp.where(ok[..., None], beta, 0.0)
        log_a = jnp.where(ok[..., None], log_a, 0.0)
        if T == 1 and g.state_slots is None and g.slot_ids is None \
                and B == ns:
            a = jnp.where(fresh[:, None], 0.0, jnp.exp(log_a[:, 0]))
            args = (q[:, 0], k[:, 0], v[:, 0], a, beta[:, 0])
            if _interpret():
                o, s_l = gd.gated_delta_step_xla(
                    *args, lax.dynamic_index_in_dim(state, l_lin, 0, False))
                state = lax.dynamic_update_index_in_dim(
                    state, s_l, l_lin, 0)
            else:
                o, state = gd.gated_delta_step(*args, state, l_lin)
            o = o.reshape(B, 1, H, dv)
        else:
            s0 = gd.ungroup_state(state[l_lin, rd], G)
            s0 = jnp.where(fresh[:, None, None, None], 0.0, s0)
            o, s1 = gd.gated_delta_chunk(q, k, v, log_a, beta, s0)
            state = state.at[l_lin, ids].set(gd.group_state(s1, G),
                                             mode="drop")
        o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + spec.norm_eps) * lp["o_norm_w"].astype(f32)
        o = o * jax.nn.silu(gate[i].astype(f32).reshape(B, T, H, dv))
        outs.append(o.reshape(B, T, H * dv).astype(h.dtype))
    return _mm(ca.flatten(outs), lp["wo"]), (state, conv)


def _linear_period(spec, x, lin, li, groups, positions, inv_freq,
                   rope_scale, rec):
    """The linear layers of ONE period of a hybrid model, which steps
    over periods: ``lin`` holds every linear layer's leaves
    (``LayerStack.linear``), this period's at ``li * linear_period``
    onwards; each advances the recurrent state ``rec`` = (state, conv)
    the one before (and the group before) left. The period's full layer
    follows in the caller. -> (x, rec)."""
    per = spec.linear_period
    for j in range(per):
        ljp = {k: lax.dynamic_index_in_dim(v, li * per + j, 0,
                                           keepdims=False)
               for k, v in lin.items()}
        x, rec, _ = _layer_body(
            spec, x, ljp, positions, inv_freq, rope_scale, None,
            mixer=partial(_linear_mixer, spec, ljp, groups=groups,
                          st=tuple(rec), l_lin=li * per + j))
    return x, rec


def _route(spec, lp, x):
    """Router of an expert layer in f32 (routing is precision-
    sensitive): x [N, D] -> (expert ids [N, K] i32, weights [N, K] f32).

    - softmax, renormalised (mixtral, qwen3_moe): softmax over the k
      largest logits;
    - softmax, raw (qwen2_moe norm_topk_prob=false): the k largest of
      the probabilities over all E, as they are;
    - sigmoid (afmoe): the k largest of sigmoid(logits) + the selection
      bias; the weight is the score WITHOUT the bias, divided by the
      selected scores' sum where the spec renormalises;
    - sigmoid, group-limited (deepseek_v3 ``noaux_tc``;
      ``moe_n_group`` > 1): the experts in equal groups, a group's
      score the sum of its two largest biased scores, the biased
      scores outside the ``moe_topk_group`` best groups set to 0 (as
      published: masked_fill(0.0), not -inf), then as above.
    Ties go to the lower index (``lax.top_k``), groups and experts
    alike. The weights carry the routed scale. The ids are PUBLISHED
    expert ids: a layer that holds a share maps them in ``_moe_mlp``."""
    K = spec.experts_per_token
    logits = jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32),
        lp["router"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST,  # near-tie routing must not be
        # decided by bf16 truncation (same convention as _attend)
    )
    if spec.moe_score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choose = scores
        if "router_bias" in lp:
            choose = scores + lp["router_bias"].astype(jnp.float32)
        if spec.moe_n_group > 1:
            G, E = spec.moe_n_group, choose.shape[-1]
            grouped = choose.reshape(-1, G, E // G)
            top2, _ = lax.top_k(grouped, 2)
            _, keep = lax.top_k(jnp.sum(top2, axis=-1),
                                spec.moe_topk_group)  # [N, topk_group]
            kept = jnp.any(keep[:, :, None] == jnp.arange(
                G, dtype=keep.dtype)[None, None, :], axis=1)  # [N, G]
            choose = jnp.where(kept[:, :, None], grouped,
                               0.0).reshape(-1, E)
        _, idx = lax.top_k(choose, K)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if spec.moe_norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    elif spec.moe_norm_topk:
        vals, idx = lax.top_k(logits, K)
        w = jax.nn.softmax(vals, axis=-1)  # renormalize over the selected k
    else:
        w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), K)
    if spec.moe_route_scale != 1.0:
        w = w * spec.moe_route_scale
    return idx.astype(jnp.int32), w


# an expert layer's matrices: never sliced out of their [n, E, ...] stack
EXPERT_LEAVES = ("moe_gate", "moe_up", "moe_down")


def _moe_mlp(spec, lp, x, valid, experts):
    """Top-k mixture of experts as a ROUTED dispatch: only the experts
    that have tokens are read, and each token costs k expert MLPs, not E.

    router (f32) -> top-k -> the N*K (token, expert) assignments sorted
    by expert -> the grouped matmuls over the sorted rows (group e =
    expert e's rows; gate and up, the activation and their product,
    down) -> back to token order -> weighted sum over k, in f32 ->
    + shared expert. ``valid`` [B, T] bool: positions that carry no
    token are routed nowhere (they sort past the last group and read no
    expert). Returns (out [B, T, D], tokens per HELD expert [E] i32 —
    for a layer that holds a share, [E + 1]: then the absent
    assignments).

    ``experts`` = (the stack's EXPERT_LEAVES as [n, E, ...] arrays, this
    layer's index in them[, whether the repo's kernel multiplies]):
    inside a layer scan the grouped matmul takes the WHOLE stack and
    reads this layer's E matrices of it — a kernel's operand cannot be
    a slice of the stack without XLA copying the slice out first (0.5
    GB a matrix a layer at 128 experts of 2048 x 1024; the attention
    kernel takes the whole cache and a layer scalar for the same
    reason). The grouped matmul is ``ops/grouped_matmul.py``'s Pallas
    kernel where ``gm.expert_path`` says so (a TPU backend, no mesh,
    stacks its tiling covers: ``forward_rows`` asks) and
    ``lax.ragged_dot`` — XLA's own, n * E groups of which this layer's
    E have rows — everywhere else; a capture names both ``ragged-dot*``.
    Around the kernel a layer that holds a SHARE moves the rows it owns
    alone (``held_rows_dispatch``: ``ops/expert_rows.py`` gathers the
    first sum(counts) sorted rows and combines them; the arrays keep
    their worst-case shapes); every other layer's gather, mask, un-sort
    and sum are XLA's over all N * K rows.

    qwen2_moe extras: a shared expert scaled by sigmoid(x·g) added to the
    mixture, un-renormalized top-k weights (norm_topk_prob=false), and
    dense-only layers (``_dense_only`` flag) where the shared slot holds a
    plain MLP whose gate is forced to 1 and the expert term is dropped."""
    E, K = spec.n_held, spec.experts_per_token
    share = E < spec.n_experts
    B, T, D = x.shape
    N = B * T
    xf = x.reshape(N, D)
    idx, w = _route(spec, lp, xf)
    flat = idx.reshape(N * K)
    if share:
        # THE SHARE: the router picked among all the published experts;
        # this layer holds ``experts_first`` .. + E of them. An
        # assignment to an absent expert takes the path of a position
        # without a token — it sorts past the last group, reads no
        # expert and adds nothing; the chip that holds that expert adds
        # it there. No token is dropped and nothing stands in for the
        # absent experts
        local = flat - spec.experts_first
        flat = jnp.where((local >= 0) & (local < E), local, E)
    if valid is not None:
        # the sentinel E sorts last and is counted in no group
        flat = jnp.where(jnp.repeat(valid.reshape(N), K), flat, E)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)  # [N*K]
    counts = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
    whole, li, *kernel = experts
    w_gate, w_up, w_down = (
        whole[k].reshape(-1, *whole[k].shape[2:]) for k in EXPERT_LEAVES)
    held_rows = held_rows_dispatch(spec, any(kernel), N)
    if any(kernel):
        # the repo's own kernel (ops/grouped_matmul.py): whole row
        # tiles, so the sorted rows are padded — with rows no group
        # holds, which read no expert
        rows = gm.padded_rows(N * K)
        src = jnp.pad(order, (0, rows - N * K)) // K
        sched = gm.schedule(counts, rows)
        # a share owns the first sum(counts) sorted rows and most of
        # the rest belong to other chips: ops/expert_rows.py moves the
        # owned rows alone, here and in the combine below
        owned = sched.offsets[-1]
        xs = er.gather_rows(xf, src, owned) if held_rows else xf[src]
        g, u = gm.grouped_matmul(xs, (w_gate, w_up), li, sched)
        (y,) = gm.grouped_matmul((_act(spec, g) * u).astype(x.dtype),
                                 (w_down,), li, sched)
        if not held_rows:
            y = y[:N * K]
    else:
        xs = xf[order // K]  # [N*K, D] rows in expert order
        sizes = lax.dynamic_update_slice(
            jnp.zeros((w_gate.shape[0],), jnp.int32), counts, (li * E,))
        g = lax.ragged_dot(xs, w_gate, sizes)
        u = lax.ragged_dot(xs, w_up, sizes)
        y = lax.ragged_dot((_act(spec, g) * u).astype(x.dtype),
                           w_down, sizes)  # [N*K, D]
    if held_rows:
        # no row past the owned ones is read: nothing to mask
        out = er.combine_rows(y, src, w.reshape(N * K)[order], owned, N)
    else:
        if valid is not None or share:
            # rows past the last group are whatever the kernel left
            y = jnp.where(
                jnp.arange(N * K, dtype=jnp.int32)[:, None]
                < jnp.sum(counts), y, 0)
        inv = jnp.zeros((N * K,), jnp.int32).at[order].set(
            jnp.arange(N * K, dtype=jnp.int32))
        out = jnp.einsum("nkd,nk->nd",
                         y[inv].reshape(N, K, D).astype(jnp.float32), w)
    out = out.reshape(B, T, D)
    if "shared_gate" in lp:
        s = (_act(spec, x @ lp["shared_gate"]) * (x @ lp["shared_up"])) \
            @ lp["shared_down"]
        sg = 1.0
        if "shared_router" in lp:  # qwen2_moe: the shared expert's gate
            sg = jax.nn.sigmoid(jnp.einsum(
                "btd,d->bt", x.astype(jnp.float32),
                lp["shared_router"].astype(jnp.float32),
            ))[..., None]  # [B,T,1]
        dense_only = lp.get("_dense_only")  # per-layer scalar via the scan
        if dense_only is not None:
            sg = jnp.where(dense_only > 0, 1.0, sg)
            out = out * (1.0 - dense_only)
        out = out + s.astype(jnp.float32) * sg
    if share:
        # [E + 1]: in the last place the assignments of real tokens
        # that went to experts held elsewhere
        n_real = (N if valid is None
                  else jnp.sum(valid, dtype=jnp.int32)) * K
        counts = jnp.concatenate(
            [counts, (n_real - jnp.sum(counts))[None].astype(jnp.int32)])
    return out.astype(x.dtype), counts


def held_rows_dispatch(spec, kernel: bool, n_tokens: int) -> bool:
    """Whether an expert layer's dispatch moves only the rows it holds
    (``ops/expert_rows.py``) in a step of ``n_tokens`` token rows: the
    layer holds a SHARE of the published experts, the repo's grouped
    kernel multiplies (``kernel``: ``gm.expert_path`` said so — a TPU
    backend, no mesh) and the step's token rows fit the kernels.
    Everywhere else XLA gathers, masks and un-sorts all N * K rows.
    ``_moe_mlp`` asks while tracing, the engine for its counter."""
    return bool(kernel and spec.n_held < spec.n_experts
                and er.fits(n_tokens, spec.d_model))


def expert_path(spec, params, mesh) -> Optional[str]:
    """``grouped_kernel`` | ``ragged_dot`` — how ``forward_rows`` will
    multiply this model's expert layers (``gm.expert_path`` on the
    stacks, the activations' dtype and the mesh) — or None for a model
    without experts. The engine reports it at load."""
    stacks = [params[k] for k in EXPERT_LEAVES if k in params]
    if not spec.n_experts or not stacks:
        return None
    act = jax.eval_shape(
        lambda p: _embed_in(spec, p, jnp.zeros((1, 1), jnp.int32)), params)
    return gm.expert_path(stacks, act.dtype, mesh)


def _layer_dense_only(spec) -> Optional[jnp.ndarray]:
    """[L] f32 flags marking qwen2_moe dense-MLP layers; None when every
    layer is sparse (mixtral) or the model has no experts."""
    if not spec.n_experts or not spec.moe_dense_layers:
        return None
    dense = set(spec.moe_dense_layers)
    return jnp.asarray(
        [1.0 if layer in dense else 0.0 for layer in range(spec.n_layers)],
        jnp.float32,
    )


def _layer_is_sliding(spec) -> Optional[list[bool]]:
    """Per-layer sliding flags; HF layer_types wins over the pattern."""
    if spec.layer_types is not None:
        return [t == "sliding_attention" for t in spec.layer_types]
    if spec.sliding_window_pattern and spec.sliding_window:
        return [(l + 1) % spec.sliding_window_pattern != 0
                for l in range(spec.n_layers)]
    return None


def _layer_windows(spec):
    """Per-layer sliding windows for alternating-window models (gemma2/3):
    [L] i32, 0 = full attention for that layer; None when uniform."""
    sliding = _layer_is_sliding(spec)
    if sliding is None or not spec.sliding_window:
        return None
    return jnp.asarray(
        [spec.sliding_window if s else 0 for s in sliding], jnp.int32
    )


def _layer_inv_freqs(spec):
    """Per-layer rotary inverse frequencies for dual-base models (gemma3:
    sliding layers rope on rope_local_base_freq UNSCALED, global layers on
    rope_theta with rope_scaling): [L, rd/2] f32; None when uniform."""
    sliding = _layer_is_sliding(spec)
    if sliding is None or not spec.rope_local_base_freq:
        return None
    rd = spec.rotary_dim
    local = 1.0 / (
        spec.rope_local_base_freq
        ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    )
    global_ = rope_inv_freq(spec)
    return jnp.stack([local if s else global_ for s in sliding])


def _layer_rope_on(spec):
    """[L] i32 flags for models that rotate on their sliding layers only
    (afmoe: full-attention layers carry no positional encoding); None
    when every layer rotates."""
    sliding = _layer_is_sliding(spec)
    if sliding is None or not spec.rope_sliding_only:
        return None
    return jnp.asarray([1 if s else 0 for s in sliding], jnp.int32)


class LayerStack(NamedTuple):
    """One homogeneous stack of layers, scanned in one ``lax.scan``."""

    first: int  # index of its first layer in the KV cache's planes
    n: int  # its layers (a hybrid model's: its PERIODS — one
    # full-attention layer each, the cache's count)
    scanned: dict  # {leaf: [n, ...]} the scan slices a layer out of
    experts: dict  # {leaf: [n, E, ...]} an expert stack's matrices,
    # never sliced (``_moe_mlp``); {} for any other stack
    linear: dict  # {leaf: [n_linear_layers, ...]} a hybrid model's
    # linear layers, without the prefix (``_linear_period`` indexes a
    # period's layers in them); {} for any other stack


def layer_stacks(spec, params) -> list:
    """The model's homogeneous layer stacks in layer order
    (``LayerStack``): the leading dense-MLP layers of an expert model
    (``DENSE_STACK`` leaves) when it has them, then every other layer.
    What differs by layer INSIDE a stack rides along as per-layer
    values (``_window``, ``_inv_freq``, ``_rope_on``, ``_dense_only``),
    sliced to the stack's layers. A hybrid model (``LINEAR_STACK``
    leaves) is one stack whose steps are PERIODS."""
    per_layer = {"_window": _layer_windows(spec),
                 "_inv_freq": _layer_inv_freqs(spec),
                 "_rope_on": _layer_rope_on(spec),
                 "_dense_only": _layer_dense_only(spec)}
    per_layer = {k: v for k, v in per_layer.items() if v is not None}
    main = {k: params[k] for k in params
            if k not in _NON_LAYER_KEYS and not k.startswith(DENSE_STACK)}
    if spec.linear_heads:
        # a hybrid model: ONE stack of periods — each full-attention
        # layer's leaves to scan over; its linear layers' leaves are
        # kept whole, [n_linear_layers, ...], and a step indexes the
        # layers of its period in them (scanned as [periods, layers a
        # period, ...] every step would COPY its period's matrices out
        # before the first matmul reads them: 3 x the bytes)
        lin = {k[len(LINEAR_STACK):]: main.pop(k) for k in list(main)
               if k.startswith(LINEAR_STACK)}
        return [LayerStack(0, spec.n_kv_layers, main, {}, lin)]
    lead = {k[len(DENSE_STACK):]: params[k] for k in params
            if k.startswith(DENSE_STACK)}
    n_lead = spec.n_dense_layers if lead else 0
    experts = {k: main.pop(k) for k in EXPERT_LEAVES if k in main}
    stacks = []
    if lead:
        stacks.append(LayerStack(0, n_lead, {**lead, **{
            k: v[:n_lead] for k, v in per_layer.items()}}, {}, {}))
    stacks.append(LayerStack(n_lead, spec.n_layers - n_lead, {**main, **{
        k: v[n_lead:] for k, v in per_layer.items()}}, experts, {}))
    return stacks


def _embed_in(spec, params, tokens):
    emb = params["embed"]
    if isinstance(emb, _QTensor):  # int8 table, per-row scales (quant.py)
        dt = next(params[k] for k in ("ln1_w", "ln_post_attn_w")
                  if k in params).dtype  # model compute dtype
        x = (emb.q[tokens].astype(dt)
             * emb.scale[tokens][..., None].astype(dt))
    else:
        x = emb[tokens]
    if spec.embedding_multiplier != 1.0:
        x = (x.astype(jnp.float32) * spec.embedding_multiplier).astype(x.dtype)
    return x


def _lm_head(spec, params, x):
    prec = (
        lax.Precision.HIGHEST if x.dtype == jnp.float32
        else lax.Precision.DEFAULT
    )
    head = params["embed"] if spec.tie_word_embeddings else params["lm_head"]
    if isinstance(head, _QTensor):
        # int8 head: both layouts carry a per-OUTPUT-logit scale [V]
        # (tied = quantize_embed's per-row [V, D]; untied = standard
        # per-out-channel [D, V]), so dequantization is one multiply on
        # the f32 logits — the MXU reads 1 byte/elem.
        eq = "btd,vd->btv" if spec.tie_word_embeddings else "btd,dv->btv"
        logits = jnp.einsum(
            eq, x, head.q.astype(x.dtype),
            preferred_element_type=jnp.float32, precision=prec,
        ) * head.scale.astype(jnp.float32)
    else:
        if spec.tie_word_embeddings:
            head = head.T
        logits = jnp.einsum("btd,dv->btv", x, head,
                            preferred_element_type=jnp.float32,
                            precision=prec)
    if "lm_head_b" in params:
        logits = logits + params["lm_head_b"].astype(jnp.float32)
    if spec.logit_softcap:
        logits = jnp.tanh(logits / spec.logit_softcap) * spec.logit_softcap
    return logits


class Rows(NamedTuple):
    """One rectangular group of query rows of a forward pass, bundled
    so that a pass can carry more than one rectangle (``forward_rows``).
    Serves both phases: prefill passes T=chunk, decode passes T=1 with
    the full slot batch. The new K/V go into the cache at rows
    ``slot_ids``, columns ``pos0 + [0..T)``."""

    tokens: jax.Array  # [B, T] int32
    pos0: jax.Array  # [B] int32: absolute position of tokens[:, 0]
    slot_ids: Optional[jax.Array] = None  # [B] i32 cache row per batch
    # row; None => identity (row b == slot b), the batched-decode hot path
    soft: Optional[tuple] = None  # multimodal: (embeds [B,T,D],
    # mask [B,T]) — rows where mask is True REPLACE the token embedding
    # (post-multiplier, matching HF's masked_scatter of image features)
    write_mask: Optional[jax.Array] = None  # [B] bool (identity path
    # only): rows where False RE-WRITE the cache content already at
    # their write positions — a no-op write. Lets a full-slot-batch
    # identity prefill park non-member rows at pos 0 without corrupting
    # their live prefixes, which in turn lets the dispatch window follow
    # the MEMBER rows' live context instead of max_seq.
    page_table: Optional[jax.Array] = None  # the engine's RAGGED
    # route (engine/cache_route.py), and only it, sets this (with
    # kv_page, q_lens and write_table): ``cache`` is the [L, n_pages,
    # page, F] arena and this [B, max_pages] int32 table maps each
    # row's logical page index to its physical arena page. The paged
    # XLA route instead gathers a dense view OUTSIDE the forward
    # (gather_kv_pages/scatter_kv_pages), so it never sees the arena.
    q_lens: Optional[jax.Array] = None  # [B] i32 per-row valid token
    # counts — 1 for decode rows, the chunk length for prefill rows, kd
    # for spec-decode verify rows. Every row kind flows through ONE
    # ragged-paged-attention kernel invocation per layer
    # (models/cache_attention.py ``ragged``): the chunk's K/V rows
    # scatter into the arena through ``write_table`` (no gathered
    # window view) and attention walks each row's pages raggedly.
    write_table: Optional[jax.Array] = None  # [B, max_pages] i32
    # physical WRITE pages per logical page (ragged mode): entries the
    # host did not grant (shared prefix pages, parked rows, pages
    # outside the dispatch's span) point at the trash page, so a
    # dispatch persists exactly its own writes.
    state_slots: Optional[jax.Array] = None  # [B] i32: the slot whose
    # recurrent state a row advances (linear-attention layers) where
    # the cache route took ``slot_ids`` for its own use; an id beyond
    # the slots (a pad row's sentinel) writes nothing
    live: Optional[jax.Array] = None  # [B] bool: rows that carry a
    # token this pass (None: all). What a parked row computes is thrown
    # away, so an expert layer routes it nowhere and reads no expert
    # for it, and the ragged kernel is given length 0 for it and reads
    # no page; nothing else looks at this.


def forward_hidden(
    spec: LLMSpec,
    params: Params,
    tokens: jax.Array,
    pos0: jax.Array,
    cache: KVCache,
    slot_ids: Optional[jax.Array],
    decode_kernel: bool = False,
    soft: Optional[tuple] = None,
    mesh: Any = None,
    ring_prefill: bool = False,
    write_mask: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,
    kv_page: int = 0,
    q_lens: Optional[jax.Array] = None,
    write_table: Optional[jax.Array] = None,
    state_slots: Optional[jax.Array] = None,
) -> tuple[jax.Array, KVCache]:
    """Run the stack up to (and including) the final norm over ONE
    rectangle of rows; returns (hidden [B, T, D], updated cache). The LM
    head lives in ``forward``; this entry is the embeddings path (ref:
    transformers backend mean-pool,
    backend/python/transformers/backend.py:286-324). The per-row
    arguments are ``Rows``' fields, the others ``forward_rows``'."""
    (x,), cache, _ = forward_rows(
        spec, params,
        (Rows(tokens, pos0, slot_ids, soft, write_mask, page_table,
              q_lens, write_table, state_slots),),
        cache, decode_kernel=decode_kernel, mesh=mesh,
        ring_prefill=ring_prefill, kv_page=kv_page)
    return x, cache


def _embed_rows(spec, params, g):
    x = _embed_in(spec, params, g.tokens)  # gather: [B, T, D]
    if g.soft is not None:
        emb, emb_mask = g.soft
        x = jnp.where(emb_mask[..., None], emb.astype(x.dtype), x)
    return x


def _expert_totals(spec, counts):
    """An expert stack's per-layer counts [n, E] (a share: [n, E + 1],
    see ``_moe_mlp``) as ``forward_rows`` reports them."""
    absent = ()
    if spec.experts_held:
        absent = (jnp.sum(counts[:, -1])[None],)
        counts = counts[:, :-1]
    return jnp.concatenate([
        jnp.sum(counts, axis=0),
        jnp.sum(counts > 0, dtype=jnp.int32)[None], *absent])


def forward_rows(
    spec: LLMSpec,
    params: Params,
    groups: tuple,  # of Rows
    cache: KVCache,
    *,
    decode_kernel: bool = False,  # one token a row in slot order on
    # the dense cache takes the Pallas decode kernel (``ca.select``)
    mesh: Any = None,  # serving mesh: the kernels run per-shard under
    # shard_map (attention is GQA-head-local over the "model" axis)
    ring_prefill: bool = False,  # long-prompt FIRST-chunk prefill on a
    # seq-sharded mesh: attention runs as ring attention over the "seq"
    # axis (parallel/ring_attention.py) — O(T/n) attention memory and
    # ICI-overlapped KV rotation instead of a [B, H, T, T] score tensor.
    # Caller contract: mesh has a nontrivial "seq" axis, every row's
    # pos0 is 0 (the chunk attends only to itself), no sliding window,
    # and T divides the seq axis.
    kv_page: int = 0,  # pool page size (tokens) when page_table is set
) -> tuple[tuple, KVCache, Optional[jax.Array]]:
    """``forward_hidden`` for one or more rectangles of rows in ONE
    pass: returns (one hidden [B, T, D] per group, updated cache,
    expert statistics [E + 1] i32 — the tokens each HELD expert took
    summed over the expert layers, then the experts that had a token,
    summed over those layers; a model that holds a share of its experts
    appends the assignments that went to absent ones, [E + 2] — or None
    for a model without experts).

    With more than one group the rows ride the layer's matmuls as one
    flat ``[1, sum(B*T), D]`` batch — each weight is read from HBM once
    for all of them, which is the point: a step that decodes
    ``[n_slots, 1]`` rows and admits ``[R, bucket]`` prompt rows costs
    one weight read, not two — and only attention runs per group, in
    order, each on the cache the group before it left
    (``ca.attend_groups``). Every group reaches the cache the same way,
    chosen once (``ca.select``: one of models/cache_attention.py's five
    routes); a row's arithmetic is that of the same row in a pass of
    its own."""
    single = len(groups) == 1
    x = ca.flatten([_embed_rows(spec, params, g) for g in groups])
    positions = ca.flatten([ca.positions_of(g) for g in groups])
    inv_freq = rope_inv_freq(spec)
    rope_scale = rope_attn_scale(spec)
    quant = cache.quantized  # int8 rows + per-row scales
    valid = None  # only an expert layer asks which rows are real
    if spec.n_experts and any(g.q_lens is not None
                              or g.live is not None for g in groups):
        valid = ca.flatten([ca.valid_of(g) for g in groups])
    route, stacked = ca.select(spec, groups, decode_kernel)

    def body(stack, carry, scanned):
        # cache rides as the scan CARRY (not xs/ys): XLA aliases loop
        # carries in place, so the per-layer update is a true in-place
        # write of the touched rows. As xs/ys the whole cache would be
        # copied through the ys stack every step (~GBs/step read+write at
        # serving shapes — measured 3-4x the decode roofline on v5e).
        x, planes, rec = carry[0], carry[1:5], carry[5:]
        l, li, lp = scanned
        if rec:  # a hybrid model: the period's linear layers first
            x, rec = _linear_period(spec, x, stack.linear, li, groups,
                                    positions, inv_freq, rope_scale, rec)
        ctx = ca.LayerCtx(
            spec, l, li, lp, stack.scanned,
            lp.get("_window", spec.sliding_window), kv_page, mesh, quant,
            ring_prefill, x.dtype, positions if single else None)
        # the planes this cache has (scale planes with int8 rows only):
        # stacked for a route that addresses them in place, this
        # layer's slices for the others
        st = planes[:4 if quant else 2]
        if not stacked:
            st = tuple(lax.dynamic_index_in_dim(p, l, 0, keepdims=False)
                       for p in st)
        experts = None
        if spec.n_experts and stack.experts:
            experts = (stack.experts, li, gm.expert_path(
                [stack.experts[k] for k in EXPERT_LEAVES], x.dtype,
                mesh) == gm.GROUPED_KERNEL)
        x, wrote, counts = _layer_body(
            spec, x, lp, positions, inv_freq, rope_scale,
            partial(ca.attend_groups, route, ctx, groups, st), valid,
            experts)
        planes = tuple(
            w if stacked else lax.dynamic_update_index_in_dim(p, w, l, 0)
            for p, w in zip(planes, wrote)) + planes[len(wrote):]
        return (x, *planes, *rec), counts

    # the stacks in turn, the cache's layer index running through them
    carry = (x, cache.k, cache.v,
             cache.k_scale if quant else jnp.zeros((), jnp.float32),
             cache.v_scale if quant else jnp.zeros((), jnp.float32))
    if spec.linear_heads:  # the recurrent state rides the carry too
        carry += (cache.state, cache.conv)
    expert_tokens = None
    for stack in layer_stacks(spec, params):
        carry, counts = lax.scan(
            partial(body, stack), carry,
            (jnp.arange(stack.first, stack.first + stack.n,
                        dtype=jnp.int32),
             jnp.arange(stack.n, dtype=jnp.int32), stack.scanned))
        if counts is not None:  # [n, E] of an expert stack
            expert_tokens = _expert_totals(spec, counts)
    x, new_k, new_v, new_ks, new_vs, *rec = carry
    new_cache = KVCache(new_k, new_v, new_ks if quant else None,
                        new_vs if quant else None, *rec)
    if spec.final_norm:
        x = _norm(spec, x, params["final_norm_w"], params.get("final_norm_b"))
    return tuple(ca.ungroup(groups, x)), new_cache, expert_tokens


def forward(
    spec: LLMSpec,
    params: Params,
    tokens: jax.Array,
    pos0: jax.Array,
    cache: KVCache,
    slot_ids: Optional[jax.Array],
    decode_kernel: bool = False,
    soft: Optional[tuple] = None,
    mesh: Any = None,
    ring_prefill: bool = False,
    page_table: Optional[jax.Array] = None,
    kv_page: int = 0,
    q_lens: Optional[jax.Array] = None,
    write_table: Optional[jax.Array] = None,
    state_slots: Optional[jax.Array] = None,
) -> tuple[jax.Array, KVCache]:
    """forward_hidden + LM head; returns (logits [B, T, V] f32, cache)."""
    x, cache = forward_hidden(
        spec, params, tokens, pos0, cache, slot_ids, decode_kernel, soft,
        mesh, ring_prefill, page_table=page_table, kv_page=kv_page,
        q_lens=q_lens, write_table=write_table, state_slots=state_slots,
    )
    return _lm_head(spec, params, x), cache


@partial(jax.jit, static_argnums=(0,), donate_argnums=(4,))
def forward_jit(spec, params, tokens, pos0, cache, slot_ids):
    return forward(spec, params, tokens, pos0, cache, slot_ids)


# ---------------------------------------------------------------------------
# training forward (no KV cache)
# ---------------------------------------------------------------------------


def forward_train(
    spec: LLMSpec, params: Params, tokens: jax.Array
) -> jax.Array:
    """Cache-free causal forward for training/fine-tuning; returns logits
    [B, T, V] f32. Same stacked-scan body as the serving path, but K/V come
    from the current sequence only and each layer is rematerialized
    (``jax.checkpoint``) so activation memory stays O(sqrt(L)) — the TPU way
    to trade FLOPs for HBM.
    """
    if spec.linear_heads:
        raise NotImplementedError(
            f"{spec.model_type}: the training forward has no "
            "linear-attention layers (serving path only)")
    if spec.kv_lora_rank or spec.experts_held:
        raise NotImplementedError(
            f"{spec.model_type}: the training forward has no latent "
            "attention and no expert share (serving path only)")
    B, T = tokens.shape
    x = _embed_in(spec, params, tokens)
    positions = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[None, :], (B, T)
    )
    inv_freq = rope_inv_freq(spec)
    rope_scale = rope_attn_scale(spec)

    def body(experts, x, scanned):
        li, lp = scanned
        x, _, _ = _layer_body(
            spec, x, lp, positions, inv_freq, rope_scale,
            lambda q, k, v: (
                _attend(spec, q, k, v, positions, lp.get("_window")), None),
            experts=(experts, li) if experts else None,
        )
        return x, None

    for stack in layer_stacks(spec, params):
        x, _ = lax.scan(jax.checkpoint(partial(body, stack.experts)), x,
                        (jnp.arange(stack.n, dtype=jnp.int32),
                         stack.scanned))
    if spec.final_norm:
        x = _norm(spec, x, params["final_norm_w"], params.get("final_norm_b"))
    return _lm_head(spec, params, x)
