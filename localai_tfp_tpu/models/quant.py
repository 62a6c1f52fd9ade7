"""Weight-only int8 quantization for serving (ref: the reference serves
quantized checkpoints as its default mode — llama.cpp Q4/Q8 GGUFs and the
exllama2 EXL2 backend; config surface `quantization`
backend_config.go/vllm fields).

TPU-first shape: per-output-channel symmetric int8 with an f32 scale.
Weights live in HBM at half the bf16 footprint; the matmul reads int8 and
upcasts inline (XLA fuses the convert into the MXU feed), so decode —
weight-bandwidth-bound at serving batch sizes — reads half the bytes.
Activations, norms, embeddings, lm_head and the MoE expert stacks stay
high-precision (quality-sensitive or gather-heavy paths)."""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class QTensor(NamedTuple):
    """int8 weight + per-output-channel scale. A NamedTuple, so it is a
    pytree: jit/scan/donation see two leaves, and lax.scan slices the
    leading (layer) axis of both together."""

    q: jax.Array  # int8 [..., in, out]
    scale: jax.Array  # f32 [..., out]


# stacked projection leaves worth quantizing (the decode bandwidth hogs);
# MoE/shared-expert stacks are excluded: routing is precision-sensitive
# and their einsums contract the expert dim separately
QUANTIZABLE = ("wq", "wk", "wv", "wo", "w_attn_gate", "w_gate", "w_up",
               "w_down")


def quantizable(name: str) -> bool:
    """Whether a parameter leaf is one of the projection stacks above —
    in the main stack or in the leading dense layers' own
    (models/transformer.py ``DENSE_STACK``)."""
    return name.removeprefix("dense.") in QUANTIZABLE


def quantize_tensor(w: jax.Array) -> QTensor:
    """Symmetric per-output-channel int8: scale over the INPUT dim
    (axis -2), so dequantization is one multiply on the matmul output."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-2) / 127.0 + 1e-12  # [..., out]
    q = jnp.clip(jnp.round(wf / scale[..., None, :]), -127, 127)
    return QTensor(q=q.astype(jnp.int8), scale=scale)


def quantize_embed(w: jax.Array) -> QTensor:
    """Embedding-table int8: PER-ROW (per-token) scales [V] — embedding
    rows vary widely in magnitude, so per-column scales would let rare
    high-norm rows crush the rest. The gather dequantizes the touched
    rows only; used tied as the LM head, the scale applies per OUTPUT
    logit (one multiply on the matmul result)."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-1) / 127.0 + 1e-12  # [V]
    q = jnp.clip(jnp.round(wf / scale[:, None]), -127, 127)
    return QTensor(q=q.astype(jnp.int8), scale=scale)


def quantize_raw_tensor(w_raw: jax.Array) -> QTensor:
    """Quantize a RAW torch-layout weight ([..., out, in]) and transpose
    the int8 result into the serving layout ([..., in, out]).

    The scale reduces over the input dim (axis -1 in raw layout), so the
    values are identical to ``quantize_tensor`` on the transposed array;
    the transpose then moves 1-byte int8 instead of 2-byte bf16, and
    under jit the cast+scale+round+transpose fuse into one XLA op —
    this is the device-streaming load path's kernel."""
    wf = w_raw.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-1) / 127.0 + 1e-12  # [..., out]
    q = jnp.clip(jnp.round(wf / scale[..., None]), -127, 127)
    return QTensor(q=jnp.swapaxes(q.astype(jnp.int8), -1, -2),
                   scale=scale)


def quantize_params(params: dict[str, Any],
                    embeddings: bool = False) -> dict[str, Any]:
    """Quantize the eligible projection stacks in place of their bf16
    leaves. ``embeddings=True`` also quantizes embed/lm_head (~2 GB on
    an 8B: the difference between batch 16 and batch 64 serving on one
    16 GB chip). Everything else passes through untouched."""
    out = dict(params)
    for name in out:
        if quantizable(name) and not isinstance(out[name], QTensor):
            out[name] = quantize_tensor(out[name])
    if embeddings:
        if not isinstance(out.get("embed"), QTensor):
            out["embed"] = quantize_embed(out["embed"])
        if "lm_head" in out and not isinstance(out["lm_head"], QTensor):
            out["lm_head"] = quantize_tensor(out["lm_head"])
    return out


def mm(x: jax.Array, w: Any):
    """x @ w for plain arrays OR QTensor (int8 read and upcast inline,
    one multiply by the per-channel scale on the output)."""
    if isinstance(w, QTensor):
        y = x @ w.q.astype(x.dtype)
        return y * w.scale.astype(x.dtype)
    return x @ w


def dequantize(w: Any) -> jax.Array:
    if isinstance(w, QTensor):
        return w.q.astype(jnp.float32) * w.scale[..., None, :]
    return w
