"""Ring attention: sequence-parallel exact attention over the "seq" mesh
axis.

The reference has NO sequence/context parallelism — long context there is
per-node RoPE scaling + self-extend (SURVEY.md §5). On TPU, sequences
sharded across chips are first-class: each device holds a sequence chunk
of Q/K/V; K/V blocks rotate around the ring via ``lax.ppermute`` over ICI
while every device accumulates its queries' attention against the visiting
block flash-style (running max / denominator). Compute overlaps the
neighbor exchange; memory per chip is O(T/n) — the standard ring-attention
recipe expressed with shard_map + XLA collectives (no NCCL analogue).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _ring_body(q, k, v, *, axis_name: str, causal: bool, scale: float):
    """Per-device program. q: [B, Tl, H, D]; k/v: [B, Tl, Hkv, D] —
    GQA K/V rotate around the ring at their NATIVE head count (the ICI
    bytes per rotation stay Hkv-sized) and are repeated to the query
    head count locally, after each receive."""
    B, Tl, H, D = q.shape
    grp = H // k.shape[2]
    n = jax.lax.psum(1, axis_name)  # ring size (static under shard_map)
    my = lax.axis_index(axis_name)
    q_pos = my * Tl + jnp.arange(Tl)  # global positions of local queries

    def step(i, carry):
        k_raw, v_raw, m, l, acc = carry
        k_blk = jnp.repeat(k_raw, grp, axis=2) if grp > 1 else k_raw
        v_blk = jnp.repeat(v_raw, grp, axis=2) if grp > 1 else v_raw
        # the block visiting us at step i started at device (my - i) mod n
        src = (my - i) % n
        kv_pos = src * Tl + jnp.arange(Tl)
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_blk,
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            mask = q_pos[None, None, :, None] >= kv_pos[None, None, None, :]
            logits = jnp.where(mask, logits, NEG_INF)
        blk_m = jnp.max(logits, axis=-1)  # [B, H, Tq]
        new_m = jnp.maximum(m, blk_m)
        # fully-masked rows keep NEG_INF: guard the exp shift
        shift = jnp.where(new_m <= NEG_INF / 2, 0.0, new_m)
        alpha = jnp.exp(m - shift)
        p = jnp.exp(logits - shift[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        m = new_m
        # rotate the (Hkv-sized) K/V block to the next device over ICI
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_raw = lax.ppermute(k_raw, axis_name, perm)
        v_raw = lax.ppermute(v_raw, axis_name, perm)
        return k_raw, v_raw, m, l, acc

    m0 = jnp.full((B, H, Tl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    acc0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    _, _, m, l, acc = lax.fori_loop(0, n, step, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, Tl, H, D]


def ring_attention(
    q: jax.Array,  # [B, T, H, D] sequence-sharded on `axis_name`
    k: jax.Array,  # [B, T, Hkv, D] — Hkv may be < H (GQA); blocks
    v: jax.Array,  # rotate at Hkv size, repeated to H locally
    mesh: Mesh,
    *,
    axis_name: str = "seq",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact attention over a seq-sharded [B, T, H, D]; returns the same
    sharding. T must divide evenly across the axis."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        partial(_ring_body, axis_name=axis_name, causal=causal,
                scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def dense_attention_reference(q, k, v, *, causal=True, scale=None):
    """Single-device reference for tests."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
