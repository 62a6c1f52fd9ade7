"""Compiled-kernel parity checks, run on the REAL device.

The pytest suite pins itself to CPU, where every Pallas kernel runs in
interpret mode — a Mosaic compile error or tiling regression would ship
silently. ``python -m localai_tfp_tpu.ops.kernel_check`` runs every
check on whatever device JAX finds, prints one JSON object and exits
non-zero unless all of them passed; chip_smoke.py runs it as its kernel
phase, and it is the quick chip check after a kernel edit
(``chiprun -- python -m localai_tfp_tpu.ops.kernel_check``).

Each check compares the compiled kernel against a straightforward XLA
reference on identical random inputs and reports the max abs error. The
serving legs run at the head geometry, page size and row shapes the
engine dispatches for one model (``Geometry``; the default is the
chip_smoke model at its serving settings).

``--sweep`` is the kernel's stopwatch, not a check: the ragged kernel
alone at the geometry's decode shapes over pages a row, parked rows and
arena dtypes, one JSON line a dtype (``sweep_decode_kernel``);
``--sweep --experts`` an expert layer's grouped matmuls alone,
``--sweep --dispatch`` what surrounds them in a layer that holds a
share (XLA's gather, mask, un-sort and sum beside the row kernels).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def _ref_decode_attention(q, cache_k, cache_v, layer, lengths,
                          n_kv_heads, scale):
    """Dense-mask XLA reference of fused_decode_attention: per-slot GQA
    attention over positions [0, lengths) of one layer."""
    k = cache_k[layer].astype(jnp.float32)  # [S, SEQ, F]
    v = cache_v[layer].astype(jnp.float32)
    S, SEQ, F = k.shape
    H = q.shape[1]
    dh = F // n_kv_heads
    group = H // n_kv_heads
    k = k.reshape(S, SEQ, n_kv_heads, dh)
    v = v.reshape(S, SEQ, n_kv_heads, dh)
    kv_idx = jnp.arange(H) // group  # q head -> kv head
    kh = k[:, :, kv_idx, :]  # [S, SEQ, H, dh]
    vh = v[:, :, kv_idx, :]
    logits = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32), kh) * scale
    mask = (jnp.arange(SEQ)[None, None, :]
            < lengths[:, None, None])  # [S, 1, SEQ]
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("sht,sthd->shd", p, vh)  # [S, H, dh]
    return out.reshape(S, H * dh)


def check_decode_attention(quantized: bool = False,
                           seed: int = 0) -> float:
    """Max abs error of the compiled ragged decode-attention kernel vs
    the dense XLA reference, serving-like shapes."""
    from ..models.transformer import _quantize_rows
    from .decode_attention import fused_decode_attention

    rng = np.random.default_rng(seed)
    L, S, SEQ, n_kv, dh, H = 2, 8, 512, 8, 128, 32
    F = n_kv * dh
    lengths = np.asarray(
        rng.integers(1, SEQ, S), np.int32)  # ragged prefixes
    cache_k = (rng.standard_normal((L, S, SEQ, F)) * 0.5)
    cache_v = (rng.standard_normal((L, S, SEQ, F)) * 0.5)
    # zero out beyond each slot's prefix so quantization scales match
    for s in range(S):
        cache_k[:, s, lengths[s]:] = 0
        cache_v[:, s, lengths[s]:] = 0
    q = jnp.asarray(rng.standard_normal((S, H, dh)) * 0.5, jnp.float32)
    layer = jnp.asarray(1, jnp.int32)
    new_k = jnp.asarray(
        np.stack([cache_k[1, s, lengths[s] - 1] for s in range(S)]),
        jnp.float32)
    new_v = jnp.asarray(
        np.stack([cache_v[1, s, lengths[s] - 1] for s in range(S)]),
        jnp.float32)
    scale = 1.0 / np.sqrt(dh)
    if quantized:
        kq, ks = _quantize_rows(jnp.asarray(cache_k, jnp.float32))
        vq, vs = _quantize_rows(jnp.asarray(cache_v, jnp.float32))
        deq_k = kq.astype(jnp.float32) * ks[..., None]
        deq_v = vq.astype(jnp.float32) * vs[..., None]
        got = fused_decode_attention(
            q.astype(jnp.bfloat16), new_k.astype(jnp.bfloat16),
            new_v.astype(jnp.bfloat16), kq, vq, layer,
            jnp.asarray(lengths), n_kv, scale=scale,
            cache_k_scale=ks, cache_v_scale=vs,
        )
        want = _ref_decode_attention(
            q, deq_k, deq_v, 1, jnp.asarray(lengths), n_kv, scale)
    else:
        ck = jnp.asarray(cache_k, jnp.bfloat16)
        cv = jnp.asarray(cache_v, jnp.bfloat16)
        got = fused_decode_attention(
            q.astype(jnp.bfloat16), new_k.astype(jnp.bfloat16),
            new_v.astype(jnp.bfloat16), ck, cv, layer,
            jnp.asarray(lengths), n_kv, scale=scale,
        )
        want = _ref_decode_attention(
            q, ck, cv, 1, jnp.asarray(lengths), n_kv, scale)
    return float(jnp.max(jnp.abs(got - want)))


def check_paged_gather(quantized: bool = False, seed: int = 0) -> float:
    """Paged-path parity: scatter a dense ragged cache into a paged
    arena under a shuffled page table, then compare BOTH paged reads —
    the XLA gather (models.transformer.gather_kv_pages, the gather
    route) and the ragged kernel at T == 1 through the page table (the
    ragged route's decode rows) — against the dense reference. The
    gather must be EXACT (pure indexing); the kernel must match the
    dense-kernel tolerance. Returns the max abs error across both."""
    import jax.numpy as jnp

    from ..models.transformer import (
        KVCache, _quantize_rows, gather_kv_pages,
    )
    from .ragged_paged_attention import ragged_paged_attention

    rng = np.random.default_rng(seed)
    L, S, SEQ, n_kv, dh, H = 2, 8, 512, 8, 128, 32
    page = 128
    F = n_kv * dh
    n_logical = SEQ // page
    lengths = np.asarray(rng.integers(1, SEQ, S), np.int32)
    cache_k = rng.standard_normal((L, S, SEQ, F)) * 0.5
    cache_v = rng.standard_normal((L, S, SEQ, F)) * 0.5
    for s in range(S):
        cache_k[:, s, lengths[s]:] = 0
        cache_v[:, s, lengths[s]:] = 0
    # shuffled page table: page 0 reserved as trash, every (slot,
    # logical page) maps to a distinct physical page in random order
    n_pages = S * n_logical + 1
    perm = rng.permutation(np.arange(1, n_pages))
    pt = perm.reshape(S, n_logical).astype(np.int32)
    arena_k = np.zeros((L, n_pages, page, F), cache_k.dtype)
    arena_v = np.zeros((L, n_pages, page, F), cache_v.dtype)
    for s in range(S):
        for p in range(n_logical):
            arena_k[:, pt[s, p]] = cache_k[:, s, p * page:(p + 1) * page]
            arena_v[:, pt[s, p]] = cache_v[:, s, p * page:(p + 1) * page]
    q = jnp.asarray(rng.standard_normal((S, H, dh)) * 0.5, jnp.float32)
    layer = jnp.asarray(1, jnp.int32)
    new_k = jnp.asarray(
        np.stack([cache_k[1, s, lengths[s] - 1] for s in range(S)]),
        jnp.float32)
    new_v = jnp.asarray(
        np.stack([cache_v[1, s, lengths[s] - 1] for s in range(S)]),
        jnp.float32)
    scale = 1.0 / np.sqrt(dh)
    pt_j = jnp.asarray(pt)

    def paged_decode(arena):
        # the decode rows of models/cache_attention.py ``ragged``: one
        # query a row at lengths-1, the current row seeded from VMEM
        ln = jnp.asarray(lengths)
        return ragged_paged_attention(
            q.astype(jnp.bfloat16)[:, None], arena.k, arena.v, layer,
            pt_j, ln - 1, jnp.ones_like(ln), n_kv, scale=scale,
            page=page, cache_k_scale=arena.k_scale,
            cache_v_scale=arena.v_scale,
            seed_kv=(new_k.astype(jnp.bfloat16),
                     new_v.astype(jnp.bfloat16)))[:, 0]

    if quantized:
        kq, ks = _quantize_rows(jnp.asarray(cache_k, jnp.float32))
        vq, vs = _quantize_rows(jnp.asarray(cache_v, jnp.float32))
        aq_k = np.zeros((L, n_pages, page, F), np.int8)
        aq_v = np.zeros((L, n_pages, page, F), np.int8)
        as_k = np.zeros((L, n_pages, page), np.float32)
        as_v = np.zeros((L, n_pages, page), np.float32)
        kq_n, vq_n = np.asarray(kq), np.asarray(vq)
        ks_n, vs_n = np.asarray(ks), np.asarray(vs)
        for s in range(S):
            for p in range(n_logical):
                sl = slice(p * page, (p + 1) * page)
                aq_k[:, pt[s, p]] = kq_n[:, s, sl]
                aq_v[:, pt[s, p]] = vq_n[:, s, sl]
                as_k[:, pt[s, p]] = ks_n[:, s, sl]
                as_v[:, pt[s, p]] = vs_n[:, s, sl]
        arena = KVCache(k=jnp.asarray(aq_k), v=jnp.asarray(aq_v),
                        k_scale=jnp.asarray(as_k),
                        v_scale=jnp.asarray(as_v))
        win = gather_kv_pages(arena, pt_j, page)
        gerr = max(
            float(jnp.max(jnp.abs(win.k.astype(jnp.int32)
                                  - kq.astype(jnp.int32)))),
            float(jnp.max(jnp.abs(win.k_scale - ks))),
        )
        if gerr > 0:
            return gerr  # indexing bug: report it, skip the kernel leg
        got = paged_decode(arena)
        deq_k = kq.astype(jnp.float32) * ks[..., None]
        deq_v = vq.astype(jnp.float32) * vs[..., None]
        want = _ref_decode_attention(
            q, deq_k, deq_v, 1, jnp.asarray(lengths), n_kv, scale)
    else:
        arena = KVCache(k=jnp.asarray(arena_k, jnp.bfloat16),
                        v=jnp.asarray(arena_v, jnp.bfloat16))
        dense_k = jnp.asarray(cache_k, jnp.bfloat16)
        dense_v = jnp.asarray(cache_v, jnp.bfloat16)
        win = gather_kv_pages(arena, pt_j, page)
        gerr = float(jnp.max(jnp.abs(
            win.k.astype(jnp.float32) - dense_k.astype(jnp.float32))))
        if gerr > 0:
            return gerr
        got = paged_decode(arena)
        want = _ref_decode_attention(
            q, dense_k, dense_v, 1, jnp.asarray(lengths), n_kv, scale)
    return float(jnp.max(jnp.abs(got - want)))


def _tp_mesh(n_kv_heads: int):
    """Largest pure-TP serving mesh buildable from the visible devices:
    tp = biggest power of two that both fits the device count and
    divides the kv-head count (each shard attends whole kv-head bands).
    None on single-device hosts — the meshed legs then skip."""
    from jax.sharding import Mesh

    devs = jax.devices()
    tp = 1
    while tp * 2 <= len(devs) and n_kv_heads % (tp * 2) == 0:
        tp *= 2
    if tp < 2:
        return None
    return Mesh(np.asarray(devs[:tp]).reshape(tp), ("model",))


def check_meshed_ragged_attention(quantized: bool = False,
                                  seed: int = 0,
                                  mix: str = "mixed") -> "float | None":
    """Pod-scale parity: the shard_map'd append+attend wrapper
    (``sharded_ragged_append_attend`` — arena head dim over "model",
    host-global page tables) vs the dense single-device oracle on the
    SAME post-scatter arena. Covers the decode seed-row path (T == 1)
    and mixed ragged rows; fp and int8 legs share the dense kernel's
    tolerance. None when fewer than 2 devices are visible."""
    from ..models.transformer import _quantize_rows
    from .ragged_paged_attention import (
        ragged_attention_reference, sharded_ragged_append_attend,
    )

    L, n_kv, dh, H, page = 2, 8, 128, 32, 128
    mesh = _tp_mesh(n_kv)
    if mesh is None:
        return None
    rng = np.random.default_rng(seed)
    F = n_kv * dh
    B, max_pages = 6, 4
    if mix == "decode":
        q_lens = np.ones(B, np.int32)
    else:  # decode rows + prefill chunks + a verify row together
        q_lens = np.asarray([1, 1, 7, 32, 4, 16], np.int32)[:B]
    T = int(q_lens.max())
    cap = max_pages * page
    pos0 = np.asarray(
        [int(rng.integers(0, cap - int(n))) for n in q_lens], np.int32)
    n_pages = B * max_pages + 1
    pt = rng.permutation(np.arange(1, n_pages)).reshape(
        B, max_pages).astype(np.int32)
    wb = pt  # rows own their pages: appends land in the read window
    arena_k = rng.standard_normal((L, n_pages, page, F)) * 0.5
    arena_v = rng.standard_normal((L, n_pages, page, F)) * 0.5
    q = jnp.asarray(rng.standard_normal((B, T, H, dh)) * 0.3,
                    jnp.float32)
    new_k = jnp.asarray(rng.standard_normal((B, T, F)) * 0.5,
                        jnp.float32)
    new_v = jnp.asarray(rng.standard_normal((B, T, F)) * 0.5,
                        jnp.float32)
    scale = 1.0 / np.sqrt(dh)
    layer = jnp.asarray(1, jnp.int32)
    pt_j, pos_j = jnp.asarray(pt), jnp.asarray(pos0)
    len_j = jnp.asarray(q_lens)
    wb_j = jnp.asarray(wb)
    if quantized:
        ak, ks = _quantize_rows(jnp.asarray(arena_k, jnp.float32))
        av, vs = _quantize_rows(jnp.asarray(arena_v, jnp.float32))
        kq, ksc = _quantize_rows(new_k)
        vq, vsc = _quantize_rows(new_v)
    else:
        ak = jnp.asarray(arena_k, jnp.bfloat16)
        av = jnp.asarray(arena_v, jnp.bfloat16)
        ks = vs = ksc = vsc = None
        kq, vq = new_k, new_v
    # dense oracle arena: the IDENTICAL scatter the wrapper body runs
    # (pads write the trash page), on unsharded arrays
    rows_i = jnp.arange(B, dtype=jnp.int32)
    tpos = pos_j[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    wpg = wb_j[rows_i[:, None], tpos // page]
    wpg = jnp.where(
        jnp.arange(T, dtype=jnp.int32)[None] < len_j[:, None], wpg, 0)
    woff = tpos % page
    ck_ref = ak.at[1, wpg, woff, :].set(
        kq.astype(ak.dtype), mode="promise_in_bounds")
    cv_ref = av.at[1, wpg, woff, :].set(
        vq.astype(av.dtype), mode="promise_in_bounds")
    if quantized:
        ks_ref = ks.at[1, wpg, woff].set(ksc, mode="promise_in_bounds")
        vs_ref = vs.at[1, wpg, woff].set(vsc, mode="promise_in_bounds")
    seed_kv = (new_k[:, 0], new_v[:, 0]) if T == 1 else None
    want = ragged_attention_reference(
        q, ck_ref, cv_ref, 1, pt_j, pos_j, len_j, n_kv, scale=scale,
        page=page, cache_k_scale=ks_ref if quantized else None,
        cache_v_scale=vs_ref if quantized else None, seed_kv=seed_kv)
    with mesh:
        res = sharded_ragged_append_attend(
            mesh, q.astype(jnp.bfloat16), new_k, new_v, kq, vq,
            ksc, vsc, ak, av, ks, vs, layer, pt_j, wb_j, pos_j, len_j,
            n_kv, scale=scale, page=page)
    got = res[0].reshape(B, T, H, dh)
    want = want.reshape(B, T, H, dh)
    # pad rows beyond each ragged length are garbage by contract; the
    # scatter itself must be EXACT (pure indexing + identical casts)
    err = float(jnp.max(jnp.abs(
        res[1].astype(jnp.float32) - ck_ref.astype(jnp.float32))))
    err = max(err, float(jnp.max(jnp.abs(
        res[2].astype(jnp.float32) - cv_ref.astype(jnp.float32)))))
    if quantized:
        err = max(err, float(jnp.max(jnp.abs(res[3] - ks_ref))))
        err = max(err, float(jnp.max(jnp.abs(res[4] - vs_ref))))
    if err > 0:
        return err  # scatter bug: report it, skip the attention leg
    for b in range(B):
        n = int(q_lens[b])
        err = max(err, float(jnp.max(jnp.abs(
            got[b, :n] - want[b, :n]))))
    return err


def check_meshed_paged_gather(quantized: bool = False,
                              seed: int = 0) -> "float | None":
    """GSPMD fallback-path parity on a mesh: ``gather_kv_pages`` over a
    PAGED_KV_SPEC-sharded arena (head dim over "model", scale planes
    replicated) must reproduce the dense cache EXACTLY — it is pure
    indexing, so any nonzero error is a resharding bug. None when fewer
    than 2 devices are visible."""
    from jax.sharding import NamedSharding

    from ..models.transformer import (
        KVCache, _quantize_rows, gather_kv_pages,
    )
    from ..parallel.sharding import PAGED_KV_SPEC, REPLICATED

    L, S, SEQ, n_kv, dh = 2, 4, 512, 8, 128
    mesh = _tp_mesh(n_kv)
    if mesh is None:
        return None
    rng = np.random.default_rng(seed)
    page = 128
    F = n_kv * dh
    n_logical = SEQ // page
    cache_k = rng.standard_normal((L, S, SEQ, F)) * 0.5
    cache_v = rng.standard_normal((L, S, SEQ, F)) * 0.5
    n_pages = S * n_logical + 1
    perm = rng.permutation(np.arange(1, n_pages))
    pt = perm.reshape(S, n_logical).astype(np.int32)

    def scatter(dense):
        arena = np.zeros((L, n_pages, page) + dense.shape[3:],
                         dense.dtype)
        for s in range(S):
            for p in range(n_logical):
                arena[:, pt[s, p]] = dense[:, s, p * page:(p + 1) * page]
        return arena

    def put(arr, spec):
        return jax.device_put(arr, NamedSharding(mesh, spec))

    if quantized:
        kq, ks = _quantize_rows(jnp.asarray(cache_k, jnp.float32))
        vq, vs = _quantize_rows(jnp.asarray(cache_v, jnp.float32))
        arena = KVCache(
            k=put(jnp.asarray(scatter(np.asarray(kq))), PAGED_KV_SPEC),
            v=put(jnp.asarray(scatter(np.asarray(vq))), PAGED_KV_SPEC),
            k_scale=put(jnp.asarray(scatter(np.asarray(ks))), REPLICATED),
            v_scale=put(jnp.asarray(scatter(np.asarray(vs))), REPLICATED),
        )
        win = gather_kv_pages(arena, jnp.asarray(pt), page)
        return max(
            float(jnp.max(jnp.abs(win.k.astype(jnp.int32)
                                  - kq.astype(jnp.int32)))),
            float(jnp.max(jnp.abs(win.v.astype(jnp.int32)
                                  - vq.astype(jnp.int32)))),
            float(jnp.max(jnp.abs(win.k_scale - ks))),
            float(jnp.max(jnp.abs(win.v_scale - vs))),
        )
    dense_k = jnp.asarray(cache_k, jnp.bfloat16)
    dense_v = jnp.asarray(cache_v, jnp.bfloat16)
    arena = KVCache(
        k=put(jnp.asarray(scatter(np.asarray(dense_k))), PAGED_KV_SPEC),
        v=put(jnp.asarray(scatter(np.asarray(dense_v))), PAGED_KV_SPEC),
    )
    win = gather_kv_pages(arena, jnp.asarray(pt), page)
    return max(
        float(jnp.max(jnp.abs(
            win.k.astype(jnp.float32) - dense_k.astype(jnp.float32)))),
        float(jnp.max(jnp.abs(
            win.v.astype(jnp.float32) - dense_v.astype(jnp.float32)))),
    )


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The shapes the engine hands the ragged kernel for one model."""

    n_heads: int = 32
    n_kv_heads: int = 8
    d_head: int = 128
    page: int = 256  # the engine's page size at this max_seq
    max_seq: int = 4096
    n_slots: int = 16  # decode and mixed dispatches are [n_slots, T]
    chunk: int = 2048  # largest prefill bucket: the longest query row
    # the kernel's tiling has to lay out (a [1, chunk] row)
    mixed: int = 512  # a [n_slots, bucket] ragged batch inside the
    # group-token budget (a mixed step's prompt group is narrower)


# Mistral-7B-Instruct-v0.3 heads at context_size 4096 / 16 slots / the
# default bucket ladder — what chip_smoke.py serves
SERVING = Geometry()
# same code paths at interpreter-friendly sizes (CPU debugging, tests)
SMALL = Geometry(n_heads=4, n_kv_heads=2, d_head=128, page=16,
                 max_seq=128, n_slots=3, chunk=64, mixed=32)


def check_serving_rows(geom: Geometry, kind: str, cache: str,
                       seed: int = 0, window: "int | None" = None) -> float:
    """Max abs error of ONE ragged kernel invocation at the engine's
    shapes against the dense oracle, for one dispatch kind:

    - ``decode``: ``[n_slots, 1]`` seeded rows at ragged context lengths
      (decode1 / decodek) — the T == 1 tiling case; ``decode_parked``:
      the same with three of the rows parked (length 0);
    - ``chunk``: a ``[1, chunk]`` full-width prompt chunk deep in the
      context (the "prefill" kind; the longest query row the engine
      routes through the kernel);
    - ``mixed``: ``[n_slots, mixed]`` with decode rows (q_len 1), short
      and full chunks and a verify-sized row together, each at its own
      context offset (the row kinds a mixed step carries).

    ``cache`` is the arena dtype: "bf16" and "int8" under a bf16 model
    (queries and seed rows bf16), "f32" for an f32 model end to end.
    The arena sits behind a shuffled page table with the trash page in
    every unallocated entry. ``window``: the layer's sliding window,
    handed to the kernel as a layer scan hands it — a traced scalar
    operand (0 = full attention, which must equal ``None``)."""
    from ..models.transformer import _quantize_rows
    from .ragged_paged_attention import (
        ragged_attention_reference, ragged_paged_attention,
    )

    rng = np.random.default_rng(seed)
    H, n_kv, dh, page = (geom.n_heads, geom.n_kv_heads, geom.d_head,
                         geom.page)
    F = n_kv * dh
    max_pages = geom.max_seq // page
    if kind in ("decode", "decode_parked"):
        B, T = geom.n_slots, 1
        q_lens = np.ones(B, np.int32)
        pos0 = rng.integers(0, geom.max_seq - 1, B).astype(np.int32)
        pos0[0] = 0  # a first decode step: the seed row alone
        pos0[-1] = geom.max_seq - 1  # the last position of the context
        if kind == "decode_parked":
            # rows with no stream (length 0, any position): the walk
            # hands its next page over them, two in a row where the
            # batch has the room (the first and last rows stay live)
            q_lens[sorted({1, B // 2, B // 2 + 1} - {0, B - 1})] = 0
    elif kind == "chunk":
        B, T = 1, geom.chunk
        q_lens = np.full(B, T, np.int32)
        pos0 = np.asarray([geom.max_seq - T - 1], np.int32)
    elif kind == "mixed":
        B, T = geom.n_slots, geom.mixed
        lens = [1, T, 1, max(T // 3, 1), 4, T - 1]
        q_lens = np.asarray([lens[i % len(lens)] for i in range(B)],
                            np.int32)
        pos0 = np.asarray(
            [int(rng.integers(0, geom.max_seq - int(n))) for n in q_lens],
            np.int32)
    else:
        raise ValueError(f"unknown row kind {kind!r}")
    L = 2
    n_pages = B * max_pages + 1
    pt = rng.permutation(np.arange(1, n_pages)).reshape(
        B, max_pages).astype(np.int32)
    for b in range(B):  # pages beyond the row's context are unallocated
        used = -(-(int(pos0[b]) + int(q_lens[b])) // page)
        pt[b, used:] = 0
    arena_k = rng.standard_normal((L, n_pages, page, F), np.float32) * 0.5
    arena_v = rng.standard_normal((L, n_pages, page, F), np.float32) * 0.5
    q = jnp.asarray(
        rng.standard_normal((B, T, H, dh), np.float32) * 0.3)
    layer = jnp.asarray(1, jnp.int32)
    scale = 1.0 / np.sqrt(dh)
    pt_j, pos_j, len_j = (jnp.asarray(pt), jnp.asarray(pos0),
                          jnp.asarray(q_lens))
    act = jnp.float32 if cache == "f32" else jnp.bfloat16
    if cache == "int8":
        ak, ks = _quantize_rows(jnp.asarray(arena_k))
        av, vs = _quantize_rows(jnp.asarray(arena_v))
    else:
        ak, av = jnp.asarray(arena_k, act), jnp.asarray(arena_v, act)
        ks = vs = None
    seed_kv = None
    if T == 1:
        # the current token's exact rows ride in VMEM; the HBM copy at
        # pos0 is what the caller scatter-appended (masked in-kernel)
        seed_kv = (
            jnp.asarray(rng.standard_normal((B, F), np.float32) * 0.5,
                        act),
            jnp.asarray(rng.standard_normal((B, F), np.float32) * 0.5,
                        act))
    # (the arena rides as an argument: closed over, its GBs are
    # compiled into the program as a constant)
    got = jax.jit(lambda ak, av, ks, vs, w: ragged_paged_attention(
        q.astype(act), ak, av, layer, pt_j, pos_j, len_j, n_kv,
        scale=scale, page=page, window=w, cache_k_scale=ks,
        cache_v_scale=vs, seed_kv=seed_kv))(
            ak, av, ks, vs,
            None if window is None else jnp.asarray(window, jnp.int32))
    if not bool(jnp.all(jnp.isfinite(got))):
        return float("inf")  # pad queries are garbage, never non-finite

    @jax.jit
    def oracle(q1, ak, av, ks, vs, pt1, pos1, len1, seed1):
        return ragged_attention_reference(
            q1, ak, av, 1, pt1, pos1, len1, n_kv, scale=scale,
            page=page, window=window or None, cache_k_scale=ks,
            cache_v_scale=vs, seed_kv=seed1)

    err = 0.0
    for b in range(B):  # one row at a time: the oracle materializes
        # [H, T, max_seq] f32 scores
        if not q_lens[b]:
            continue  # a parked row: finite (above), otherwise unread
        sl = slice(b, b + 1)
        want = oracle(q[sl], ak, av, ks, vs, pt_j[sl], pos_j[sl],
                      len_j[sl],
                      None if seed_kv is None
                      else (seed_kv[0][sl], seed_kv[1][sl]))
        n = int(q_lens[b])
        err = max(err, float(jnp.max(jnp.abs(
            got[b, :n] - want[0, :n]))))
    return err


def check_forward_parity(geom: Geometry, seed: int = 0) -> float:
    """The kernel INSIDE the model: ``transformer.forward`` through the
    ragged route (table scatter-append + kernel, as every paged engine
    dispatch calls it) against the XLA gather/scatter route on the same
    two-layer model at this head geometry — a prompt chunk, then one
    decode step that attends what the chunk wrote. Returns the max
    logit difference over both steps relative to the logit scale."""
    from ..models.llm_spec import LLMSpec
    from ..models.transformer import (
        KVCache, forward, gather_kv_pages, init_params,
    )

    spec = LLMSpec(
        vocab_size=512, d_model=256, n_layers=2, n_heads=geom.n_heads,
        n_kv_heads=geom.n_kv_heads, d_head=geom.d_head, d_ff=512,
        max_position=geom.max_seq)
    params = init_params(jax.random.PRNGKey(seed), spec)
    page = geom.page
    B, T = 2, 16
    max_pages = geom.max_seq // page
    rng = np.random.default_rng(seed)
    pt = jnp.asarray(rng.permutation(np.arange(1, B * max_pages + 1))
                     .reshape(B, max_pages).astype(np.int32))
    tokens = jnp.asarray(rng.integers(0, spec.vocab_size, (B, T)),
                         jnp.int32)
    q_lens = jnp.asarray([T, T - 5], jnp.int32)
    worst = 0.0

    def ragged(cache, toks, pos0, lens):
        # the arena itself: table scatter-append + kernel
        return forward(spec, params, toks, pos0, cache, None,
                       page_table=pt, kv_page=page, q_lens=lens,
                       write_table=pt)

    def gathered(win, toks, pos0, lens):
        # a dense window view, carried from step to step
        return forward(spec, params, toks, pos0, win, None)

    arena = KVCache.create(spec, B * max_pages + 1, page, jnp.bfloat16)
    ones = jnp.ones((B,), jnp.int32)
    outs = []
    for step, cache in ((ragged, arena),
                        (gathered, gather_kv_pages(arena, pt, page))):
        l1, cache = jax.jit(step)(cache, tokens,
                                  jnp.zeros((B,), jnp.int32), q_lens)
        l2, _ = jax.jit(step)(cache, tokens[:, :1], q_lens, ones)
        outs.append((l1, l2))
    (r1, r2), (g1, g2) = outs
    for b in range(B):
        n = int(q_lens[b])
        for r, g in ((r1[b, :n], g1[b, :n]), (r2[b], g2[b])):
            worst = max(worst, float(
                jnp.max(jnp.abs(r - g)) / (jnp.max(jnp.abs(g)) + 1e-6)))
    return worst


def _device_lines(trace_dir: str):
    """(line name, its events) of every device plane's lines in the one
    capture under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                yield line.name, line.events


def _kernel_times_us(trace_dir: str) -> list[float]:
    """Device time of every ``ragged_paged_attention`` call in the one
    capture under ``trace_dir``, in the order the calls ran."""
    calls = [(e.start_ns, e.duration_ns)
             for name, events in _device_lines(trace_dir)
             if name == "XLA Ops"
             for e in events if "ragged_paged_attention" in e.name]
    return [d / 1e3 for _, d in sorted(calls)]


def sweep_decode_kernel(geom: Geometry, cache: str, window: int = 0, *,
                        pages=(1, 2, 3, 4, 9, 12), parked=(0, 1, 8),
                        parked_pages: int = 3, calls: int = 64,
                        seed: int = 0) -> dict[str, Any]:
    """Time the kernel ALONE at the decode shapes of ``geom``:
    ``[n_slots, 1]`` seeded rows, every row holding the same number of
    pages (the last one half full), for each entry of ``pages``; then at
    ``parked_pages`` a row with some rows parked (length 0: the rows
    read nothing), spread evenly through the batch. One program serves
    every point (context lengths are data), each point is ``calls``
    invocations in one scan over the layers, and a call's time is the
    median DEVICE time of its profiler events — chip only, no host
    clock. ``a_us`` / ``b_us`` fit ``us_row = a + b * pages`` over the
    points with no parked row; ``roof_share`` is the live rows' context
    (K and V data bytes, as ``attn_kernel_roofline_counted`` counts it)
    over peak HBM bytes per second over the call's time."""
    import tempfile

    from ..telemetry.costmodel import peak_rates
    from .ragged_paged_attention import ragged_paged_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("--sweep times the compiled kernel: chip only")
    hbm = peak_rates(dev.device_kind)[1]
    H, n_kv, dh, page, B = (geom.n_heads, geom.n_kv_heads, geom.d_head,
                            geom.page, geom.n_slots)
    F, L = n_kv * dh, 2
    max_pages = geom.max_seq // page
    n_pages = B * max_pages + 1
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    act = jnp.float32 if cache == "f32" else jnp.bfloat16
    shape = (L, n_pages, page, F)
    if cache == "int8":
        ak = jax.random.randint(keys[0], shape, -127, 128, jnp.int8)
        av = jax.random.randint(keys[1], shape, -127, 128, jnp.int8)
        ks = jax.random.uniform(keys[2], shape[:3], jnp.float32,
                                0.002, 0.006)
        vs = jax.random.uniform(keys[3], shape[:3], jnp.float32,
                                0.002, 0.006)
    else:
        ak = (jax.random.normal(keys[0], shape, jnp.float32) * 0.5
              ).astype(act)
        av = (jax.random.normal(keys[1], shape, jnp.float32) * 0.5
              ).astype(act)
        ks = vs = None
    q = (jax.random.normal(keys[4], (B, 1, H, dh), jnp.float32) * 0.3
         ).astype(act)
    seed_kv = tuple(
        (jax.random.normal(k, (B, F), jnp.float32) * 0.5).astype(act)
        for k in keys[5:7])
    pt = jnp.asarray(np.random.default_rng(seed).permutation(
        np.arange(1, n_pages)).reshape(B, max_pages).astype(np.int32))
    win = jnp.asarray(window, jnp.int32)

    @jax.jit
    def run(ak, av, ks, vs, pos0, q_lens):  # (the arena rides as an
        # argument: closed over, it is compiled in as a constant)
        def one(acc, i):
            out = ragged_paged_attention(
                q, ak, av, i % L, pt, pos0, q_lens, n_kv,
                scale=dh ** -0.5, page=page, window=win,
                cache_k_scale=ks, cache_v_scale=vs, seed_kv=seed_kv)
            return acc + out, None
        return jax.lax.scan(one, jnp.zeros((B, 1, H * dh), jnp.float32),
                            jnp.arange(calls, dtype=jnp.int32))[0]

    points = [(p, 0) for p in pages] + [
        (parked_pages, k) for k in parked if k]
    args = []
    for p, k in points:
        q_lens = np.ones(B, np.int32)
        q_lens[np.linspace(0, B - 1, k).round().astype(int)] = 0
        args.append((jnp.full((B,), (p - 1) * page + page // 2, jnp.int32),
                     jnp.asarray(q_lens)))
    arena = (ak, av, ks, vs)
    run(*arena, *args[0]).block_until_ready()  # compile + warm, untraced
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for a in args:
                run(*arena, *a).block_until_ready()
        times = _kernel_times_us(tmp)
    assert len(times) == calls * len(points), (len(times), calls)
    rows_out = []
    for i, ((p, k), (pos0, _)) in enumerate(zip(points, args)):
        us = float(np.median(times[i * calls:(i + 1) * calls]))
        live = B - k
        ctx_bytes = live * int(pos0[0]) * 2 * F * ak.dtype.itemsize
        rows_out.append({
            "rows": B, "pages": p, "parked": k,
            "us_call": round(us, 2), "us_row": round(us / B, 3),
            "roof_share": round(ctx_bytes / hbm / (us * 1e-6), 4)})
    fit = [r for r in rows_out if not r["parked"]]
    b_us, a_us = np.polyfit([r["pages"] for r in fit],
                            [r["us_row"] for r in fit], 1)
    return {
        "device_kind": dev.device_kind, "cache": cache, "window": window,
        "geometry": dataclasses.asdict(geom), "calls": calls,
        "points": rows_out, "a_us": round(float(a_us), 3),
        "b_us": round(float(b_us), 3),
        "page_pair_dma_us": round(
            2 * page * F * ak.dtype.itemsize / hbm * 1e6, 3)}


# ---------------------------------------------------------------------------
# the expert layer's grouped matmul (ops/grouped_matmul.py)
# ---------------------------------------------------------------------------

# (d_model, expert d_ff, experts held, experts the router scores): the
# benchmark's two expert configurations at published widths
EXPERT_WIDTHS = {"trinity": (2048, 1024, 128, 128),
                 "deepseek": (7168, 2048, 16, 256)}
_EXPERT_SMALL = (256, 128, 8, 16)  # off the chip: the interpreter's size
_EXPERT_K = 8  # experts a token, both configurations
_EXPERT_LAYERS = 2  # the stack's depth here: the offset is exercised
# tokens a step: decode rows | PR 38's middle point | a 512-token
# prompt row beside 16 decode rows
_EXPERT_TOKENS = (16, 144, 528)


def _expert_counts(rng, tokens: int, held: int, published: int,
                   pool: int) -> np.ndarray:
    """Assignments a HELD expert gets when each of ``tokens`` tokens
    picks ``_EXPERT_K`` distinct experts among ``pool`` of the
    ``published`` (the pool chosen at random; experts 0 .. held - 1 are
    the held ones): uniform routing at pool == published, the served
    skew at Trinity's pool of 50 (46 of 128 touched at 16 tokens)."""
    ids = rng.permutation(published)[:pool]
    picks = np.concatenate([rng.permutation(ids)[:_EXPERT_K]
                            for _ in range(tokens)])
    return np.bincount(picks[picks < held], minlength=held).astype(np.int32)


def _expert_stack(key, n: int, e: int, k: int, m: int, dtype):
    return (jax.random.normal(key, (n * e, k, m), jnp.float32)
            * (k ** -0.5)).astype(dtype)


def _expert_stacks(seed: int, d: int, f: int, held: int, dtype):
    """(gate, up, down) stacks of ``_EXPERT_LAYERS`` layers."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    n = _EXPERT_LAYERS
    return (_expert_stack(keys[0], n, held, d, f, dtype),
            _expert_stack(keys[1], n, held, d, f, dtype),
            _expert_stack(keys[2], n, held, f, d, dtype))


def _ragged_dot_layer(lhs, w, layer, counts):
    """The parent's form: ``lax.ragged_dot`` over the WHOLE stack with
    one layer's groups non-empty."""
    e = counts.shape[0]
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((w.shape[0],), jnp.int32), counts, (layer * e,))
    return jax.lax.ragged_dot(lhs, w, sizes)


def _kernel_layer(lhs, w, layer, counts):
    from . import grouped_matmul as gm

    rows = gm.padded_rows(lhs.shape[0])
    lhs = jnp.pad(lhs, ((0, rows - lhs.shape[0]), (0, 0)))
    return gm.grouped_matmul(lhs, (w,), layer, gm.schedule(counts, rows))[0]


def check_grouped_matmul(widths=EXPERT_WIDTHS["trinity"], dtype="bf16",
                         seed: int = 0) -> dict[str, Any]:
    """The grouped-matmul kernel against ``lax.ragged_dot`` at one
    configuration's widths, both projections' shapes (in -> d_ff and
    d_ff -> out), on the device JAX finds:

    - ``max_rel_err``: the worst |kernel - ragged_dot| over the rows a
      group holds, relative to the largest output, over the three row
      counts and every layer of the stack (two roundings of one f32
      sum: a bf16 ulp);
    - ``rows_equal``: ONE row's output bit for bit when it rides among
      4, 16 and 528 tokens' assignments (the same row of the same
      expert; the other rows and the groups' sizes differ) — what the
      fixed row tile is for; ``ragged_dot_rows_equal`` is the same
      question put to XLA's op ALONE (equal too on a v5e: the
      row-count rounding PR 45 found is the whole program's)."""
    d, f, held, published = widths
    dt = jnp.float32 if dtype == "f32" else jnp.bfloat16
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    probe_e = held // 3
    worst, equal, xla_equal = 0.0, True, True
    kernel, xla = jax.jit(_kernel_layer), jax.jit(_ragged_dot_layer)
    for i, (k, m) in enumerate(((d, f), (f, d))):
        w = _expert_stack(keys[i], _EXPERT_LAYERS, held, k, m, dt)
        probe = jax.random.normal(keys[2 + i], (1, k), jnp.float32)
        seen, xla_seen = [], []
        for tokens in (4, 16, 528):
            counts = _expert_counts(rng, tokens, held, published, published)
            counts[probe_e] += 1  # the probe: its group's first row
            rows = tokens * _EXPERT_K + 1
            lhs = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
            at = int(counts[:probe_e].sum())
            lhs = lhs.at[at].set(probe[0]).astype(dt)
            total = int(counts.sum())
            for layer in range(_EXPERT_LAYERS):
                got = kernel(lhs, w, layer, jnp.asarray(counts))
                want = xla(lhs, w, layer, jnp.asarray(counts))
                g32 = np.asarray(got[:total], np.float32)
                w32 = np.asarray(want[:total], np.float32)
                worst = max(worst, float(
                    np.max(np.abs(g32 - w32)) / (np.max(np.abs(w32)) + 1e-9)))
            seen.append(np.asarray(got[at], np.float32))
            xla_seen.append(np.asarray(want[at], np.float32))
        equal &= all(np.array_equal(seen[0], o) for o in seen[1:])
        xla_equal &= all(np.array_equal(xla_seen[0], o) for o in xla_seen[1:])
    return {"max_rel_err": worst, "rows_equal": bool(equal),
            "ragged_dot_rows_equal": bool(xla_equal)}


def _module_times_us(trace_dir: str, name: str) -> "tuple[list, list]":
    """(device time of every run of the program whose name holds
    ``name``, in the order they ran; the time of the ``ragged-dot*``
    ops inside each — XLA's op, its set-up, or the kernel under that
    name) in the one capture under ``trace_dir``."""
    mods, ops = [], []
    for line, events in _device_lines(trace_dir):
        if line == "XLA Modules":
            mods += [(e.start_ns, e.duration_ns) for e in events
                     if name in e.name]
        elif line == "XLA Ops":
            ops += [(e.start_ns, e.duration_ns) for e in events
                    if e.name.lstrip("%").startswith("ragged-dot")]
    mods.sort()
    inside = [sum(d for s, d in ops if m0 <= s < m0 + md)
              for m0, md in mods]
    return [d / 1e3 for _, d in mods], [d / 1e3 for d in inside]


def _module_ops_us(trace_dir: str, name: str) -> "list[tuple]":
    """For every run of the program whose name holds ``name``, in the
    order they ran: (its device time, {an op's own name without its
    number: its time}), us; ``while`` wrappers left out."""
    import re

    mods, ops = [], []
    for line, events in _device_lines(trace_dir):
        if line == "XLA Modules":
            mods += [(e.start_ns, e.duration_ns) for e in events
                     if name in e.name]
        elif line == "XLA Ops":
            ops += [(e.start_ns, e.duration_ns,
                     re.sub(r"\.\d+$", "", re.split(
                         r"[ =(]", e.name.lstrip("%"), maxsplit=1)[0]))
                    for e in events]
    out = []
    for m0, md in sorted(mods):
        by: dict = {}
        for s, d, op in ops:
            if m0 <= s < m0 + md and not op.startswith("while"):
                by[op] = by.get(op, 0.0) + d / 1e3
        out.append((md / 1e3, by))
    return out


def sweep_grouped_matmul(widths, label: str, *, calls: int = 12,
                         seed: int = 0) -> dict[str, Any]:
    """Time ONE expert layer's three grouped matmuls (gate, up, the
    activation and their product, down) ALONE at ``widths``, bf16, for
    16 | 144 | 528 tokens' assignments under uniform routing and, for a
    layer that holds every expert, the served skew: ``lax.ragged_dot``
    as the parent calls it (the whole stack as groups), ``megablox.gmm``
    (jax's Pallas grouped matmul, tiled 128 x 2048 | 1024 x 512) and
    the kernel (gate and up in one call: each projection in a call of
    its own read 0-6 % slower, PERF.md §6 PR 46). A point is
    ``calls`` layer-steps in one scan that walks the stack's layers;
    ``us_layer`` is the program's DEVICE time a layer-step (the
    schedule or metadata ops included), ``us_matmul`` the part under
    ops named ``ragged-dot*``; ``roof`` the touched experts' bytes over
    peak HBM bytes per second over ``us_layer``. Chip only."""
    import tempfile

    from jax.experimental.pallas.ops.tpu.megablox import gmm as mb

    from ..telemetry.costmodel import peak_rates
    from . import grouped_matmul as gm

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("--sweep times the compiled kernel: chip only")
    hbm = peak_rates(dev.device_kind)[1]
    d, f, held, published = widths
    dt, n = jnp.bfloat16, _EXPERT_LAYERS
    stacks = _expert_stacks(seed, d, f, held, dt)
    act = jax.nn.silu

    def xla_layer(x, ws, layer, counts):
        g, u = (_ragged_dot_layer(x, w, layer, counts) for w in ws[:2])
        return _ragged_dot_layer((act(g) * u).astype(dt), ws[2], layer,
                                 counts)

    def gmm_layer(x, ws, layer, counts):
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n * held,), jnp.int32), counts, (layer * held,))

        def one(a, w):
            tk = 2048 if a.shape[1] % 2048 == 0 else 1024
            return mb(a, w, sizes, dt, (128, min(tk, a.shape[1]), 512))
        g, u = one(x, ws[0]), one(x, ws[1])
        return one((act(g) * u).astype(dt), ws[2])

    def kernel_layer(x, ws, layer, counts):
        sched = gm.schedule(counts, x.shape[0])
        g, u = gm.grouped_matmul(x, ws[:2], layer, sched)
        return gm.grouped_matmul((act(g) * u).astype(dt), ws[2:], layer,
                                 sched)[0]

    def program(layer_fn, name):
        def run(x, ws, counts):
            def one(acc, i):
                y = layer_fn(x, ws, i % n, counts)
                return acc + y[:8].astype(jnp.float32), None
            return jax.lax.scan(one, jnp.zeros((8, d), jnp.float32),
                                jnp.arange(calls, dtype=jnp.int32))[0]
        run.__name__ = name
        return jax.jit(run)

    impls = {"ragged_dot": program(xla_layer, "sweep_ragged_dot"),
             "gmm": program(gmm_layer, "sweep_gmm"),
             "kernel": program(kernel_layer, "sweep_kernel")}
    rng = np.random.default_rng(seed)
    routings = {"uniform": published}
    if held == published:
        routings["skew"] = 50
    points = []
    for routing, pool in routings.items():
        for tokens in _EXPERT_TOKENS:
            counts = _expert_counts(rng, tokens, held, published, pool)
            rows = gm.padded_rows(tokens * _EXPERT_K)
            x = jnp.asarray(rng.standard_normal((rows, d)), dt)
            points.append((routing, tokens, jnp.asarray(counts), x))
    out = []
    for name, fn in impls.items():
        for *_, counts, x in points:  # compile + warm, untraced
            fn(x, stacks, counts).block_until_ready()
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for *_, counts, x in points:
                    fn(x, stacks, counts).block_until_ready()
            mods, inside = _module_times_us(tmp, fn.__name__)
        assert len(mods) == len(points), (name, len(mods))
        for (routing, tokens, counts, _), us, us_in in zip(
                points, mods, inside):
            touched = int(np.count_nonzero(np.asarray(counts)))
            floor_us = touched * 3 * d * f * 2 / hbm * 1e6
            out.append({
                "impl": name, "routing": routing, "tokens": tokens,
                "assignments": int(np.asarray(counts).sum()),
                "touched": touched,
                "us_layer": round(us / calls, 1),
                "us_matmul": round(us_in / calls, 1),
                "roof": round(floor_us / (us / calls), 4)})
    return {"device_kind": dev.device_kind, "widths": label,
            "d_model": d, "d_ff": f, "held": held, "published": published,
            "calls": calls, "points": out}


# ---------------------------------------------------------------------------
# the dispatch AROUND the grouped matmul of a layer that holds a share
# (ops/expert_rows.py)
# ---------------------------------------------------------------------------

_DISPATCH_TOKENS = (16, 528)  # a decode step | a 512-token prompt step


def _share_routing(rng, tokens: int, held: int, published: int,
                   probe=None):
    """Uniform routing of ``tokens`` tokens over ``published`` experts
    of which 0 .. held - 1 are here: (group of each assignment, the
    sentinel ``held`` for an absent one [tokens * K] i32, weights
    [tokens, K] f32). ``probe`` = (token, its K picks, its K weights)
    overrides one token's."""
    ids = np.stack([rng.permutation(published)[:_EXPERT_K]
                    for _ in range(tokens)])
    w = rng.uniform(0.05, 0.3, (tokens, _EXPERT_K)).astype(np.float32)
    if probe is not None:
        ids[probe[0]], w[probe[0]] = probe[1], probe[2]
    flat = np.where(ids < held, ids, held).reshape(-1).astype(np.int32)
    return flat, w


def _sorted(flat, held: int):
    """``_moe_mlp``'s sort: (the assignments in expert order [N * K],
    rows a held expert [held])."""
    return (jnp.argsort(flat, stable=True).astype(jnp.int32),
            jnp.zeros((held,), jnp.int32).at[flat].add(1, mode="drop"))


def _dispatch_layer(form: str, act, x, ws, layer, order, counts, w):
    """One expert layer after the router and the sort, ``_moe_mlp``'s
    kernel branch for a share: ``xla`` as the parent traces it (gather
    of all N * K rows, mask, un-sort, weighted sum), ``rows`` through
    ``ops/expert_rows.py`` (the held rows alone).
    -> (out f32 [N, D], the gathered rows, H)."""
    from . import expert_rows as er
    from . import grouped_matmul as gm

    N, D = x.shape
    K = _EXPERT_K
    rows = gm.padded_rows(N * K)
    src = jnp.pad(order, (0, rows - N * K)) // K
    sched = gm.schedule(counts, rows)
    held = jnp.sum(counts)
    xs = x[src] if form == "xla" else er.gather_rows(x, src, held)
    g, u = gm.grouped_matmul(xs, ws[:2], layer, sched)
    (y,) = gm.grouped_matmul((act(g) * u).astype(x.dtype), ws[2:], layer,
                             sched)
    if form != "xla":
        return (er.combine_rows(y, src, w.reshape(-1)[order], held, N),
                xs, held)
    y = y[:N * K]
    y = jnp.where(jnp.arange(N * K, dtype=jnp.int32)[:, None] < held, y, 0)
    inv = jnp.zeros((N * K,), jnp.int32).at[order].set(
        jnp.arange(N * K, dtype=jnp.int32))
    return jnp.einsum("nkd,nk->nd",
                      y[inv].reshape(N, K, D).astype(jnp.float32), w), xs, held


def check_expert_rows(widths=EXPERT_WIDTHS["deepseek"],
                      seed: int = 0) -> dict[str, Any]:
    """The row kernels of a layer that holds a share
    (ops/expert_rows.py) at one configuration's widths against the XLA
    dispatch they replace, the grouped kernel multiplying both times,
    for a decode step's 16 tokens and a prompt step's 528, on the
    device JAX finds:

    - ``gather_rows_differ``: rows r < H of the gathered array that are
      not the token's row bit for bit (0);
    - ``max_rel_err``: the worst |rows - xla| of the layer's f32 output
      relative to the largest (the same products, summed over a token's
      experts in another order: f32 round-off);
    - ``rows_equal``: ONE token's output bit for bit in both steps —
      its row, its picks (two of them held) and its weights the same,
      the other tokens and its index not."""
    d, f, held, published = widths
    dt = jnp.bfloat16
    rng = np.random.default_rng(seed)
    stacks = _expert_stacks(seed, d, f, held, dt)
    picks = np.concatenate([[held - 1, 0],
                            held + 1 + np.arange(_EXPERT_K - 2)])
    probe_w = rng.uniform(0.05, 0.3, (_EXPERT_K,)).astype(np.float32)
    probe_x = rng.standard_normal((d,)) * 0.1
    layer = jax.jit(  # (the stacks as an argument: closed over, 1.4 GB
        # of them would be constants of every program)
        lambda form, x, ws, flat, w: _dispatch_layer(
            form, jax.nn.silu, x, ws, 1, *_sorted(flat, held), w),
        static_argnums=0)
    worst, differ, seen = 0.0, 0, []
    for tokens, at in zip(_DISPATCH_TOKENS, (3, 301)):
        flat, w = _share_routing(rng, tokens, held, published,
                                 (at, picks, probe_w))
        x = rng.standard_normal((tokens, d)) * 0.1
        x[at] = probe_x
        args = (jnp.asarray(x, dt), stacks, jnp.asarray(flat),
                jnp.asarray(w))
        got, xs, h = layer("rows", *args)
        want, xs_xla, _ = layer("xla", *args)
        h = int(h)
        differ += int(np.sum(np.any(
            np.asarray(xs[:h], np.float32)
            != np.asarray(xs_xla[:h], np.float32), axis=1)))
        got, want = np.asarray(got), np.asarray(want)
        worst = max(worst, float(np.max(np.abs(got - want))
                                 / (np.max(np.abs(want)) + 1e-9)))
        seen.append(got[at])
    return {"max_rel_err": worst, "gather_rows_differ": differ,
            "rows_equal": bool(np.array_equal(*seen)
                               and np.any(seen[0] != 0))}


def sweep_expert_dispatch(widths, label: str, *, calls: int = 10,
                          seed: int = 0) -> dict[str, Any]:
    """Time what surrounds ONE expert layer's grouped matmuls ALONE at
    ``widths`` (a layer that holds ``held`` of ``published`` experts,
    bf16, uniform routing: 6-7 % of the assignments held), for a decode
    step's 16 tokens and a prompt step's 528: the parent's XLA form
    beside the row kernels. A point is ``calls`` layer-steps in one
    scan whose carry is the layer's input (x += layer(x), so nothing is
    hoisted); router and sort are outside it. ``us_layer`` the
    program's device time a layer-step, ``us_matmul`` the part under
    ``ragged-dot*``, ``us_dispatch`` the rest: gather, activation,
    mask, un-sort and sum — or the two kernels. Chip only."""
    import tempfile

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("--sweep times the compiled kernels: chip only")
    d, f, held, published = widths
    dt, n = jnp.bfloat16, _EXPERT_LAYERS
    stacks = _expert_stacks(seed, d, f, held, dt)

    def program(form):
        def run(x, ws, flat, w):
            order, counts = _sorted(flat, held)

            def one(x, i):
                out, _, _ = _dispatch_layer(form, jax.nn.silu, x, ws, i % n,
                                            order, counts, w)
                return x + out.astype(x.dtype), None
            return jax.lax.scan(one, x,
                                jnp.arange(calls, dtype=jnp.int32))[0]
        run.__name__ = f"sweep_dispatch_{form}"
        return jax.jit(run)

    rng = np.random.default_rng(seed)
    points = []
    for tokens in _DISPATCH_TOKENS:
        flat, w = _share_routing(rng, tokens, held, published)
        x = jnp.asarray(rng.standard_normal((tokens, d)) * 0.1, dt)
        points.append((tokens, jnp.asarray(flat), jnp.asarray(w), x))
    out = []
    for form in ("xla", "rows"):
        fn = program(form)
        for _, flat, w, x in points:  # compile + warm, untraced
            fn(x, stacks, flat, w).block_until_ready()
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _, flat, w, x in points:
                    fn(x, stacks, flat, w).block_until_ready()
            runs = _module_ops_us(tmp, fn.__name__)
        assert len(runs) == len(points), (form, len(runs))
        for (tokens, flat, *_), (us, ops) in zip(points, runs):
            us_in = sum(v for k, v in ops.items()
                        if k.startswith("ragged-dot"))
            top = sorted(ops.items(), key=lambda kv: -kv[1])[:8]
            out.append({
                "form": form, "tokens": tokens,
                "held_rows": int(np.sum(np.asarray(flat) < held)),
                "us_layer": round(us / calls, 1),
                "us_matmul": round(us_in / calls, 1),
                "us_dispatch": round((us - us_in) / calls, 1),
                "ops_us": {k: round(v / calls, 1) for k, v in top}})
    return {"device_kind": dev.device_kind, "widths": label, "d_model": d,
            "d_ff": f, "held": held, "published": published, "calls": calls,
            "points": out}


# (max abs error) budgets: attention outputs are O(1) post-softmax and
# bf16 inputs put parity at ~1e-2; int8 pages add their rounding
# latent attention's widths (heads, d_nope, d_rope, d_v, rank, page,
# the prompt row's length): DeepSeek-V3's as published and served, and
# the interpreter's size off the chip
LATENT_WIDTHS = {"deepseek": (128, 128, 64, 128, 512, 256, 512)}
_LATENT_SMALL = (4, 16, 16, 16, 128, 8, 32)


def check_latent_flash(widths=LATENT_WIDTHS["deepseek"],
                       seed: int = 0) -> dict[str, Any]:
    """The expanded flash kernel (ops/latent_flash_attention.py) at one
    configuration's widths against the XLA form it replaces for prompt
    rows (``models/transformer.py`` ``latent_attend_expanded``, same
    dtype), on the device JAX finds — two rows a call, one starting on
    a page boundary with a whole chunk, one off it with a chunk cut by
    ``q_lens``:

    - ``max_rel_err``: the worst |kernel - XLA| over the valid queries,
      relative to the largest output (two flash-vs-dense roundings of
      bf16 probabilities);
    - ``rows_equal``: a token's output bit for bit at two indices of
      two chunks over the same pages (the second chunk starts T/4 + 5
      tokens later, off the boundary, and is cut short) — what the
      benchmark's repeated-prompt probe needs on the chip."""
    from types import SimpleNamespace

    from ..models.transformer import latent_attend_expanded
    from .latent_flash_attention import join_query, latent_flash_attention

    H, dn, dr, dv, r, page, T = widths
    F = -(-(r + dr) // 128) * 128
    dt = jnp.bfloat16
    n_pos = 4 * T
    maxp = n_pos // page
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    rows = jax.random.normal(ks[0], (2, n_pos, F))
    rows = rows.at[..., r + dr:].set(0).astype(dt)
    arena = jnp.concatenate(
        [jnp.zeros((1, page, F), dt), rows.reshape(2 * maxp, page, F)])[None]
    table = (1 + jnp.arange(2 * maxp, dtype=jnp.int32)).reshape(2, maxp)
    wk = (jax.random.normal(ks[1], (2, H, dn, r)) * r ** -0.5).astype(dt)
    wv = (jax.random.normal(ks[2], (2, H, r, dv)) * r ** -0.5).astype(dt)
    qn = jax.random.normal(ks[3], (2, n_pos, H, dn)).astype(dt)
    qr = jax.random.normal(ks[4], (2, n_pos, H, dr)).astype(dt)
    spec = SimpleNamespace(kv_lora_rank=r, qk_rope_dim=dr, d_head=dn + dr,
                           attn_scale_mult=1.0)
    scale = (dn + dr) ** -0.5
    b = np.arange(2)[:, None]

    @jax.jit
    def kernel(pos0, q_lens):
        at = pos0[:, None] + jnp.arange(T)[None]
        return latent_flash_attention(
            join_query(qn[b, at], qr[b, at], F - r), arena, jnp.int32(0),
            table, pos0, q_lens, wk, wv, jnp.int32(1), scale=scale,
            page=page)

    @jax.jit
    def xla(pos0):
        at = pos0[:, None] + jnp.arange(T)[None]
        return latent_attend_expanded(
            spec, {"wkv_b_k": wk[1], "wkv_b_v": wv[1]}, qn[b, at],
            qr[b, at], rows, at)

    off = page // 2 - 3  # a chunk that starts off a page boundary
    pos0 = jnp.asarray([2 * T, T + off], jnp.int32)
    q_lens = jnp.asarray([T, T - T // 3], jnp.int32)
    got = np.asarray(kernel(pos0, q_lens), np.float32)
    want = np.asarray(xla(pos0), np.float32)
    worst = 0.0
    for i, n in enumerate(np.asarray(q_lens)):
        worst = max(worst, float(np.max(np.abs(got[i, :n] - want[i, :n]))
                                 / (np.max(np.abs(want[i, :n])) + 1e-9)))
    # row 0's tokens again, their chunk ``late`` tokens later, cut short
    late = T // 4 + 5
    again = np.asarray(
        kernel(pos0.at[0].add(late), q_lens.at[0].set(T - 9)), np.float32)
    equal = np.array_equal(got[0, late:], again[0, :T - late])
    return {"max_rel_err": worst, "rows_equal": bool(equal)}


_TOL_FP, _TOL_INT8 = 2e-2, 5e-2
_TOL_FORWARD = 5e-2  # relative to the logit scale, bf16 end to end
_TOL_ROWS = 1e-5  # one f32 sum of <= 8 products in two orders


def run_kernel_checks(geom: Geometry = SERVING) -> dict[str, Any]:
    """Every compiled-kernel parity number plus the device they ran on
    and a pass/fail verdict. A kernel that does not compile raises: a
    crash here is the finding, and the caller (module entry, bench,
    chip_smoke) must not mistake it for a result."""
    dev = jax.devices()[0]
    out: dict[str, Any] = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "geometry": dataclasses.asdict(geom),
    }
    budget: dict[str, float] = {}

    def leg(name: str, err: float, tol: float) -> None:
        out[name] = round(err, 5)
        budget[name] = tol

    # the engine's own row shapes through the ONE kernel
    for kind in ("decode", "decode_parked", "chunk", "mixed"):
        leg(f"serving_{kind}_max_err",
            check_serving_rows(geom, kind, "bf16"), _TOL_FP)
        leg(f"serving_{kind}_int8_max_err",
            check_serving_rows(geom, kind, "int8"), _TOL_INT8)
    # a layer with a sliding window (the kernel's operand): the pages
    # wholly below it are skipped, the boundary falls inside a page
    win = geom.max_seq // 2 + geom.page // 2
    for kind in ("decode", "mixed"):
        leg(f"serving_{kind}_window_max_err",
            check_serving_rows(geom, kind, "bf16", window=win), _TOL_FP)
        leg(f"serving_{kind}_window_int8_max_err",
            check_serving_rows(geom, kind, "int8", window=win), _TOL_INT8)
    # an f32 model (dtype: float32): f32 queries, seed rows and pages
    for kind in ("decode", "mixed"):
        leg(f"serving_{kind}_f32_max_err",
            check_serving_rows(geom, kind, "f32"), _TOL_FP)
    leg("forward_parity_rel_err", check_forward_parity(geom),
        _TOL_FORWARD)
    # the wrappers the non-default cache layouts use: dense cache viewed
    # as pages (LOCALAI_PAGED_KV=off) and the page-table indirection
    leg("decode_attention_max_err", check_decode_attention(False),
        _TOL_FP)
    leg("decode_attention_int8_max_err", check_decode_attention(True),
        _TOL_INT8)
    leg("paged_gather_max_err", check_paged_gather(False), _TOL_FP)
    leg("paged_gather_int8_max_err", check_paged_gather(True), _TOL_INT8)
    # pod-scale legs: the shard_map'd append+attend wrapper and the
    # GSPMD gather fallback over a "model"-sharded arena vs the same
    # dense single-device oracles (skipped on 1-device hosts). The
    # gather is pure indexing — anything nonzero is a bug
    if check_meshed_ragged_attention(False, mix="decode") is not None:
        leg("meshed_ragged_max_err", max(
            check_meshed_ragged_attention(False, mix=m)
            for m in ("mixed", "decode")), _TOL_FP)
        leg("meshed_ragged_int8_max_err", max(
            check_meshed_ragged_attention(True, mix=m)
            for m in ("mixed", "decode")), _TOL_INT8)
        leg("meshed_paged_gather_max_err",
            check_meshed_paged_gather(False), 0.0)
        leg("meshed_paged_gather_int8_max_err",
            check_meshed_paged_gather(True), 0.0)
    # the expert layer's grouped matmul at both expert configurations'
    # widths (interpreted off the chip: at the interpreter's): against
    # XLA's op within bf16 rounding, and one row's bits the same
    # whatever the step's row count
    for label, widths in (EXPERT_WIDTHS if dev.platform == "tpu"
                          else {"small": _EXPERT_SMALL}).items():
        res = check_grouped_matmul(widths)
        leg(f"grouped_matmul_{label}_max_rel_err", res["max_rel_err"],
            _TOL_FP)
        leg(f"grouped_matmul_{label}_rows_differ",
            0.0 if res["rows_equal"] else 1.0, 0.0)
        out[f"ragged_dot_{label}_rows_equal"] = res["ragged_dot_rows_equal"]
    # a layer that holds a share: the row kernels around the grouped
    # matmul against XLA's gather, mask, un-sort and sum, and a token's
    # bits the same in a decode step and in a prompt step
    for label, widths in ({"deepseek": EXPERT_WIDTHS["deepseek"]}
                          if dev.platform == "tpu"
                          else {"small": _EXPERT_SMALL}).items():
        res = check_expert_rows(widths)
        leg(f"expert_rows_{label}_max_rel_err", res["max_rel_err"],
            _TOL_ROWS)
        leg(f"expert_rows_{label}_gather_rows_differ",
            float(res["gather_rows_differ"]), 0.0)
        leg(f"expert_rows_{label}_rows_differ",
            0.0 if res["rows_equal"] else 1.0, 0.0)
    # a latent model's prompt rows: the expanded flash kernel against
    # the XLA form at the published widths, and a token's bits the same
    # wherever its chunk started
    for label, widths in (LATENT_WIDTHS if dev.platform == "tpu"
                          else {"small": _LATENT_SMALL}).items():
        res = check_latent_flash(widths)
        leg(f"latent_flash_{label}_max_rel_err", res["max_rel_err"],
            _TOL_FP)
        leg(f"latent_flash_{label}_rows_differ",
            0.0 if res["rows_equal"] else 1.0, 0.0)
    out["failed"] = sorted(  # (NaN fails: it is not <= anything)
        name for name, tol in budget.items() if not out[name] <= tol)
    out["ok"] = not out["failed"]
    return out


def main(argv: "list[str] | None" = None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        "python -m localai_tfp_tpu.ops.kernel_check",
        description="compiled-kernel parity on the device JAX finds")
    ap.add_argument("--small", action="store_true",
                    help="interpreter-friendly sizes (CPU debugging)")
    for f in dataclasses.fields(Geometry):
        ap.add_argument("--" + f.name.replace("_", "-"), type=int,
                        default=None)
    ap.add_argument("--sweep", action="store_true",
                    help="time the kernel alone over pages a row and "
                    "parked rows at the geometry's decode shapes (a "
                    "tool: one JSON line a cache dtype; chip only)")
    ap.add_argument("--experts", action="store_true",
                    help="--sweep: time the expert layer's grouped "
                    "matmuls alone instead (lax.ragged_dot, megablox."
                    "gmm, the kernel; one JSON line a configuration)")
    ap.add_argument("--dispatch", action="store_true",
                    help="--sweep: time what surrounds the grouped matmul "
                    "of a layer that holds a share instead (XLA's gather, "
                    "mask, un-sort and sum beside the row kernels)")
    ap.add_argument("--cache", default="int8,bf16",
                    help="--sweep: arena dtypes, comma-separated")
    ap.add_argument("--window", type=int, default=0,
                    help="--sweep: the layer's window operand (0 = full)")
    args = ap.parse_args(argv)
    geom = SMALL if args.small else SERVING
    geom = dataclasses.replace(geom, **{
        f.name: getattr(args, f.name)
        for f in dataclasses.fields(Geometry)
        if getattr(args, f.name) is not None})
    if args.sweep and args.dispatch:
        print(json.dumps(sweep_expert_dispatch(
            EXPERT_WIDTHS["deepseek"], "deepseek")), flush=True)
        return 0
    if args.sweep and args.experts:
        for label, widths in EXPERT_WIDTHS.items():
            print(json.dumps(sweep_grouped_matmul(widths, label)),
                  flush=True)
        return 0
    if args.sweep:
        for cache in args.cache.split(","):
            print(json.dumps(
                sweep_decode_kernel(geom, cache, args.window)), flush=True)
        return 0
    res = run_kernel_checks(geom)
    print(json.dumps(res))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
