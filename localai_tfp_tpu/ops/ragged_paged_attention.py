"""Ragged paged attention: ONE Pallas TPU kernel for every row kind.

PR 5's paged KV pool still fed three device paths — the fused decode
kernel (ops/decode_attention.py), the XLA gather/scatter window view
(models/transformer.py gather_kv_pages), and the mixed dispatch's
bucket x window variant ladder. This kernel unifies them following
"Ragged Paged Attention" (PAPERS.md, arxiv 2604.15464): the batch is
RAGGED in both axes — every row carries its own query length (1 for decode
rows, the chunk length for prefill rows, k+1 for spec-decode verify
rows) and its own context length — and one kernel invocation walks each
row's page table, DMA-ing only the pages covering its live context.

Shapes:
- the paged arena ``[L, n_pages, page, F]`` (F = n_kv_heads * d_head,
  head-FLAT like the dense cache — full 128-lane rows, no relayouts),
  addressed with a layer scalar so the caller's layer scan never slices
  arena buffers;
- per-row int32 page tables ``[B, max_pages]`` (scalar-prefetch operand:
  DMA source addresses are computable before the body runs; entries
  beyond a row's allocation point at the trash page, whose garbage is
  causally masked);
- queries ``[B, T, H, Dh]`` with per-row valid lengths ``q_lens`` and
  start positions ``pos0`` — query t of row b sits at absolute position
  pos0[b] + t and attends positions [max(0, pos+1-window), pos].

Design notes (see /opt/skills/guides/pallas_guide.md):
- grid = (row, query block): an inner double-buffered manual-DMA loop
  walks only the pages that block's queries can see (a grid over
  max_seq pages pays a fixed per-page cost, valid or not). Query
  blocking (``_q_tiling``) bounds the per-step VMEM footprint — a
  2048-token chunk never holds an [n_heads * 2048, page] logits slab —
  and blocks wholly beyond a row's q_len read nothing.
- queries ride as ``[B, Hkv, T*group, Dh]``: the kernel picks a kv head
  on a LEADING dim and contracts ``q_h [TQ*group, Dh] @ k_page_h.T`` on
  the MXU; the per-head k/v bands are 128-lane-aligned column slices of
  the head-flat page. No sublane slicing or stacking anywhere, so the
  T == 1 / group 4 decode case lays out as whole tiles.
- flash state (m, l, acc) lives in VMEM scratch, one slab per kv head,
  m/l lane-replicated so every load/store is a full vreg.
- int8 k/v pages dequantize by PER-ROW scales that commute through the
  row-wise contractions: the k scale multiplies logits on the kv axis
  and the v scale folds into pexp before the pv matmul — the MXU never
  reads a dequantized page from HBM.
- ``seed_kv`` (decode wrappers, T == 1): the current token's exact
  K/V rows ride in VMEM and seed the flash accumulator while their HBM
  copy is masked — preserving the fused decode kernel's numerics
  (an int8 cache attends the EXACT current row, not its quantized HBM
  copy).

The XLA fallback (CPU tests / meshed engines / ineligible shapes) is
the existing gather-a-window-view path: engine dispatch functions keep
gathering ``gather_kv_pages`` at FULL table width, which is value-
identical to the kernel's ragged reads (``ragged_attention_reference``
below is the dense-math oracle kernel_check compares against).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _interpret

NEG_INF = -1e30

# query rows (n_heads * TQ) of f32 softmax state one grid step may hold.
# At 32 heads this is TQ 64: per step ~1 MiB each of m/l/acc scratch,
# a [256, page] f32 logits slab per kv head, and the double-buffered
# q/out blocks — about 10 MiB, against which the limit below leaves the
# compiler room for its own temporaries.
_ROWS_PER_STEP = 2048
_STAT_LANES = 128  # m/l scratch rows are lane-replicated (full vregs)
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _q_tiling(T: int, n_heads: int, group: int) -> tuple[int, int]:
    """(TQ, Tp): query tokens per grid step and the padded query length.

    Two constraints, both about what Mosaic will lay out:
    - each kv head's query slab is ``[TQ * group, Dh]``; its row count
      must fill whole sublane tiles (16 rows covers bf16 and f32), so TQ
      is a multiple of ``16 / gcd(16, group)`` — a T == 1 decode row at
      group 4 rides as 4 query slots of which 1 is valid, never as a
      4-row sub-tile slice;
    - one grid step holds ``n_heads * TQ`` query rows of f32 softmax
      state in VMEM; _ROWS_PER_STEP bounds that, so a 2048-token prefill
      chunk walks its pages in query blocks instead of materializing a
      [n_heads * 2048, page] logits slab."""
    tq_min = 16 // math.gcd(16, group)
    tq_cap = max(tq_min, _ROWS_PER_STEP // n_heads // tq_min * tq_min)
    if T <= tq_cap:
        tq = -(-T // tq_min) * tq_min
        return tq, tq
    return tq_cap, -(-T // tq_cap) * tq_cap


def _ragged_kernel(*refs, scale: float, page: int, tq: int, group: int,
                   n_kv_heads: int, d_head: int, quantized: bool,
                   seeded: bool):
    qlen_ref, pos_ref, layer_ref, win_ref, pt_ref, q_ref, *rest = refs
    if seeded:
        newk_ref, newv_ref, *rest = rest
    ck_in, cv_in, *rest = rest
    if quantized:
        ks_ref, vs_ref, *rest = rest
    out_ref, kbuf, vbuf, rsem, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    t0 = pl.program_id(1) * tq  # first query token of this block
    layer = layer_ref[0]
    window = win_ref[0]  # this layer's sliding window; 0 = full attention
    qlen = qlen_ref[b]
    p0 = pos_ref[b]
    R = tq * group  # query rows per kv head: row = t_local*group + g
    # absolute position of each query row of the block
    row_i = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    t_i = t0 + row_i // group
    qpos = p0 + t_i  # [R, 1]
    q_valid = t_i < qlen  # pad queries beyond the row's ragged length
    hi = qpos - (1 if seeded else 0)  # last HBM row each query attends
    # context this block reads: through its LAST valid query (causal),
    # nothing at all when the block lies wholly beyond q_len. Seeded
    # mode keeps the current token in VMEM and masks its HBM copy (the
    # decode kernel's contract)
    t_end = jnp.minimum(qlen, t0 + tq)
    n_hbm = p0 + t_end - (1 if seeded else 0)
    n_pages = jnp.where(t_end > t0, lax.div(n_hbm + page - 1, page), 0)
    # pages wholly below the block's EARLIEST query window are never
    # read; the per-query mask below handles the ragged boundary
    first_page = jnp.where(
        window > 0, lax.div(jnp.maximum(p0 + t0 + 1 - window, 0), page), 0)
    # the last position BELOW each query's window (none: -1)
    below = jnp.where(window > 0, qpos - window, -1)  # [R, 1]

    def band(h):
        return slice(h * d_head, (h + 1) * d_head)

    def widen(x):
        """int8 page rows -> the query dtype. Mosaic converts int8 only
        through f32; the values (|x| <= 127) are exact in bf16."""
        if quantized:
            return x.astype(jnp.float32).astype(q_ref.dtype)
        return x

    def lanes(x):
        """[R, 1] softmax statistic -> its lane-replicated scratch row."""
        return jnp.broadcast_to(x, (R, _STAT_LANES))

    def stat(ref, h):
        """Scratch row -> [R, 1] (every lane holds the same value)."""
        return jnp.max(ref[h], axis=1, keepdims=True)

    # flash accumulator state lives in VMEM scratch, one slab per kv head
    for h in range(n_kv_heads):
        if seeded:
            # the current token's contribution seeds the accumulator
            # from VMEM (always valid, needs no HBM read): a VPU row dot,
            # not a [R, Dh] x [Dh, 1] matmul
            # (the band is sliced on the REF: slicing the loaded [1, F]
            # row leaves an f32 value at lane offset 128, which Mosaic
            # then refuses to broadcast — "Invalid input layout")
            qh = q_ref[0, h].astype(jnp.float32)
            kc = newk_ref[0, :, band(h)].astype(jnp.float32)  # [1, Dh]
            vc = newv_ref[0, :, band(h)].astype(jnp.float32)
            m_ref[h] = lanes(
                jnp.sum(qh * kc, axis=1, keepdims=True) * scale)
            l_ref[h] = jnp.ones((R, _STAT_LANES), jnp.float32)
            acc_ref[h] = jnp.broadcast_to(vc, (R, d_head))
        else:
            m_ref[h] = jnp.full((R, _STAT_LANES), NEG_INF, jnp.float32)
            l_ref[h] = jnp.zeros((R, _STAT_LANES), jnp.float32)
            acc_ref[h] = jnp.zeros((R, d_head), jnp.float32)

    def get_dma(slot, p):
        phys = pt_ref[b, p]
        return (
            pltpu.make_async_copy(ck_in.at[layer, phys],
                                  kbuf.at[slot], rsem.at[slot, 0]),
            pltpu.make_async_copy(cv_in.at[layer, phys],
                                  vbuf.at[slot], rsem.at[slot, 1]),
        )

    @pl.when(first_page < n_pages)
    def _():
        k0, v0 = get_dma(0, first_page)
        k0.start()
        v0.start()

    def body(p, carry):
        slot = lax.rem(p - first_page, 2)

        @pl.when(p + 1 < n_pages)
        def _():
            kn, vn = get_dma(1 - slot, p + 1)
            kn.start()
            vn.start()

        kp, vp = get_dma(slot, p)
        kp.wait()
        vp.wait()
        kvrow = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (R, page), 1)
        valid = (kvrow <= hi) & (kvrow > below) & q_valid
        if quantized:
            # per-row page scales, [1, page]: the k scale multiplies
            # logits on the kv axis, the v scale folds into pexp
            ks_row = ks_ref[0, pl.ds(p, 1), :]
            vs_row = vs_ref[0, pl.ds(p, 1), :]
        for h in range(n_kv_heads):
            qh = q_ref[0, h]  # [R, Dh]
            kh = widen(kbuf[slot, :, band(h)])  # [page, Dh]
            logits = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [R, page]
            if quantized:
                logits = logits * ks_row
            logits = jnp.where(valid, logits, NEG_INF)
            m_prev = stat(m_ref, h)
            m_new = jnp.maximum(
                m_prev, jnp.max(logits, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pexp = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
            l_new = stat(l_ref, h) * alpha + jnp.sum(
                pexp, axis=1, keepdims=True)
            if quantized:
                pexp = pexp * vs_row
            vh = widen(vbuf[slot, :, band(h)])  # [page, Dh]
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                pexp.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = lanes(m_new)
            l_ref[h] = lanes(l_new)
        return carry

    lax.fori_loop(first_page, n_pages, body, 0)
    for h in range(n_kv_heads):
        out_ref[0, h] = (
            acc_ref[h] / jnp.maximum(stat(l_ref, h), 1e-30)
        ).astype(out_ref.dtype)


def _window_operand(window) -> jax.Array:
    """A layer's window as the kernel takes it: [1] i32, 0 = full."""
    return jnp.asarray(0 if window is None else window,
                       jnp.int32).reshape(1)


def ragged_paged_attention(
    q: jax.Array,  # [B, T, H, Dh] post-rope queries (T static; rows pad
    # their tail queries beyond q_lens — outputs there are garbage the
    # caller discards)
    cache_k: jax.Array,  # [L, n_pages, page, F] paged arena, already
    # holding this dispatch's K rows at [pos0, pos0 + q_lens) (the
    # caller scatter-appends through its write table)
    cache_v: jax.Array,
    layer: jax.Array,  # [] i32 layer index
    page_table: jax.Array,  # [B, max_pages] i32 physical pages
    pos0: jax.Array,  # [B] i32 absolute position of q[:, 0]
    q_lens: jax.Array,  # [B] i32 valid query tokens per row
    n_kv_heads: int,
    *,
    scale: float,
    page: int,
    window=None,  # the layer's sliding window, an OPERAND of the kernel:
    # an int, or an i32 scalar a layer scan carries (a model whose layers
    # differ); None or 0 = full attention
    cache_k_scale: Optional[jax.Array] = None,  # [L, n_pages, page] f32
    cache_v_scale: Optional[jax.Array] = None,
    seed_kv: Optional[tuple] = None,  # (new_k [B, F], new_v [B, F]):
    # T==1 decode mode — the current rows' EXACT values ride in VMEM and
    # their HBM copies are masked (ops/decode_attention.py contract)
) -> jax.Array:
    """Ragged attention for the whole batch in ONE kernel invocation;
    returns [B, T, H * Dh] f32."""
    B, T, H, Dh = q.shape
    L, NP, PG, F = cache_k.shape
    assert PG == page, (PG, page)
    _, max_pages = page_table.shape
    group = H // n_kv_heads
    quantized = cache_k_scale is not None
    seeded = seed_kv is not None
    if seeded:
        assert T == 1, "seed_kv is the decode (T == 1) contract"
    tq, Tp = _q_tiling(T, H, group)
    R = tq * group
    # [B, T, H, Dh] -> [B, Hkv, Tp*group, Dh], row t*group + g: a query
    # block is a contiguous row range of every kv head's slab, and the
    # kernel indexes heads on a LEADING dim (no sublane slicing)
    q3 = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0))).reshape(
        B, Tp, n_kv_heads, group, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, n_kv_heads, Tp * group, Dh)
    nsp = 5  # q_lens, pos0, layer, window, page_table
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    def _row_spec(shape):
        # one block per batch row, whole along every other dim
        return pl.BlockSpec(
            shape, lambda b, qi, *_: (b,) + (0,) * (len(shape) - 1))

    q_spec = pl.BlockSpec((1, n_kv_heads, R, Dh),
                          lambda b, qi, *_: (b, 0, qi, 0))
    operands = [q_lens, pos0, layer[None], _window_operand(window),
                page_table, q3]
    in_specs = [q_spec]
    if seeded:
        new_k, new_v = seed_kv
        operands += [new_k[:, None, :], new_v[:, None, :]]
        in_specs += [_row_spec((1, 1, F)), _row_spec((1, 1, F))]
    operands += [cache_k, cache_v]
    in_specs += [any_spec, any_spec]
    if quantized:
        # per-row scale pages gathered through the table ([B, max_pages,
        # page] — logical page p of row b lands at row p, where the
        # kernel's page walk indexes it)
        ks_g = lax.dynamic_index_in_dim(
            cache_k_scale, layer, 0, keepdims=False)[page_table]
        vs_g = lax.dynamic_index_in_dim(
            cache_v_scale, layer, 0, keepdims=False)[page_table]
        operands += [ks_g, vs_g]
        in_specs += [_row_spec((1, max_pages, page)),
                     _row_spec((1, max_pages, page))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(B, Tp // tq),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, page, F), cache_k.dtype),
            pltpu.VMEM((2, page, F), cache_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((n_kv_heads, R, _STAT_LANES), jnp.float32),  # m
            pltpu.VMEM((n_kv_heads, R, _STAT_LANES), jnp.float32),  # l
            pltpu.VMEM((n_kv_heads, R, Dh), jnp.float32),  # acc
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, scale=scale, page=page, tq=tq, group=group,
        n_kv_heads=n_kv_heads, d_head=Dh, quantized=quantized,
        seeded=seeded,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (B, n_kv_heads, Tp * group, Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
        name="ragged_paged_attention",
    )(*operands)
    # [B, Hkv, Tp*group, Dh] -> [B, T, H*Dh]
    return out.reshape(B, n_kv_heads, Tp, group, Dh).transpose(
        0, 2, 1, 3, 4).reshape(B, Tp, H * Dh)[:, :T]


def mesh_ragged_eligible(mesh, n_kv_heads: int, n_heads: int,
                         kv_dim: int) -> bool:
    """Whether the ragged kernel can run under ``shard_map`` on this
    serving mesh: kv heads split evenly over "model" (the kernel's
    per-kv-head contractions are GQA-head-local, so each shard attends
    its own whole kv-head band with full 128-lane rows).

    Unlike ``decode_attention.mesh_kernel_eligible`` there is NO
    slots-divide-"data" requirement: the page arena has no slot dim, so
    batch rows and the arena replicate over "data"/"seq" shards —
    redundant compute per step, never incorrect (ADVICE r3 #4)."""
    tp = mesh.shape.get("model", 1)
    return (
        n_kv_heads % tp == 0
        and n_heads % tp == 0
        and (kv_dim // tp) % 128 == 0
    )


def sharded_ragged_append_attend(
    mesh,
    q: jax.Array,  # [B, T, H, Dh] post-rope queries
    new_k: jax.Array,  # [B, T, F] post-rope K rows (bf16/f32; T == 1
    new_v: jax.Array,  # rows also seed the kernel accumulator)
    kq: jax.Array,  # [B, T, F] rows to SCATTER (int8 when quantized,
    vq: jax.Array,  # else the rows themselves)
    ksc: Optional[jax.Array],  # [B, T] f32 per-row scales (GLOBAL amax —
    vsc: Optional[jax.Array],  # see note below), None when unquantized
    cache_k: jax.Array,  # [L, n_pages, page, F] paged arena
    cache_v: jax.Array,
    cache_k_scale: Optional[jax.Array],  # [L, n_pages, page] f32 | None
    cache_v_scale: Optional[jax.Array],
    layer: jax.Array,  # [] i32
    page_table: jax.Array,  # [B, max_pages] i32 READ pages
    write_table: jax.Array,  # [B, max_pages] i32 WRITE pages (non-owned
    # entries point at the trash page)
    pos0: jax.Array,  # [B] i32
    q_lens: jax.Array,  # [B] i32 ragged valid-token counts
    n_kv_heads: int,
    *,
    scale: float,
    page: int,
    window=None,  # as ragged_paged_attention's
) -> tuple:
    """Table-scatter append + ragged attend under ``shard_map`` on a
    serving mesh — the meshed counterpart of the caller-side scatter +
    ``ragged_paged_attention`` pair in models/transformer.ragged_attn.
    The arena shards its head-flat F dim over "model"
    (parallel/sharding.PAGED_KV_SPEC): each device holds its kv-head
    slice of EVERY page, the host-owned int32 page tables stay global,
    and each model shard runs the kernel over its own kv-head band with
    ZERO collectives inside the body. Batch rows and the arena replicate
    over "data"/"seq" (the arena has no slot dim to shard).

    The caller must quantize rows with the GLOBAL per-row amax (computed
    outside, where GSPMD reduces across model shards): every model shard
    then scatters identical values into the model-replicated scale
    planes, keeping them consistent — same contract as
    ``decode_attention.sharded_append_attend``.

    Returns (out [B, T, H*Dh] sharded over "model", ck, cv[, ks, vs]).
    """
    from ..parallel.sharding import (
        PAGED_KV_SPEC, RAGGED_Q_SPEC, RAGGED_ROW_SPEC, REPLICATED,
    )

    tp = mesh.shape.get("model", 1)
    quant = cache_k_scale is not None
    n_kv_local = n_kv_heads // tp

    row_spec = RAGGED_ROW_SPEC  # [B, T, F] rows
    arena_spec = PAGED_KV_SPEC
    rep = REPLICATED  # tables, scalars, per-row + per-plane scales

    in_specs = [
        RAGGED_Q_SPEC,  # q: heads over "model"
        row_spec, row_spec,  # new_k, new_v
        row_spec, row_spec,  # kq, vq
        arena_spec, arena_spec,  # cache_k, cache_v
        rep, rep, rep, rep, rep, rep,  # layer, window, pt, wt, pos0,
        # q_lens
    ]
    operands = [q, new_k, new_v, kq, vq, cache_k, cache_v,
                layer, _window_operand(window), page_table, write_table,
                pos0, q_lens]
    if quant:
        in_specs += [rep, rep, rep, rep]
        operands += [ksc, vsc, cache_k_scale, cache_v_scale]
        out_specs = (row_spec, arena_spec, arena_spec, rep, rep)
    else:
        out_specs = (row_spec, arena_spec, arena_spec)

    def body(q_l, nk_l, nv_l, kq_l, vq_l, ck, cv, lay, win, pt, wt, p0,
             qls, ksr=None, vsr=None, ksp=None, vsp=None):
        B, T = kq_l.shape[:2]
        rows = jnp.arange(B, dtype=jnp.int32)
        tpos = p0[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        wpg = wt[rows[:, None], tpos // page]
        # pad positions beyond the row's ragged length write trash
        wpg = jnp.where(
            jnp.arange(T, dtype=jnp.int32)[None] < qls[:, None], wpg, 0)
        woff = tpos % page
        ck = ck.at[lay, wpg, woff, :].set(
            kq_l.astype(ck.dtype), mode="promise_in_bounds")
        cv = cv.at[lay, wpg, woff, :].set(
            vq_l.astype(cv.dtype), mode="promise_in_bounds")
        if quant:
            ksp = ksp.at[lay, wpg, woff].set(
                ksr, mode="promise_in_bounds")
            vsp = vsp.at[lay, wpg, woff].set(
                vsr, mode="promise_in_bounds")
        seed = (nk_l[:, 0], nv_l[:, 0]) if T == 1 else None
        out = ragged_paged_attention(
            q_l, ck, cv, lay, pt, p0, qls, n_kv_local,
            scale=scale, page=page, window=win[0],
            cache_k_scale=ksp if quant else None,
            cache_v_scale=vsp if quant else None,
            seed_kv=seed,
        )
        if quant:
            return out, ck, cv, ksp, vsp
        return out, ck, cv

    # check_vma=False: the model-replicated scale planes are updated with
    # identical values on every model shard (global-amax quantization), a
    # replication invariant shard_map cannot verify itself
    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=False,
    )(*operands)


def ragged_attention_reference(
    q, cache_k, cache_v, layer, page_table, pos0, q_lens, n_kv_heads,
    *, scale, page, window=None, cache_k_scale=None,
    cache_v_scale=None, seed_kv=None,
) -> jax.Array:
    """Dense XLA oracle: gather each row's pages into a contiguous
    window, dequantize, and run masked softmax attention. Used by
    ops/kernel_check.py (and tests) to validate the kernel; the engine's
    own XLA fallback is the gather_kv_pages serving path, which computes
    the same values through models.transformer._attend."""
    B, T, H, Dh = q.shape
    W = page_table.shape[1] * page
    k = cache_k[layer][page_table].reshape(B, W, -1)
    v = cache_v[layer][page_table].reshape(B, W, -1)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    if cache_k_scale is not None:
        ks = cache_k_scale[layer][page_table].reshape(B, W)
        vs = cache_v_scale[layer][page_table].reshape(B, W)
        k = k * ks[..., None]
        v = v * vs[..., None]
    if seed_kv is not None:
        assert T == 1
        rows = jnp.arange(B)
        k = k.at[rows, jnp.maximum(pos0, 0)].set(
            seed_kv[0].astype(jnp.float32))
        v = v.at[rows, jnp.maximum(pos0, 0)].set(
            seed_kv[1].astype(jnp.float32))
    group = H // n_kv_heads
    kh = k.reshape(B, W, n_kv_heads, Dh)[:, :, jnp.arange(H) // group, :]
    vh = v.reshape(B, W, n_kv_heads, Dh)[:, :, jnp.arange(H) // group, :]
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), kh,
                        precision=lax.Precision.HIGHEST) * scale
    kv_pos = jnp.arange(W)[None, None, None, :]
    qpos = (pos0[:, None] + jnp.arange(T)[None, :])[:, None, :, None]
    mask = (kv_pos <= qpos) & (
        jnp.arange(T)[None, None, :, None] < q_lens[:, None, None, None])
    if window is not None:  # an int or a traced scalar; 0 = full
        mask &= (jnp.asarray(window) <= 0) | (kv_pos > qpos - window)
    logits = jnp.where(mask, logits, NEG_INF)
    # fully-masked pad queries: keep softmax finite, zero the output
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(mask.any(-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhts,bshd->bthd", probs, vh,
                     precision=lax.Precision.HIGHEST)
    return out.reshape(B, T, H * Dh)
