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
- grid = (row, query block), run IN ORDER on one core
  (``dimension_semantics`` says so): a step walks only the pages its
  block's queries can see (a grid over max_seq pages pays a fixed
  per-page cost, valid or not). Query blocking (``_q_tiling``) bounds
  the per-step VMEM footprint — a 2048-token chunk never holds an
  [n_heads * 2048, page] logits slab — and a block wholly beyond a
  row's q_len, a row of length 0 (a parked row: the caller passes 0 for
  a row that carries no stream) and a seeded row at position 0 read
  nothing and cost a bare grid step (0.4 us on a v5e).
- ONE page walk for the whole call, two page slots deep: while a page
  is computed the next one is in flight, and from a step's last page on
  "the next one" is the FIRST page of the next step that reads (found
  on the scalar core from the prefetched lengths and positions). The
  slot that page lands in is handed over in SMEM scratch, which
  persists across grid steps; every DMA that is started is waited for
  exactly once, by the step that reads it. Row by row, each row paid
  its first page's issue + latency + transfer in the open: 0.9 us of a
  5.4 us row at Mistral's 2.35 pages a row (PERF.md section 6, PR 39).
- within a page the kv heads go through each phase together (every QK
  matmul, every softmax update, every PV matmul, the stores), as many
  heads at a time as keep their logits in half the vector registers
  (``_PHASE_VREGS``). The MXU takes its matmuls in program order: head
  after head, the heads' matmul -> reduce -> exp -> matmul chains ran
  end to end, 1.72 us a page against 0.82 for 8 kv heads of int8 (the
  page pair's DMA alone: 0.64 us).
- queries ride as ``[B, Hkv, T*group, Dh]``: the kernel picks a kv head
  on a LEADING dim and contracts ``q_h [TQ*group, Dh] @ k_page_h.T`` on
  the MXU; the per-head k/v bands are 128-lane-aligned column slices of
  the head-flat page. No sublane slicing or stacking anywhere, so the
  T == 1 / group 4 decode case lays out as whole tiles.
- flash state (m, l, acc) lives in VMEM scratch, one slab per kv head,
  m/l lane-replicated so every load/store is a full vreg (reading a
  statistic back as one lane column instead of a lane reduction was
  measured SLOWER: 2.34 us a page against 1.72).
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
# q/out blocks — about 10 MiB. Chosen in PR 22 so that a 2048-token
# chunk compiles, and not tuned since: it does not reach decode rows
# (T == 1 rides as ONE 16-row tile a kv head whatever this says), which
# is where the cells' time is.
_ROWS_PER_STEP = 2048
_STAT_LANES = 128  # m/l scratch rows are lane-replicated (full vregs)
# f32 vregs (8 x 128) of logits that one phase of the page loop keeps
# live: half of the core's 64
_PHASE_VREGS = 32
# Room, not a tuned number: every serving shape (decode, [4, 128],
# [16, 512], a [1, 2048] chunk; int8 / bf16 / f32 pages, 8 and 4 kv
# heads) also compiles for a v5e at 12 MiB (PR 39, compile only); the
# default scoped limit is 16 MiB of the core's 128.
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
                   seeded: bool, v_lanes: int = 0):
    qlen_ref, pos_ref, layer_ref, win_ref, pt_ref, q_ref, *rest = refs
    if seeded:
        newk_ref, newv_ref, *rest = rest
    if v_lanes:
        # a latent arena: ONE plane, whose page is the key (all d_head
        # lanes) and, in its first v_lanes lanes, the value
        ck_in, out_ref, kbuf, rsem, hand_ref, m_ref, l_ref, acc_ref = rest
        cv_in, vbuf = None, kbuf
    else:
        ck_in, cv_in, *rest = rest
        if quantized:
            ks_ref, vs_ref, *rest = rest
        out_ref, kbuf, vbuf, rsem, hand_ref, m_ref, l_ref, acc_ref = rest
    d_v = v_lanes or d_head  # lanes of a head's value and output
    b = pl.program_id(0)
    nq = pl.num_programs(1)
    step = b * nq + pl.program_id(1)  # the grid runs in this order
    n_steps = pl.num_programs(0) * nq
    t0 = pl.program_id(1) * tq  # first query token of this block
    layer = layer_ref[0]
    window = win_ref[0]  # this layer's sliding window; 0 = full attention
    qlen = qlen_ref[b]
    p0 = pos_ref[b]
    R = tq * group  # query rows per kv head: row = t_local*group + g
    # heads whose [R, page] f32 logits are live at once (see the page
    # loop): all of them for decode rows (4 vregs a head), one for a
    # 64-token prompt block (64 vregs a head)
    heads_per_phase = max(1, min(
        n_kv_heads, _PHASE_VREGS // max(1, R * page // 1024)))
    # absolute position of each query row of the block
    row_i = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    t_i = t0 + row_i // group
    qpos = p0 + t_i  # [R, 1]
    q_valid = t_i < qlen  # pad queries beyond the row's ragged length
    hi = qpos - (1 if seeded else 0)  # last HBM row each query attends
    # the last position BELOW each query's window (none: -1)
    below = jnp.where(window > 0, qpos - window, -1)  # [R, 1]

    def pages_of(s):
        """[first, end) logical pages grid step ``s`` reads: through its
        block's LAST valid query (causal), from the page its EARLIEST
        query's window reaches (the per-query mask handles the ragged
        boundaries); nothing (end <= first) for a row of length 0 and
        for a block wholly beyond q_len. Seeded mode keeps the current
        token in VMEM and masks its HBM copy (the decode kernel's
        contract), so a seeded row at position 0 reads nothing either."""
        row, blk0 = lax.div(s, nq), lax.rem(s, nq) * tq
        pos = pos_ref[row]
        t_end = jnp.minimum(qlen_ref[row], blk0 + tq)
        n_hbm = pos + t_end - (1 if seeded else 0)
        end = jnp.where(t_end > blk0, lax.div(n_hbm + page - 1, page), 0)
        first = jnp.where(
            window > 0,
            lax.div(jnp.maximum(pos + blk0 + 1 - window, 0), page), 0)
        return row, first, end

    _, first_page, n_pages = pages_of(step)
    reads = first_page < n_pages

    # the walk crosses grid steps: the step that follows this one AND
    # reads a page (parked rows and blocks beyond q_len in between read
    # none), found on the scalar core; a step that reads nothing looks
    # for nothing and leaves the hand-over as it found it
    def seek(c):
        s, _, _, _ = c
        row, first, end = pages_of(s)
        hit = first < end
        return jnp.where(hit, s, s + 1), row, first, ~hit

    nxt, nxt_row, nxt_first, _ = lax.while_loop(
        lambda c: c[3] & (c[0] < n_steps), seek,
        (step + 1, jnp.int32(0), jnp.int32(0), reads))
    has_next = reads & (nxt < n_steps)

    def band(h):
        return slice(h * d_head, (h + 1) * d_head)

    def vband(h):
        return slice(0, v_lanes) if v_lanes else band(h)

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
            acc_ref[h] = jnp.zeros((R, d_v), jnp.float32)

    def get_dma(slot, row, p):
        phys = pt_ref[row, p]
        k_dma = pltpu.make_async_copy(ck_in.at[layer, phys],
                                      kbuf.at[slot], rsem.at[slot, 0])
        if v_lanes:
            return (k_dma,)
        return (
            k_dma,
            pltpu.make_async_copy(cv_in.at[layer, phys],
                                  vbuf.at[slot], rsem.at[slot, 1]),
        )

    def start(slot, row, p):
        for dma in get_dma(slot, row, p):
            dma.start()

    # hand_ref (SMEM scratch, kept from one grid step to the next): the
    # slot in which the FIRST page of the next step that reads is in
    # flight, started by the reading step before it; -1 = none
    @pl.when(step == 0)
    def _():
        hand_ref[0] = -1

    handed = hand_ref[0] >= 0
    slot0 = jnp.maximum(hand_ref[0], 0)

    @pl.when(reads & ~handed)
    def _():  # the call's first reading step: nobody fetched for it
        start(0, b, first_page)

    def body(p, carry):
        slot = lax.rem(slot0 + p - first_page, 2)
        last = p + 1 == n_pages

        # the other slot's page was consumed one trip ago: fill it with
        # this step's next page or, from its last page on, with the next
        # reading step's first — that fetch runs under this page's
        # compute, the output's write-back and the next step's prologue
        @pl.when(~last | has_next)
        def _():
            start(1 - slot, jnp.where(last, nxt_row, b),
                  jnp.where(last, nxt_first, p + 1))

        for dma in get_dma(slot, b, p):
            dma.wait()
        kvrow = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (R, page), 1)
        valid = (kvrow <= hi) & (kvrow > below) & q_valid
        if quantized:
            # per-row page scales, [1, page]: the k scale multiplies
            # logits on the kv axis, the v scale folds into pexp
            ks_row = ks_ref[0, pl.ds(p, 1), :]
            vs_row = vs_ref[0, pl.ds(p, 1), :]
        # the heads go through each phase TOGETHER, a block of heads at
        # a time: every QK matmul, then every softmax update, then
        # every PV matmul. The MXU runs its matmuls in program order:
        # written head after head, a head's PV waits for its softmax
        # with the next head's QK queued behind it (design notes)
        for h0 in range(0, n_kv_heads, heads_per_phase):
            hs = range(h0, min(h0 + heads_per_phase, n_kv_heads))
            logits, m_new, alpha, l_new, pexp, pv = ({} for _ in range(6))
            for h in hs:
                kh = widen(kbuf[slot, :, band(h)])  # [page, Dh]
                logits[h] = jax.lax.dot_general(
                    q_ref[0, h], kh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [R, page]
                if quantized:
                    logits[h] = logits[h] * ks_row
                logits[h] = jnp.where(valid, logits[h], NEG_INF)
            for h in hs:
                m_prev = stat(m_ref, h)
                m_new[h] = jnp.maximum(
                    m_prev, jnp.max(logits[h], axis=1, keepdims=True))
                alpha[h] = jnp.exp(m_prev - m_new[h])
                pexp[h] = jnp.where(
                    valid, jnp.exp(logits[h] - m_new[h]), 0.0)
                l_new[h] = stat(l_ref, h) * alpha[h] + jnp.sum(
                    pexp[h], axis=1, keepdims=True)
                if quantized:
                    pexp[h] = pexp[h] * vs_row
            for h in hs:
                vh = widen(vbuf[slot, :, vband(h)])  # [page, Dh]
                pv[h] = jax.lax.dot_general(
                    pexp[h].astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            for h in hs:
                acc_ref[h] = acc_ref[h] * alpha[h] + pv[h]
                m_ref[h] = lanes(m_new[h])
                l_ref[h] = lanes(l_new[h])
        return carry

    lax.fori_loop(first_page, n_pages, body, 0)

    @pl.when(reads)
    def _():  # every started DMA is waited for once: by this step's
        # walk, or by the step this hands the last one over to
        hand_ref[0] = jnp.where(
            has_next, lax.rem(slot0 + n_pages - first_page, 2), -1)

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
    # holding this dispatch's K rows at [pos0, pos0 + q_lens)
    # (``append_rows``)
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
    v_lanes: int = 0,  # > 0: a LATENT arena (absorbed latent attention,
    # models/cache_attention.py ``latent_ragged``): ``cache_v`` is None,
    # n_kv_heads is 1, F == Dh is the whole cached row [c | k_r | pad]
    # and a page's first ``v_lanes`` lanes are also its value — one
    # plane is walked, each page fetched ONCE for both matmuls. The
    # call is named ``latent_paged_attention`` in a capture
) -> jax.Array:
    """Ragged attention for the whole batch in ONE kernel invocation;
    returns [B, T, H * Dh] f32 ([B, T, H * v_lanes] for a latent
    arena)."""
    B, T, H, Dh = q.shape
    L, NP, PG, F = cache_k.shape
    assert PG == page, (PG, page)
    if v_lanes:
        assert cache_v is None and n_kv_heads == 1 and F == Dh \
            and v_lanes % 128 == 0 and cache_k_scale is None \
            and seed_kv is None, "latent arena: one bf16 plane, no seed"
    Dv = v_lanes or Dh
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
    operands += [cache_k] if v_lanes else [cache_k, cache_v]
    in_specs += [any_spec] if v_lanes else [any_spec, any_spec]
    if quantized:
        # per-row scale pages gathered through the table ([B, max_pages,
        # page] — logical page p of row b lands at row p, where the
        # kernel's page walk indexes it)
        ks_g = cache_k_scale[layer, page_table]
        vs_g = cache_v_scale[layer, page_table]
        operands += [ks_g, vs_g]
        in_specs += [_row_spec((1, max_pages, page)),
                     _row_spec((1, max_pages, page))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(B, Tp // tq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_kv_heads, R, Dv),
                               lambda b, qi, *_: (b, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page, F), cache_k.dtype),
            *(() if v_lanes else (
                pltpu.VMEM((2, page, F), cache_v.dtype),)),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),  # the cross-step hand-over
            pltpu.VMEM((n_kv_heads, R, _STAT_LANES), jnp.float32),  # m
            pltpu.VMEM((n_kv_heads, R, _STAT_LANES), jnp.float32),  # l
            pltpu.VMEM((n_kv_heads, R, Dv), jnp.float32),  # acc
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, scale=scale, page=page, tq=tq, group=group,
        n_kv_heads=n_kv_heads, d_head=Dh, quantized=quantized,
        seeded=seeded, v_lanes=v_lanes,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (B, n_kv_heads, Tp * group, Dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # one page walk from the first grid step to the last: a
            # step waits for the DMA the step before it started, so the
            # grid must run in order on one core
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
        name=("latent_paged_attention" if v_lanes
              else "ragged_paged_attention"),
    )(*operands)
    # [B, Hkv, Tp*group, Dh] -> [B, T, H*Dh]
    return out.reshape(B, n_kv_heads, Tp, group, Dv).transpose(
        0, 2, 1, 3, 4).reshape(B, Tp, H * Dv)[:, :T]


def append_rows(planes: tuple, values: tuple, layer: jax.Array,
                write_table: jax.Array, pos0: jax.Array,
                q_lens: jax.Array, page: int) -> tuple:
    """A dispatch's rows scattered into the arena through its WRITE
    table — what ``ragged_paged_attention`` then reads through the READ
    table. ``planes``: arena planes [L, n_pages, page(, F)] (K and V
    rows, their scale planes, or a latent arena's one plane);
    ``values``: one [B, T(, F)] array a plane, token t of row b at
    position ``pos0[b] + t``, which lands on page ``write_table[b,
    position // page]`` of layer ``layer``. Positions beyond a row's
    ``q_lens`` go to the trash page (page 0), as do the pages the host
    did not grant — the table already points those at it. -> the
    planes, written."""
    B, T = values[0].shape[:2]
    rows = jnp.arange(B, dtype=jnp.int32)
    tpos = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    wpg = write_table[rows[:, None], tpos // page]
    wpg = jnp.where(
        jnp.arange(T, dtype=jnp.int32)[None] < q_lens[:, None], wpg, 0)
    woff = tpos % page
    return tuple(
        p.at[layer, wpg, woff].set(new.astype(p.dtype),
                                   mode="promise_in_bounds")
        for p, new in zip(planes, values))


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
    serving mesh — the meshed counterpart of the ``append_rows`` +
    ``ragged_paged_attention`` pair in models/cache_attention.py
    ``ragged``.
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
        planes = append_rows(
            (ck, cv, ksp, vsp) if quant else (ck, cv),
            (kq_l, vq_l, ksr, vsr), lay, wt, p0, qls, page)
        ksp, vsp = planes[2:] if quant else (None, None)
        seed = (nk_l[:, 0], nv_l[:, 0]) if kq_l.shape[1] == 1 else None
        out = ragged_paged_attention(
            q_l, planes[0], planes[1], lay, pt, p0, qls, n_kv_local,
            scale=scale, page=page, window=win[0],
            cache_k_scale=ksp, cache_v_scale=vsp, seed_kv=seed,
        )
        return (out, *planes)

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
