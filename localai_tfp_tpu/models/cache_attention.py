"""A layer's attention over the KV cache: one function per way to it.

    layer body  ->  route(ctx, g, st, q, k, v)  ->  the cache planes
    (transformer._layer_body)   one of five      (KVCache's arrays)

``select`` picks the route ONCE for a forward pass, from the groups of
rows the engine's cache route handed over (engine/cache_route.py names
the same decision ``attention_path``), and says whether the route
addresses the stacked ``[L, ...]`` planes in place. Every route has one
signature:

    route(ctx, g, st, q, k, v) -> (attn [B, T, H * Dh], planes written)

``ctx`` (``LayerCtx``) is what the route needs of the layer step it
runs in; ``g`` one rectangle of rows (``transformer.Rows``); ``st`` the
cache's planes — (k, v), and (k_scale, v_scale) after them where the
rows are int8 — as the group before left them, stacked for a route
that addresses them in place, this layer's slices otherwise; q [B, T,
H, Dh], k and v [B, T, Hkv, Dh] after rotary. The latent pair takes
(q_n, q_r, row) as ``transformer._latent_mixer`` calls it. What comes
back beside the attention is every plane the route wrote, in the
planes' order.

The contractions (``_attend``, the latent forms) and the K/V row
quantisation live here too; the table scatter is
``ops/ragged_paged_attention.append_rows``, beside the kernel that
reads what it wrote.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax


class LayerCtx(NamedTuple):
    """What a route needs of the layer step it runs in; the scan body of
    ``transformer.forward_rows`` builds one a step."""

    spec: Any  # LLMSpec
    l: jax.Array  # [] i32: the layer's index in the cache planes
    li: jax.Array  # [] i32: its index in its stack
    lp: dict  # its leaves
    stack: dict  # the stack's scanned leaves WHOLE, [n, ...]: the
    # expanded latent kernel indexes W_kvb in them
    window: Any  # the layer's sliding window for the kernels: a scalar
    # riding the scan (0 = full attention), or the spec's own number
    kv_page: int  # pool page size (tokens) on the ragged routes
    mesh: Any
    quant: bool  # int8 rows + per-row scale planes
    ring_prefill: bool
    dtype: Any  # the activations'
    positions: Optional[jax.Array]  # [B, T] of the ONE group; None
    # with more groups (each derives its own, ``positions_of``)


# ---------------------------------------------------------------------------
# groups of rows
# ---------------------------------------------------------------------------


def flatten(parts: list) -> jax.Array:
    """Each group's [B, T, ...] rectangle side by side as one flat
    [1, sum(B * T), ...] batch; ONE group stays the rectangle it is."""
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(
        [a.reshape(1, a.shape[0] * a.shape[1], *a.shape[2:])
         for a in parts], axis=1)


def ungroup(groups: tuple, a: jax.Array) -> list:
    """``flatten``'s inverse: [1, N, ...] back into each group's
    [B, T, ...] rectangle."""
    if len(groups) == 1:
        return [a]
    out, lo = [], 0
    for g in groups:
        b, t = g.tokens.shape
        out.append(a[0, lo:lo + b * t].reshape(b, t, *a.shape[2:]))
        lo += b * t
    return out


def positions_of(g) -> jax.Array:
    """[B, T] absolute positions of a group's tokens."""
    return g.pos0[:, None] + jnp.arange(
        g.tokens.shape[1], dtype=jnp.int32)[None, :]


def valid_of(g) -> jax.Array:
    """[B, T] bool: the positions of a group that carry a token —
    within the row's ragged length, on a live row."""
    b, t = g.tokens.shape
    ok = jnp.ones((b, t), bool)
    if g.q_lens is not None:
        ok &= jnp.arange(t, dtype=jnp.int32)[None] < g.q_lens[:, None]
    if g.live is not None:
        ok &= g.live[:, None]
    return ok


def _positions(ctx, g) -> jax.Array:
    return positions_of(g) if ctx.positions is None else ctx.positions


def _attend_lens(g) -> jax.Array:
    """The lengths the ragged kernels attend at. A parked row attends
    nothing: at length 0 the kernel walks none of the pages under the
    position it carries (its K/V rows go to the trash page either way)."""
    return g.q_lens if g.live is None else jnp.where(g.live, g.q_lens, 0)


# ---------------------------------------------------------------------------
# the contractions
# ---------------------------------------------------------------------------


def softmax_scale(spec) -> float:
    return 1.0 / math.sqrt(spec.query_pre_attn_scalar or spec.d_head)


def _quantize_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., F] -> (int8 rows, per-row f32 scales)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def quantize_kv(k: jax.Array, v: jax.Array, quant: bool) -> tuple:
    """The rows a cache stores for head-flat K/V rows [..., F]:
    (k, v, k scales, v scales) — int8 rows beside per-row f32 scales
    for an int8 cache, the rows themselves and no scales otherwise."""
    if not quant:
        return k, v, None, None
    kq, ksc = _quantize_rows(k)
    vq, vsc = _quantize_rows(v)
    return kq, vq, ksc, vsc


def _prec(x):
    # bf16 operands ride the MXU natively; fp32 operands (tests) must not be
    # silently truncated to bf16, hence HIGHEST. Accumulation is fp32 either
    # way via preferred_element_type — flash-attention-style numerics.
    return (lax.Precision.HIGHEST if x.dtype == jnp.float32
            else lax.Precision.DEFAULT)


def _attend(
    spec,
    q: jax.Array,  # [B, T, H, Dh]
    k: jax.Array,  # [B, S, Hkv, Dh]
    v: jax.Array,  # [B, S, Hkv, Dh]
    q_pos: jax.Array,  # [B, T] absolute positions of queries
    window: Optional[jax.Array] = None,  # per-layer scalar; 0/neg = full
    # (gemma2 alternates sliding/global layers — traced through the scan)
) -> jax.Array:
    B, T, H, Dh = q.shape
    S = k.shape[1]
    group = H // spec.n_kv_heads
    prec = _prec(q)
    qg = q.reshape(B, T, spec.n_kv_heads, group, Dh)
    logits = jnp.einsum(
        "btkgd,bskd->bktgs", qg, k,
        preferred_element_type=jnp.float32, precision=prec,
    ) * softmax_scale(spec)  # [B, Hkv, T, group, S]
    if spec.attn_logit_softcap:
        cap = spec.attn_logit_softcap
        logits = jnp.tanh(logits / cap) * cap
    kv_pos = lax.broadcasted_iota(jnp.int32, (1, 1, 1, 1, S), 4)
    qp = q_pos[:, None, :, None, None]  # [B,1,T,1,1]
    mask = kv_pos <= qp
    if window is not None:
        mask &= (window <= 0) | (kv_pos > qp - window)
    elif spec.sliding_window and not spec.sliding_window_pattern:
        mask &= kv_pos > qp - spec.sliding_window
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bktgs,bskd->btkgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32, precision=prec,
    )
    return out.reshape(B, T, H * Dh).astype(q.dtype)


def latent_scale(spec) -> float:
    """Softmax scale of latent attention: (d_n + d_r)^-1/2, times the
    YaRN mscale^2 where the model puts it there."""
    return spec.attn_scale_mult / math.sqrt(spec.d_head)


def latent_attend_expanded(spec, lp, qn, qr, rows, q_pos):
    """The EXPANDED form: cached rows [B, S, latent_row] up-projected
    through W_kvb into every head's k_n [S, H, d_n] and v [S, H, d_v],
    then causal attention at (d_n + d_r) x d_v a head — what the
    published description computes, and the engine's XLA route.
    q_pos [B, T]: the queries' absolute positions. -> [B, T, H * d_v]."""
    r, dr = spec.kv_lora_rank, spec.qk_rope_dim
    B, T = qn.shape[0], qn.shape[1]
    c, kr = rows[..., :r], rows[..., r:r + dr]
    prec = _prec(qn)
    kn = jnp.einsum("bsc,hnc->bshn", c, lp["wkv_b_k"], precision=prec)
    v = jnp.einsum("bsc,hcv->bshv", c, lp["wkv_b_v"], precision=prec)
    logits = (jnp.einsum("bthn,bshn->bhts", qn, kn, precision=prec,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthr,bsr->bhts", qr, kr, precision=prec,
                           preferred_element_type=jnp.float32)
              ) * latent_scale(spec)
    kv_pos = lax.broadcasted_iota(jnp.int32, (1, 1, 1, rows.shape[1]), 3)
    logits = jnp.where(kv_pos <= q_pos[:, None, :, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshv->bthv", probs.astype(v.dtype), v,
                     precision=prec, preferred_element_type=jnp.float32)
    return out.reshape(B, T, -1).astype(qn.dtype)


def latent_absorb_query(spec, lp, qn, qr):
    """The ABSORBED form's query: q~_h = W_kvb,k,h^T q_n,h beside q_r
    and the row's zero lanes -> [B, T, H, latent_row]; its score against
    a cached row is the expanded form's score."""
    qa = jnp.einsum("bthn,hnc->bthc", qn, lp["wkv_b_k"],
                    precision=_prec(qn)).astype(qn.dtype)
    pad = spec.latent_row - spec.latent_width
    return jnp.concatenate(
        [qa, qr, jnp.zeros((*qr.shape[:3], pad), qr.dtype)], axis=-1)


def latent_absorb_out(spec, lp, ctx, dtype):
    """The absorbed form's output: sum_s p_s c_s [B, T, H, r] through
    each head's W_kvb,v -> [B, T, H * d_v]."""
    out = jnp.einsum("bthc,hcv->bthv", ctx.astype(dtype), lp["wkv_b_v"],
                     precision=_prec(lp["wkv_b_v"]),
                     preferred_element_type=jnp.float32)
    return out.reshape(*out.shape[:2], -1).astype(dtype)


# ---------------------------------------------------------------------------
# the five routes
# ---------------------------------------------------------------------------


def ragged(ctx, g, st, q, k, v):
    """``ragged_paged_kernel``: the chunk's K/V rows scatter into the
    arena through the WRITE table (``append_rows``: positions beyond a
    row's q_len go to the trash page, as do pages the host did not
    grant), then ONE kernel invocation attends every row kind — decode
    rows, prefill chunks, spec-verify rows — walking pages through the
    READ table (ops/ragged_paged_attention.py). No gathered window view
    is ever materialized. T == 1 keeps the decode kernel's VMEM-seeded
    current-row contract (an int8 cache attends the EXACT current row,
    not its quantized HBM copy)."""
    from ..ops.ragged_paged_attention import (
        append_rows, ragged_paged_attention, sharded_ragged_append_attend,
    )

    spec, quant = ctx.spec, ctx.quant
    attend = _attend_lens(g)
    B, T = k.shape[0], k.shape[1]
    kf = k.reshape(B, T, spec.kv_dim)
    vf = v.reshape(B, T, spec.kv_dim)
    new = quantize_kv(kf, vf, quant)
    scale = softmax_scale(spec)
    if ctx.mesh is not None:
        # meshed serving: table-scatter append + ragged attend
        # per-shard under shard_map — the arena's head-flat F dim is
        # sharded over "model" (PAGED_KV_SPEC) and the quantization
        # above already ran OUTSIDE (global per-row amax), so every
        # model shard scatters identical scale values
        # (sharded_append_attend's contract, extended to the paged
        # arena)
        res = sharded_ragged_append_attend(
            ctx.mesh, q, kf, vf, *new, *st, *(() if quant else (None, None)),
            ctx.l, g.page_table, g.write_table, g.pos0, attend,
            spec.n_kv_heads, scale=scale, page=ctx.kv_page,
            window=ctx.window)
        return res[0].astype(ctx.dtype), tuple(res[1:])
    planes = append_rows(st, new[:len(st)], ctx.l, g.write_table, g.pos0,
                         g.q_lens, ctx.kv_page)
    ks_new, vs_new = planes[2:] if quant else (None, None)
    out = ragged_paged_attention(
        q, planes[0], planes[1], ctx.l, g.page_table, g.pos0, attend,
        spec.n_kv_heads, scale=scale, page=ctx.kv_page, window=ctx.window,
        cache_k_scale=ks_new, cache_v_scale=vs_new,
        seed_kv=(kf[:, 0], vf[:, 0]) if T == 1 else None,
    )  # [B, T, H*Dh]
    return out.astype(ctx.dtype), planes


def dense_kernel(ctx, g, st, q, k, v):
    """``dense_decode_kernel`` (one token a row, row b IS slot b): the
    current K/V rows are appended via an in-place scatter on the
    scan-CARRIED full cache (XLA keeps carry scatters in place; single
    bf16 rows cannot be DMA'd into the tiled HBM buffer from inside a
    kernel), then one read-only kernel attends over each slot's VALID
    pages only (ragged reads — the decode bandwidth win;
    ops/decode_attention.py). int8 caches scatter quantized rows +
    per-row scales; the kernel dequantizes per page in VMEM (the bytes
    stay halved)."""
    from ..ops.decode_attention import (
        fused_decode_attention, sharded_append_attend,
    )

    spec, quant, l = ctx.spec, ctx.quant, ctx.l
    pos0, B = g.pos0, k.shape[0]
    kf = k.reshape(B, spec.kv_dim)
    vf = v.reshape(B, spec.kv_dim)
    rows = jnp.arange(B, dtype=jnp.int32)
    new = quantize_kv(kf, vf, quant)  # rows [B, F], scales f32 [B]
    scale = softmax_scale(spec)
    if ctx.mesh is not None:
        # meshed serving: append + attend per-shard under shard_map —
        # the quantization above already ran OUTSIDE (global per-row
        # amax), so every model shard scatters identical scale values
        # (VERDICT r2 weak #5)
        res = sharded_append_attend(
            ctx.mesh, q[:, 0], kf, vf, *new, *st,
            *(() if quant else (None, None)),
            l, pos0, spec.n_kv_heads, scale=scale, window=ctx.window)
        return res[0][:, None, :].astype(ctx.dtype), tuple(res[1:])
    planes = tuple(
        p.at[l, rows, pos0].set(row.astype(p.dtype),
                                mode="promise_in_bounds")
        for p, row in zip(st, new))
    ks_new, vs_new = planes[2:] if quant else (None, None)
    out = fused_decode_attention(
        q[:, 0], kf, vf, planes[0], planes[1], l, pos0 + 1,
        spec.n_kv_heads, scale=scale, window=ctx.window,
        cache_k_scale=ks_new, cache_v_scale=vs_new,
    )
    return out[:, None, :].astype(ctx.dtype), planes


def _write_row(buf_row, new_row, off):
    return lax.dynamic_update_slice(
        buf_row, new_row.astype(buf_row.dtype), (off, 0))


def _write_scale(srow, val, off):
    return lax.dynamic_update_slice(srow, val, (off,))


def kv_from_cache(ctx, g, st, k, v, raw=False):
    """The XLA routes' cache-write half: this chunk's K/V rows written
    into the layer's slices ``st`` at rows ``g.slot_ids`` (None: batch
    row b IS cache row b), columns ``g.pos0 + [0..T)``. -> (the K rows
    the batch rows attend [B, S, Hkv, Dh], the V rows, the planes
    written). Cache rows are head-FLAT [seq, kv_dim] (see KVCache);
    heads are re-split transiently for the attention contraction.
    ``raw``: the rows come back flat as they are cached — a latent
    cache, whose ``v`` has no lanes."""
    spec, quant = ctx.spec, ctx.quant
    ck, cv = st[:2]
    ks, vs = st[2:] if quant else (None, None)
    pos0, slot_ids, write_mask = g.pos0, g.slot_ids, g.write_mask
    B, T = k.shape[0], k.shape[1]
    kq, vq, ksc, vsc = quantize_kv(  # int8 [B,T,F], f32 [B,T]
        k.reshape(B, T, -1), v.reshape(B, T, -1), quant)

    def split(buf, scales):
        # [B, S, kv_dim](+scales [B, S]) -> [B, S, Hkv, Dh] compute
        if raw:
            return buf
        out = buf.reshape(
            buf.shape[0], buf.shape[1], spec.n_kv_heads, spec.d_head)
        if scales is not None:  # dequantize; XLA fuses the convert
            out = out.astype(ctx.dtype) * scales[
                :, :, None, None].astype(ctx.dtype)
        return out

    if slot_ids is None:
        # hot path: per-row dynamic_update_slice, no gather/scatter
        # (a cross-slot scatter would copy the whole cache layer
        # every decode step — ~GBs/step at serving shapes)
        if write_mask is not None:
            # masked rows write back what is already there: the
            # [B, T, F] read is tiny next to the layer traffic
            def cur_row(buf_row, off):
                return lax.dynamic_slice(
                    buf_row, (off, 0), (T, buf_row.shape[-1]))

            def cur_scale(srow, off):
                return lax.dynamic_slice(srow, (off,), (T,))

            m3 = write_mask[:, None, None]
            kq = jnp.where(m3, kq.astype(ck.dtype),
                           jax.vmap(cur_row)(ck, pos0))
            vq = jnp.where(m3, vq.astype(cv.dtype),
                           jax.vmap(cur_row)(cv, pos0))
            if quant:
                m2 = write_mask[:, None]
                ksc = jnp.where(m2, ksc, jax.vmap(cur_scale)(ks, pos0))
                vsc = jnp.where(m2, vsc, jax.vmap(cur_scale)(vs, pos0))
        ck2 = jax.vmap(_write_row)(ck, kq, pos0)
        cv2 = jax.vmap(_write_row)(cv, vq, pos0)
        if quant:
            ks2 = jax.vmap(_write_scale)(ks, ksc, pos0)
            vs2 = jax.vmap(_write_scale)(vs, vsc, pos0)
            return (split(ck2, ks2), split(cv2, vs2),
                    (ck2, cv2, ks2, vs2))
        return split(ck2, None), split(cv2, None), (ck2, cv2)
    if B == 1:
        # single-row update (prefill/embed): DUS straight into the
        # 3D buffer at (slot, pos, 0)
        ck2 = lax.dynamic_update_slice(
            ck, kq.astype(ck.dtype), (slot_ids[0], pos0[0], 0))
        cv2 = lax.dynamic_update_slice(
            cv, vq.astype(cv.dtype), (slot_ids[0], pos0[0], 0))
        if quant:
            ks2 = lax.dynamic_update_slice(
                ks, ksc, (slot_ids[0], pos0[0]))
            vs2 = lax.dynamic_update_slice(
                vs, vsc, (slot_ids[0], pos0[0]))
    else:
        def write(one, cbuf, new):
            rows = jax.vmap(one)(cbuf[slot_ids], new, pos0)
            return cbuf.at[slot_ids].set(rows)

        ck2 = write(_write_row, ck, kq)
        cv2 = write(_write_row, cv, vq)
        if quant:
            ks2 = write(_write_scale, ks, ksc)
            vs2 = write(_write_scale, vs, vsc)
    if quant:
        return (split(ck2[slot_ids], ks2[slot_ids]),
                split(cv2[slot_ids], vs2[slot_ids]),
                (ck2, cv2, ks2, vs2))
    return (split(ck2[slot_ids], None), split(cv2[slot_ids], None),
            (ck2, cv2))


def xla(ctx, g, st, q, k, v):
    """``dense_xla`` and ``paged_xla_gather`` (the engine gathers the
    pages into a dense view OUTSIDE the forward): rows written into the
    layer's slices (``kv_from_cache``), then XLA's own contraction over
    each row's whole view."""
    k_eff, v_eff, wrote = kv_from_cache(ctx, g, st, k, v)
    if ctx.ring_prefill:
        # seq-parallel exact attention over the chunk itself
        # (caller guarantees pos0 == 0, so the cache holds no
        # earlier positions to attend). K/V still went through
        # kv_from_cache above for the cache WRITE; attention
        # reads the pre-quantization chunk rows.
        from ..parallel.ring_attention import ring_attention

        # GQA K/V go in at their native head count; the ring
        # repeats them locally after each ICI receive
        out = ring_attention(q, k, v, ctx.mesh, causal=True,
                             scale=softmax_scale(ctx.spec))
        B, T = q.shape[0], q.shape[1]
        return out.reshape(B, T, -1).astype(ctx.dtype), wrote
    return _attend(ctx.spec, q, k_eff, v_eff, _positions(ctx, g),
                   ctx.lp.get("_window")), wrote


def latent_ragged(ctx, g, st, qn, qr, row):
    """``latent_paged_kernel``: the chunk's rows scatter into the arena
    through the write table as K rows do, then the group's rows attend
    them in the form that costs them less (``latent_prompt_form``, from
    the widths and the group's row length alone). ABSORBED
    (ops/ragged_paged_attention.py, ``v_lanes``): 128 query heads x the
    whole row against a page, PV against the page's first kv_lora_rank
    lanes; W_kvb never touches a cached row. EXPANDED
    (ops/latent_flash_attention.py): a page's rows through W_kvb head
    by head in the kernel, the query as ``_latent_mixer`` has it beside
    the zeros of the row's tail lanes, the output written once."""
    from ..ops.latent_flash_attention import (
        EXPANDED, join_query, latent_flash_attention, latent_prompt_form,
    )
    from ..ops.ragged_paged_attention import (
        append_rows, ragged_paged_attention,
    )

    spec, l, page = ctx.spec, ctx.l, ctx.kv_page
    attend = _attend_lens(g)
    B, T = row.shape[0], row.shape[1]
    (ck_new,) = append_rows(st[:1], (row,), l, g.write_table, g.pos0,
                            g.q_lens, page)
    if latent_prompt_form(spec, T) == EXPANDED:
        out = latent_flash_attention(
            join_query(qn, qr, spec.latent_row - spec.kv_lora_rank),
            ck_new, l, g.page_table, g.pos0, attend,
            ctx.stack["wkv_b_k"], ctx.stack["wkv_b_v"], ctx.li,
            scale=latent_scale(spec), page=page)
        return out, (ck_new, st[1])
    heads = ragged_paged_attention(
        latent_absorb_query(spec, ctx.lp, qn, qr), ck_new, None, l,
        g.page_table, g.pos0, attend, 1,
        scale=latent_scale(spec), page=page, v_lanes=spec.kv_lora_rank,
    )  # [B, T, H * r] f32
    heads = heads.reshape(B, T, spec.n_heads, spec.kv_lora_rank)
    return (latent_absorb_out(spec, ctx.lp, heads, ctx.dtype),
            (ck_new, st[1]))


def latent_xla(ctx, g, st, qn, qr, row):
    """The XLA routes over a latent cache: rows written as any K row is,
    then the EXPANDED form over the row's whole view."""
    view, _, wrote = kv_from_cache(ctx, g, st, row, row[..., :0], raw=True)
    return latent_attend_expanded(ctx.spec, ctx.lp, qn, qr, view,
                                  _positions(ctx, g)), wrote


def select(spec, groups: tuple, decode_kernel: bool) -> tuple:
    """The route of a forward pass over ``groups`` -> (route, whether it
    addresses the stacked [L, ...] planes in place). Every group
    reaches the cache the same way, so the first one says it: a page
    table IS the ragged route (engine/cache_route.py sets it on that
    route alone); ``decode_kernel`` takes the dense cache's kernel for
    ONE group of one token a row in slot order."""
    g0 = groups[0]
    paged = g0.page_table is not None
    if spec.kv_lora_rank:
        return (latent_ragged, True) if paged else (latent_xla, False)
    if paged:
        return ragged, True
    if (decode_kernel and len(groups) == 1 and g0.slot_ids is None
            and g0.tokens.shape[1] == 1):
        return dense_kernel, True
    return xla, False


def attend_groups(route, ctx, groups, st, q, k, v):
    """``_layer_body``'s ``attn_fn``: the rows of every group through
    ``route`` in order, each on the planes the group before it left.
    -> (attn over the flat rows, the planes written)."""
    outs = []
    for g, *qkv in zip(groups, *(ungroup(groups, a) for a in (q, k, v))):
        o, wrote = route(ctx, g, st, *qkv)
        st = tuple(wrote) + tuple(st[len(wrote):])
        outs.append(o)
    return flatten(outs), st[:len(wrote)]
