"""Latent attention for PROMPT rows in the expanded form, as a flash
kernel over the paged latent arena.

A latent model (``LLMSpec.kv_lora_rank``) caches ONE row a token,
``[c | k_r | 0]``, and can attend it two ways that give the same
scores and the same output:

- ABSORBED (``ops/ragged_paged_attention.py`` with ``v_lanes``): W_kvb
  folded into the query and the output, the cached row read as it is —
  2 (r + d_r + r) FLOP a (query, key, head). Right for a decode row:
  nothing is up-projected for one query.
- EXPANDED (this file): a cached row up-projected through W_kvb to each
  head's ``k_n`` and ``v``, then attention at (d_n + d_r) x d_v —
  2 (d_n + d_r + d_v) FLOP a (query, key, head) plus 2 r (d_n + d_v) a
  (key, head) ONCE for all the row's queries. At DeepSeek-V3's widths
  640 against 2176 and 131 k a key-head: equal at
  ``expanded_from`` = 170.7 queries, 1.89 x fewer at 512.

``latent_prompt_form`` is that rule, from the spec's widths alone; the
forward (models/cache_attention.py ``latent_ragged``) asks it per group of
rows while tracing.

The kernel. Grid = (row, head block), in order. A step holds its head
block's queries ``[T, hb * (d_n + tail)]`` (``[q_n | q_r | 0]`` a head,
``tail`` = the arena row's lanes past ``c``: what ``_latent_mixer`` has,
joined by ``join_query`` — nothing absorbed), the block's ``W_kvb``
matrices out of
the WHOLE stacks (the index map adds the layer: a slice of a stack as
an operand would be copied out first) and walks the row's pages once,
two page slots deep, the next head block's first page fetched under
this one's last. Per page: ``c`` (the page's first r lanes) goes
through each head's W_kvb,k and W_kvb,v on the MXU, rounded to the
activations' dtype where ``latent_attend_expanded`` rounds them;
``[k_n | the page's tail lanes]`` is the head's key; scores, an online
softmax in f32, PV — query chunk by query chunk. Pages wholly under the
row's first query need no mask; the chunk's own pages take the causal
mask by absolute position and skip query chunks that see none of them.
Context blocks are whole pages at absolute page boundaries and a query
row's arithmetic depends neither on its index in the chunk nor on
``q_lens``: the same token over the same pages gives the same bits
wherever its chunk started. The output is written once, in the
activations' dtype, ``[B, T, H * d_v]``.

A capture lists the call as ``latent_paged_attention_expanded``: the
benchmark finds latent attention by the prefix ``latent_paged_attention``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _interpret
from .ragged_paged_attention import NEG_INF, _STAT_LANES

KERNEL_NAME = "latent_paged_attention_expanded"
ABSORBED, EXPANDED = "absorbed", "expanded"
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# heads a grid step holds and query rows a softmax update covers. Not
# knobs: the MXU holds a 128 x 128 tile of the keys while the query
# rows stream past it, so a chunk of 512 rows (a serving prompt row
# whole) read 2353 us where 256 read 2704 and 128 read 3606 (one layer,
# T = 512 against 4096 cached tokens, 8 heads a step; 4 heads a step
# read 2478 at 512 rows, 16 read 2670 at 256; PERF.md section 6,
# PR 50); 8 heads x 512 rows are 12 MB of f32 softmax state
_HEAD_BLOCK = 8
_QUERY_CHUNK = 512


def expanded_from(rank: int, d_nope: int, d_v: int) -> float:
    """Queries a row from which the expanded form costs fewer FLOPs
    than the absorbed one: T* = r (d_n + d_v) / (2 r - d_n - d_v);
    inf where absorbing never costs more."""
    if 2 * rank <= d_nope + d_v:
        return math.inf
    return rank * (d_nope + d_v) / (2 * rank - d_nope - d_v)


def latent_prompt_form(spec, T: int) -> str:
    """``expanded`` | ``absorbed`` for a group of rows ``T`` queries
    long, from the spec's widths alone. On a TPU the kernel's lane
    slices need whole lane tiles; elsewhere it is interpreted."""
    if T < expanded_from(spec.kv_lora_rank, spec.qk_nope_dim,
                         spec.v_head_dim):
        return ABSORBED
    tiles = all(w % 128 == 0 for w in (
        spec.kv_lora_rank, spec.qk_nope_dim, spec.v_head_dim))
    return EXPANDED if tiles or _interpret() else ABSORBED


def join_query(qn: jax.Array, qr: jax.Array, tail: int) -> jax.Array:
    """The kernel's query: a head's ``[q_n | q_r (rotated) | 0]``,
    ``d_n + tail`` wide — lane for lane against ``[k_n | the cached
    row's ``tail`` lanes past c]``. qn [B, T, H, d_n], qr [B, T, H, d_r]."""
    pad = jnp.zeros((*qr.shape[:3], tail - qr.shape[-1]), qr.dtype)
    return jnp.concatenate([qn, qr, pad], axis=-1)


def _lanes(x, n: int):
    """A lane-replicated [R, 128] statistic as [R, n]."""
    if n % _STAT_LANES == 0:
        return x if n == _STAT_LANES else jnp.tile(x, (1, n // _STAT_LANES))
    return x[:, :n]


def _flash_kernel(qlen_ref, pos_ref, layer_ref, wl_ref, pt_ref,
                  q_ref, wk_ref, wv_ref, arena, out_ref,
                  pbuf, sem, hand_ref, kcat_ref, v_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, page: int, T: int, hb: int,
                  rc: int, rank: int, d_n: int, d_v: int):
    del wl_ref  # the weight blocks' index maps read it
    b, g = pl.program_id(0), pl.program_id(1)
    ng = pl.num_programs(1)
    layer = layer_ref[0]
    qlen, p0 = qlen_ref[b], pos_ref[b]
    dq = q_ref.shape[-1] // hb  # d_n + the row's tail lanes
    dt = q_ref.dtype
    # pages the row's valid queries can see; those wholly at or under
    # its FIRST query are seen whole by every query
    n_pages = jnp.where(qlen > 0, lax.div(p0 + qlen + page - 1, page), 0)
    n_full = jnp.minimum(lax.div(p0 + 1, page), n_pages)
    n_chunks = lax.div(qlen + rc - 1, rc)  # query chunks with a token
    reads = n_pages > 0

    for h in range(hb):
        m_ref[h] = jnp.full((T, _STAT_LANES), NEG_INF, jnp.float32)
        l_ref[h] = jnp.zeros((T, _STAT_LANES), jnp.float32)
        acc_ref[h] = jnp.zeros((T, d_v), jnp.float32)

    def dma(slot, p):
        return pltpu.make_async_copy(
            arena.at[layer, pt_ref[b, p]], pbuf.at[slot], sem.at[slot])

    # the row's walk crosses its head blocks: a block's last page
    # fetches the next block's first into the other slot and hands the
    # slot over in SMEM; a row's first block fetches its own
    slot0 = jnp.where(g == 0, 0, hand_ref[0])

    @pl.when(reads & (g == 0))
    def _():
        dma(0, 0).start()

    def attend(masked: bool, p, carry):
        slot = lax.rem(slot0 + p, 2)
        last = p + 1 == n_pages

        @pl.when(~last | (g + 1 < ng))
        def _():
            dma(1 - slot, jnp.where(last, 0, p + 1)).start()

        dma(slot, p).wait()
        c = pbuf[slot, :, :rank]  # [page, r]
        tail = pbuf[slot, :, rank:]  # [page, k_r | 0]
        for h in range(hb):
            kn = lax.dot_general(c, wk_ref[0, h], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            kcat_ref[h, :, :d_n] = kn.astype(dt)
            kcat_ref[h, :, d_n:] = tail
            v_ref[h] = jnp.dot(
                c, wv_ref[0, h],
                preferred_element_type=jnp.float32).astype(dt)

        def chunk(i, _):
            r0 = pl.multiple_of(i * rc, rc)
            rows = pl.ds(r0, rc)
            if masked:
                kvpos = p * page + lax.broadcasted_iota(
                    jnp.int32, (rc, page), 1)
                qpos = p0 + r0 + lax.broadcasted_iota(
                    jnp.int32, (rc, page), 0)
                seen = kvpos <= qpos
            for h in range(hb):
                s = lax.dot_general(
                    q_ref[0, rows, h * dq:(h + 1) * dq], kcat_ref[h],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if masked:
                    s = jnp.where(seen, s, NEG_INF)
                m_prev = m_ref[h, rows]
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # every row has seen key 0 by its first page, so m_new
                # is a real score and a masked key's weight is exp(-1e30)
                pexp = jnp.exp(s - _lanes(m_new, page))
                l_ref[h, rows] = alpha * l_ref[h, rows] + jnp.sum(
                    pexp, axis=1, keepdims=True)
                m_ref[h, rows] = m_new
                pv = jnp.dot(pexp.astype(dt), v_ref[h],
                             preferred_element_type=jnp.float32)
                acc_ref[h, rows] = (
                    acc_ref[h, rows] * _lanes(alpha, d_v) + pv)
            return 0

        # a masked page's first chunk with a query at or past the
        # page's first key (chunks before it see nothing of the page)
        first = lax.div(jnp.maximum(p * page - p0, 0), rc) if masked else 0
        lax.fori_loop(first, n_chunks, chunk, 0)
        return carry

    lax.fori_loop(0, n_full, functools.partial(attend, False), 0)
    lax.fori_loop(n_full, n_pages, functools.partial(attend, True), 0)

    @pl.when(reads)
    def _():
        hand_ref[0] = lax.rem(slot0 + n_pages, 2)

    for h in range(hb):
        l = jnp.maximum(l_ref[h], 1e-30)
        out_ref[0, :, h * d_v:(h + 1) * d_v] = (
            acc_ref[h] / _lanes(l, d_v)).astype(out_ref.dtype)


def latent_flash_attention(
    q: jax.Array,  # [B, T, H, d_n + tail]: [q_n | q_r (rotated) | 0]
    arena: jax.Array,  # [L, n_pages, page, r + tail] latent rows,
    # already holding this dispatch's rows (the caller scatters them)
    layer: jax.Array,  # [] i32 the arena's layer
    page_table: jax.Array,  # [B, max_pages] i32
    pos0: jax.Array,  # [B] i32 absolute position of q[:, 0]
    q_lens: jax.Array,  # [B] i32 valid queries a row; 0 = parked
    wkv_b_k: jax.Array,  # [n, H, d_n, r] a WHOLE stack
    wkv_b_v: jax.Array,  # [n, H, r, d_v] a WHOLE stack
    w_layer: jax.Array,  # [] i32 this layer's index in the stacks
    *,
    scale: float,
    page: int,
) -> jax.Array:
    """-> [B, T, H * d_v] in q's dtype (rows past ``q_lens``: finite
    values the caller discards)."""
    B, T, H, dq = q.shape
    _, _, PG, F = arena.shape
    _, _, d_n, rank = wkv_b_k.shape
    d_v = wkv_b_v.shape[-1]
    assert PG == page and dq == d_n + F - rank, (q.shape, arena.shape)
    assert arena.dtype == q.dtype == wkv_b_k.dtype, "one dtype throughout"
    hb = math.gcd(H, _HEAD_BLOCK)
    rc = math.gcd(T, _QUERY_CHUNK)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # q_lens, pos0, layer, w_layer, table
        grid=(B, H // hb),
        in_specs=[
            pl.BlockSpec((1, T, hb * dq), lambda b, g, *_: (b, 0, g)),
            pl.BlockSpec((1, hb, d_n, rank),
                         lambda b, g, ql, ps, ly, wl, pt: (wl[0], g, 0, 0)),
            pl.BlockSpec((1, hb, rank, d_v),
                         lambda b, g, ql, ps, ly, wl, pt: (wl[0], g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, T, hb * d_v),
                               lambda b, g, *_: (b, 0, g)),
        scratch_shapes=[
            pltpu.VMEM((2, page, F), arena.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),  # the slot handed over
            pltpu.VMEM((hb, page, dq), q.dtype),  # [k_n | tail] a head
            pltpu.VMEM((hb, page, d_v), q.dtype),  # v a head
            pltpu.VMEM((hb, T, _STAT_LANES), jnp.float32),  # m
            pltpu.VMEM((hb, T, _STAT_LANES), jnp.float32),  # l
            pltpu.VMEM((hb, T, d_v), jnp.float32),  # acc
        ],
    )
    kernel = functools.partial(
        _flash_kernel, scale=scale, page=page, T=T, hb=hb, rc=rc,
        rank=rank, d_n=d_n, d_v=d_v)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H * d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a row's page walk runs from its first head block to its
            # last: the grid runs in order on one core
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(q_lens, pos0, layer[None], w_layer[None], page_table,
      q.reshape(B, T, H * dq), wkv_b_k, wkv_b_v, arena)
