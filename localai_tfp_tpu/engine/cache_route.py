"""How a dispatch program reaches the KV cache — decided ONCE per engine.

The engine picks one route at construction from what it observes (mesh
axes and page geometry decide pool vs dense; platform and shape
eligibility decide kernel vs XLA — ``LLMEngine._kernel_ineligible``)
and every program builder runs the same three calls around its forward:

    view = route.open(cache, tables, window)
    ... forward(spec, params, tokens, pos0, view, **route.forward_kw(...))
    cache = route.close(cache, view, tables)

``tables`` is the dispatch's ``(page_table, write_table)`` pair on the
pool routes and ``()`` on the dense one. On the host, ``window`` /
``ladder`` say which context window a dispatch is planned (and warmed)
at. Nothing outside this file knows which of the routes runs (three,
and the ragged one's latent shape).
"""

from __future__ import annotations

from typing import Any, Iterable

import jax
import jax.numpy as jnp
from jax import lax

from ..models.transformer import KVCache, gather_kv_pages, scatter_kv_pages


def window_bucket(need: int, max_seq: int) -> int:
    """Smallest power-of-two window >= need (floor 256, cap max_seq)."""
    w = 256
    while w < need:
        w *= 2
    return min(w, max_seq)


def _window_cache(cache: KVCache, window: int) -> KVCache:
    """Slice the cache to its first ``window`` positions. Per-dispatch
    windowing keeps attention/write traffic proportional to the
    live-context bucket, not max_seq (the dense cache's stand-in for
    ragged paged attention)."""
    L, S, SEQ, F = cache.k.shape
    if window >= SEQ:
        return cache
    return cache.with_kv(
        lax.slice(cache.k, (0, 0, 0, 0), (L, S, window, F)),
        lax.slice(cache.v, (0, 0, 0, 0), (L, S, window, F)),
        (lax.slice(cache.k_scale, (0, 0, 0), (L, S, window))
         if cache.quantized else None),
        (lax.slice(cache.v_scale, (0, 0, 0), (L, S, window))
         if cache.quantized else None),
    )


def _restore_window(cache: KVCache, win: KVCache) -> KVCache:
    """Write a ``_window_cache`` view back into the full buffer."""
    if win.k.shape[2] >= cache.k.shape[2]:
        return win
    # the recurrent state is the view's: the forward advanced it there
    return win.with_kv(
        lax.dynamic_update_slice(cache.k, win.k, (0, 0, 0, 0)),
        lax.dynamic_update_slice(cache.v, win.v, (0, 0, 0, 0)),
        (lax.dynamic_update_slice(cache.k_scale, win.k_scale, (0, 0, 0))
         if cache.quantized else None),
        (lax.dynamic_update_slice(cache.v_scale, win.v_scale, (0, 0, 0))
         if cache.quantized else None),
    )


def _pin_win_sharding(win: KVCache, mesh, batch: bool) -> KVCache:
    """Constrain a gathered window view [L, B, W, F] on a mesh. With
    ``batch`` True the slot dim rides "data" and F rides "model" — the
    DENSE cache's exact layout, which is the only window placement
    whose jitted forward is numerically correct on a data x model mesh:
    with the slot dim replicated (F-sharded or fully replicated alike),
    GSPMD picks a partitioning for the fused gather -> forward ->
    scatter program that computes O(1)-wrong hidden states and KV
    writes (jit vs eager diverges on the written pages). With ``batch``
    False the window is pinned back to the ARENA's layout (slot dim
    replicated, F over "model") so the writeback scatter sees updates
    shaped like its data-replicated operand. Scale planes are global
    per-row amax, replicated either way."""
    from jax.sharding import NamedSharding

    from ..parallel.sharding import (
        KV_CACHE_SPEC, PAGED_KV_SPEC, REPLICATED, _divisible_spec,
    )

    row_sp = KV_CACHE_SPEC if batch else PAGED_KV_SPEC
    plane_sp = REPLICATED

    def pin(a, sp):
        sp = _divisible_spec(a.shape, sp, mesh)
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, sp))

    return win.with_kv(
        pin(win.k, row_sp), pin(win.v, row_sp),
        pin(win.k_scale, plane_sp) if win.quantized else None,
        pin(win.v_scale, plane_sp) if win.quantized else None,
    )


class _PoolRoute:
    """Both pool routes ride FULL-width page tables (max_seq // page
    entries), so the jit cache holds ONE variant per token-budget shape
    instead of a bucket x window ladder."""

    def __init__(self, max_seq: int, page: int, mesh: Any) -> None:
        self.max_seq, self.page, self.mesh = max_seq, page, mesh

    def window(self, need: int, kind: str,
               compiled: Iterable[int] = ()) -> int:
        return self.max_seq

    def ladder(self, kind: str, need: int = 1) -> list[int]:
        return [self.max_seq]


class RaggedRoute(_PoolRoute):
    """The arena goes straight into the one ragged Pallas kernel
    (ops/ragged_paged_attention.py): every row kind — decode rows,
    prefill chunks, finals, spec-verify rows — is a ragged row of
    ``q_lens`` tokens whose K/V scatter through the write table (rows
    and pages the host did not grant land on the trash page) while
    attention walks the read table's pages in-kernel. No gathered view
    is ever materialized. Over a LATENT arena (``KVCache`` of a model
    with ``kv_lora_rank``: one plane of latent rows, no V lanes) the
    tables, the scatter and the page walk are the same and the kernel
    runs in its absorbed form (``v_lanes``; a capture names it
    ``latent_paged_attention``): the route only says, by its name, that
    the pages are latent — which form attends them is the forward's
    business (models/cache_attention.py ``latent_ragged``)."""

    def __init__(self, max_seq: int, page: int, mesh: Any,
                 latent: bool = False) -> None:
        super().__init__(max_seq, page, mesh)
        self.name = ("latent_paged_kernel" if latent
                     else "ragged_paged_kernel")

    def open(self, cache: KVCache, tables: tuple, window: int) -> KVCache:
        return cache

    def forward_kw(self, tables: tuple, q_lens: jax.Array, *,
                   slot_ids=None, write_mask=None, ring: bool = False,
                   decode: bool = False, row0=None) -> dict:
        phys, wb = tables
        # the tables say where a row's K/V live; ``state_slots`` keeps
        # saying whose recurrent state it advances (linear layers)
        return {"slot_ids": None, "state_slots": slot_ids,
                "mesh": self.mesh, "page_table": phys,
                "kv_page": self.page, "q_lens": q_lens, "write_table": wb}

    def close(self, cache: KVCache, view: KVCache, tables: tuple) -> KVCache:
        return view


class GatherRoute(_PoolRoute):
    """CPU, ineligible shapes and meshes the kernel cannot split:
    gather a dense per-row view of the pages (identity layout: batch
    row b is view row b, the slot mapping lives in the tables), run the
    XLA forward on it, scatter back through the write table — parked
    and pad rows never write back (their pages are trash). On a mesh
    the forward runs on the dense cache's layout and the scatter on the
    arena's (``_pin_win_sharding``)."""

    name = "paged_xla_gather"

    def open(self, cache: KVCache, tables: tuple, window: int) -> KVCache:
        view = gather_kv_pages(cache, tables[0], self.page)
        if self.mesh is not None:
            view = _pin_win_sharding(view, self.mesh, batch=True)
        return view

    def forward_kw(self, tables: tuple, q_lens: jax.Array, *,
                   slot_ids=None, write_mask=None, ring: bool = False,
                   decode: bool = False, row0=None) -> dict:
        # row0: the group's first row in a view opened for more rows
        # than the group has (a mixed step's two groups share one view)
        # (q_lens: the XLA contraction masks causally and needs no
        # lengths, but a recurrent state must not see a chunk's pad)
        return {"state_slots": slot_ids, "q_lens": q_lens,
                "slot_ids": None if row0 is None else row0 + jnp.arange(
                    q_lens.shape[0], dtype=jnp.int32)}

    def close(self, cache: KVCache, view: KVCache, tables: tuple) -> KVCache:
        if self.mesh is not None:
            view = _pin_win_sharding(view, self.mesh, batch=False)
        return scatter_kv_pages(cache, view, tables[1], self.page)


class DenseRoute:
    """The dense ``[L, n_slots, max_seq, F]`` cache (seq-sharded meshes,
    page < 8 geometry, and the reference the pool is tested against):
    slice the live-context window, run, restore. ``kernel``: decode
    steps take the fused Pallas decode kernel, which reads valid pages
    only — their window is always max_seq."""

    def __init__(self, max_seq: int, mesh: Any, kernel: bool) -> None:
        self.max_seq, self.mesh, self.kernel = max_seq, mesh, kernel
        self.name = "dense_decode_kernel" if kernel else "dense_xla"

    def open(self, cache: KVCache, tables: tuple, window: int) -> KVCache:
        return _window_cache(cache, window)

    def forward_kw(self, tables: tuple, q_lens: jax.Array, *,
                   slot_ids=None, write_mask=None, ring: bool = False,
                   decode: bool = False, row0=None) -> dict:
        kw = {"slot_ids": slot_ids, "q_lens": q_lens, "mesh": self.mesh,
              "ring_prefill": ring}
        if write_mask is not None:
            kw["write_mask"] = write_mask
        if decode:  # one token a row of the MAIN model (shapes checked)
            kw["decode_kernel"] = self.kernel
        return kw

    def close(self, cache: KVCache, view: KVCache, tables: tuple) -> KVCache:
        return _restore_window(cache, view)

    def window(self, need: int, kind: str,
               compiled: Iterable[int] = ()) -> int:
        """Window for a dispatch whose rows reach ``need`` positions:
        the power-of-two bucket, or the smallest already-``compiled``
        window that covers it (a cold jit costs seconds; reading a
        slightly larger window costs microseconds). Nothing compiled
        covers it: a decode scan compiles its bucket, the prompt kinds
        fall back to max_seq, which is always warmed. Chunk prefills
        are warmed along the whole ladder and take the bucket."""
        if kind == "decode" and self.kernel:
            return self.max_seq
        w = window_bucket(need, self.max_seq)
        if kind == "prefill":
            return w
        covering = [c for c in compiled if c >= w]
        if covering:
            return min(covering)
        return w if kind == "decode" else self.max_seq

    def ladder(self, kind: str, need: int = 1) -> list[int]:
        """Every window ``window(n, kind)`` can pick for n >= need."""
        if kind == "decode" and self.kernel:
            return [self.max_seq]
        out, w = [], window_bucket(need, self.max_seq)
        while w < self.max_seq:
            out.append(w)
            w *= 2
        return out + [self.max_seq]


def choose_route(*, paged: bool, kernel: bool, max_seq: int, page: int,
                 mesh: Any, latent: bool = False):
    """The engine's one route. ``paged``: the pool exists (no "seq"
    mesh axis, page >= 8); ``kernel``: ``_kernel_ineligible()`` is
    empty; ``latent``: the cache holds latent rows."""
    if not paged:
        return DenseRoute(max_seq, mesh, kernel)
    if kernel:
        return RaggedRoute(max_seq, page, mesh, latent)
    return GatherRoute(max_seq, page, mesh)
