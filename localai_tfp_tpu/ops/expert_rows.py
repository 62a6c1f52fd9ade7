"""The dispatch AROUND the grouped matmul of an expert layer that holds
a SHARE: only the rows a held expert owns move.

``models/transformer.py`` ``_moe_mlp`` sorts a step's N * K (token,
expert) assignments by expert; a layer that holds ``n_held`` of the
published experts owns the first ``H = sum(counts)`` of them — 6-7 % at
16 of 256 — and the rest belong to other chips. XLA's dispatch moves all
N * K rows all the same (a gather into expert order, a mask over what no
group wrote, the un-sort, the weighted sum: ~490 MB a layer of a
512-token DeepSeek-V3 step). The two kernels here keep the array shapes
(``[padded_rows(N * K), D]``, the worst case: every assignment held is
legal and exact) and touch the first H rows alone:

- ``gather_rows``: sorted row r < H := the token's row ``x[src[r]]``,
  bit for bit. The grid runs over the ``ROWS_TILE``-row output tiles
  that hold a row < H (a dynamic bound); tiles past them are never
  written and hold whatever was there — the grouped matmul reads them
  only inside its last visited tile, where a row's garbage stays in that
  row.
- ``combine_rows``: out[n] = sum over n's held assignments of w · y[r],
  in f32, walking the sorted rows r < H in order. A row past H is never
  read, so nothing has to be masked. A token's rows come in the order
  of its held experts' ids — its own routing and nothing else — each as
  one f32 multiply and one f32 add into the token's accumulator: a
  token's bits do not depend on the step's row count, on the tile a
  row fell in, or on what the other tokens routed.

Why the rows move through VMEM by vector loads and not by one DMA a
row: this Mosaic refuses a DMA slice of fewer than 8 rows of a tiled
2-D array ("Slice shape along dimension 0 must be aligned to tiling
(8)"), in HBM and in VMEM alike, and a bf16 row shares its 32-bit words
with its neighbour. So the token rows (``[N, D]``, 7.5 MB at 528 x
7168) sit whole in VMEM, widened to f32 once a call, and a row is 56
one-sublane loads and stores at a dynamic sublane; the combine's
accumulator is the whole ``[N, D]`` f32 output, resident the same way,
and y's tiles stream past it. ``fits`` says whether a step's token rows
fit; ``_moe_mlp`` asks it while tracing and keeps XLA's dispatch where
they do not.

The kernels are named ``expert-rows-gather`` / ``expert-rows-combine``:
no reader that finds the grouped matmul by the prefix ``ragged-dot``
matches them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _interpret
from .grouped_matmul import _VMEM_LIMIT_BYTES

GATHER_NAME = "expert-rows-gather"
COMBINE_NAME = "expert-rows-combine"
# sorted rows a grid step: one bf16 tile's sublanes, so a step wastes at
# most 15 rows past H
ROWS_TILE = 16
# resident bytes an element of the token rows: the gather holds them in
# the model's dtype (double-buffered) and in f32, the combine holds the
# f32 output double-buffered
_RESIDENT_BYTES = 8


def fits(n_tokens: int, d_model: int) -> bool:
    """Whether a step's ``[n_tokens, d_model]`` token rows can sit in
    VMEM beside the streamed tiles: 3/4 of the kernels' limit. (Dtype
    and lane width are the grouped kernel's to refuse:
    ``gm.expert_path``.)"""
    return (n_tokens * d_model * _RESIDENT_BYTES
            <= _VMEM_LIMIT_BYTES * 3 // 4)


def _tiles(held) -> jax.Array:
    return (jnp.asarray(held, jnp.int32) + ROWS_TILE - 1) // ROWS_TILE


def _gather_kernel(src_ref, x_ref, out_ref, wide_ref, stage_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        wide_ref[...] = x_ref[...].astype(jnp.float32)

    # a row past H in the last tile copies some token's row too: src is
    # a token index everywhere, and nobody reads the row
    for i in range(ROWS_TILE):
        s = src_ref[t * ROWS_TILE + i]
        stage_ref[pl.ds(i, 1), :] = wide_ref[pl.ds(s, 1), :]
    out_ref[...] = stage_ref[...].astype(out_ref.dtype)


def gather_rows(x: jax.Array, src: jax.Array, held) -> jax.Array:
    """``x`` [N, D] token rows; ``src`` [R] i32 (R a multiple of
    ROWS_TILE): the token of sorted row r, a valid index everywhere;
    ``held`` i32: how many of the sorted rows a held expert owns.
    -> ``[R, D]`` in ``x``'s dtype whose rows r < held are ``x[src[r]]``
    bit for bit; the rest of the last tile that holds one is some
    token's row, every later tile is never written."""
    N, D = x.shape
    R = src.shape[0]
    assert R % ROWS_TILE == 0 and fits(N, D), (x.shape, R)
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(_tiles(held),),
            in_specs=[pl.BlockSpec((N, D), lambda t, src: (0, 0))],
            out_specs=pl.BlockSpec((ROWS_TILE, D), lambda t, src: (t, 0)),
            scratch_shapes=[pltpu.VMEM((N, D), jnp.float32),
                            pltpu.VMEM((ROWS_TILE, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=(N + R) * D * x.dtype.itemsize),
        interpret=_interpret(),
        name=GATHER_NAME,
    )(src.astype(jnp.int32), x)


def _combine_kernel(tok_ref, held_ref, wt_ref, y_ref, out_ref, stage_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    stage_ref[...] = y_ref[...].astype(jnp.float32)
    for i in range(ROWS_TILE):
        r = t * ROWS_TILE + i

        @pl.when(r < held_ref[0])
        def _():
            n = tok_ref[r]
            out_ref[pl.ds(n, 1), :] += (
                wt_ref[pl.ds(i, 1), :] * stage_ref[pl.ds(i, 1), :])


def combine_rows(y: jax.Array, tok: jax.Array, wt: jax.Array, held,
                 n_tokens: int) -> jax.Array:
    """``y`` [R, D] the down projection's sorted rows (R a multiple of
    ROWS_TILE); ``tok`` [R] i32 the token of sorted row r; ``wt`` [>= the
    rows below ``held``] f32 its routing weight; ``held`` i32: the rows
    a held expert owns. -> f32 ``[n_tokens, D]``: token n's weighted sum
    over its rows r < held in row order, 0 for a token without one. No
    row from ``held`` on is read."""
    R, D = y.shape
    assert R % ROWS_TILE == 0 and fits(n_tokens, D), (y.shape, n_tokens)
    wt = jnp.pad(wt.astype(jnp.float32), (0, R - wt.shape[0]))[:, None]
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # one step at least: a step without a held row still writes
            # its zeros
            grid=(jnp.maximum(_tiles(held), 1),),
            in_specs=[
                pl.BlockSpec((ROWS_TILE, 1), lambda t, tok, h: (t, 0)),
                pl.BlockSpec((ROWS_TILE, D), lambda t, tok, h: (t, 0))],
            out_specs=pl.BlockSpec((n_tokens, D),
                                   lambda t, tok, h: (0, 0)),
            scratch_shapes=[pltpu.VMEM((ROWS_TILE, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_tokens, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * D, transcendentals=0,
            bytes_accessed=R * D * y.dtype.itemsize + n_tokens * D * 4),
        interpret=_interpret(),
        name=COMBINE_NAME,
    )(tok.astype(jnp.int32), jnp.asarray(held, jnp.int32).reshape(1), wt, y)

