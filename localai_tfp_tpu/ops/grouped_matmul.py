"""The routed expert layer's grouped matmul as a Pallas kernel.

``out[r] = lhs[r] @ rhs[layer * E + g]`` for the rows ``r`` of group
``g``: the sorted (token, expert) assignments of ``_moe_mlp`` against
the WHOLE ``[n * E, in, out]`` view of an expert stack, of which one
layer's ``E`` groups have rows. An expert layer is memory-bound at every
row count the engine serves (a decode step puts 128 assignments on
~46 experts of 2048 x 1024 x 3 matrices), so the kernel is built around
the weight stream and nothing else:

- a VISIT is one (group, row tile) pair that has rows; the grid runs
  over the visits that exist (a dynamic bound — an expert without rows
  is never read) and, inside a visit, over the contraction's blocks;
- an expert's matrix comes from HBM in blocks of whole rows of ``out``
  (contiguous: ``[tk, out]`` of a row-major matrix), as large as
  ``_BLOCK_BYTES`` allows, the whole matrix where it fits; Pallas'
  pipeline fetches the next visit's block while this one is multiplied,
  across group boundaries, and a group that spans two row tiles keeps
  its block where the matrix is one block;
- the row tile is ``ROW_TILE`` = 128 at every row count and the
  contraction's split depends on the matrix alone, so a row's
  arithmetic — which blocks, in which order, into an f32 accumulator —
  does not depend on how many other rows the step carries. (On a v5e
  the MXU holds a 128 x 128 weight tile while the rows stream past it:
  fewer rows a tile would not shorten a visit, the tile's load does.)
- several matrices that share their rows (gate and up) ride ONE call:
  the row tile is loaded once and each matrix is a stream of its own.

The kernel is named ``ragged-dot-grouped``: a capture lists it under
that instruction name, and the benchmark's expert-layer readers find
the grouped matmul by the prefix ``ragged-dot`` (XLA's own op, which it
replaces on the chip, is ``ragged-dot``).

``expert_path`` decides, from what the code can see, whether an expert
stack takes the kernel or ``lax.ragged_dot``; ``models/transformer.py``
``_moe_mlp`` asks it while tracing and the engine asks it once at load
for the worker's model info.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _interpret

KERNEL_NAME = "ragged-dot-grouped"
ROW_TILE = 128
# one block of one matrix: double buffered, two matrices a call at most
_BLOCK_BYTES = 8 * 1024 * 1024
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024

GROUPED_KERNEL, RAGGED_DOT = "grouped_kernel", "ragged_dot"


def contraction_block(k: int, n: int, dtype) -> Optional[int]:
    """Rows of an ``[k, n]`` matrix a block holds: the largest divisor
    of ``k`` in whole lane tiles whose ``[tk, n]`` block stays within
    ``_BLOCK_BYTES``; None where the kernel's tiling does not cover the
    matrix (a dtype it does not multiply, widths that are no multiple
    of 128, an ``n`` so wide that 128 rows pass the block)."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return None
    if k % 128 or n % 128:
        return None
    fits = [d for d in range(128, k + 1, 128)
            if k % d == 0 and d * n * dt.itemsize <= _BLOCK_BYTES]
    return max(fits) if fits else None


def expert_path(stacks: Sequence[Any], act_dtype, mesh) -> str:
    """``grouped_kernel`` | ``ragged_dot`` for expert matrices
    ``stacks`` (arrays or shapes with dtypes, ``[.., in, out]``)
    multiplied by rows of ``act_dtype``: the kernel on a TPU backend,
    outside a mesh (a sharded stack needs a ``shard_map`` no caller
    has), for stacks of the rows' dtype that ``contraction_block``
    covers; ``lax.ragged_dot`` everywhere else."""
    if _interpret() or mesh is not None:
        return RAGGED_DOT
    for w in stacks:
        if jnp.dtype(w.dtype) != jnp.dtype(act_dtype) or \
                contraction_block(*w.shape[-2:], w.dtype) is None:
            return RAGGED_DOT
    return GROUPED_KERNEL


class Schedule(NamedTuple):
    """The visits of one layer-step, as the kernel's scalar operands."""

    offsets: jax.Array  # [E + 1] i32: group g's rows are [g], [g + 1]
    groups: jax.Array  # [T] i32: the visit's group
    tiles: jax.Array  # [T] i32: the visit's row tile
    visits: jax.Array  # [] i32: how many of the T exist


def padded_rows(rows: int) -> int:
    return -(-rows // ROW_TILE) * ROW_TILE


def schedule(sizes: jax.Array, rows: int) -> Schedule:
    """``sizes`` [E] i32 rows a group, in row order from row 0;
    ``rows`` (a multiple of ROW_TILE) the row count. Visits run in row
    order: a row tile's groups one after the other, a group's row
    tiles one after the other, empty groups nowhere. At most
    ``rows / ROW_TILE + E - 1`` of them."""
    assert rows % ROW_TILE == 0, rows
    E = sizes.shape[0]
    i32 = jnp.int32
    sizes = sizes.astype(i32)
    ends = jnp.cumsum(sizes, dtype=i32)
    starts = ends - sizes
    first = starts // ROW_TILE
    n_tiles = jnp.where(sizes > 0, (ends - 1) // ROW_TILE - first + 1, 0)
    v_end = jnp.cumsum(n_tiles, dtype=i32)
    T = rows // ROW_TILE + E - 1
    t = jnp.arange(T, dtype=i32)
    g = jnp.minimum(
        jnp.sum(v_end[None, :] <= t[:, None], axis=1, dtype=i32), E - 1)
    tile = first[g] + t - (v_end - n_tiles)[g]
    return Schedule(
        jnp.concatenate([jnp.zeros((1,), i32), ends]), g,
        jnp.clip(tile, 0, rows // ROW_TILE - 1), v_end[-1])


def _kernel(layer_ref, off_ref, group_ref, tile_ref, lhs_ref, *refs,
            n_mats: int, n_k: int):
    del layer_ref  # the index maps' operand
    rhs = refs[:n_mats]
    outs = refs[n_mats:2 * n_mats]
    accs = refs[2 * n_mats:]
    t, k = pl.program_id(0), pl.program_id(1)
    x = lhs_ref[...]
    prods = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
             for w in rhs]
    if n_k > 1:
        @pl.when(k == 0)
        def _():
            for acc, p in zip(accs, prods):
                acc[...] = p

        @pl.when(k > 0)
        def _():
            for acc, p in zip(accs, prods):
                acc[...] += p

    @pl.when(k == n_k - 1)
    def _():
        g, tile = group_ref[t], tile_ref[t]
        row = tile * ROW_TILE + lax.broadcasted_iota(
            jnp.int32, (ROW_TILE, 1), 0)
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
        # a row tile's block stays in fast memory over its visits: the
        # first one starts it from zeros, the others add their rows
        fresh = (t == 0) | (tile != tile_ref[jnp.maximum(t - 1, 0)])
        vals = [acc[...] for acc in accs] if n_k > 1 else prods

        @pl.when(fresh)
        def _():
            for o, v in zip(outs, vals):
                o[...] = jnp.where(mine, v, 0.0).astype(o.dtype)

        @pl.when(jnp.logical_not(fresh))
        def _():
            for o, v in zip(outs, vals):
                o[...] = jnp.where(mine, v.astype(o.dtype), o[...])


def grouped_matmul(lhs: jax.Array, mats: Sequence[jax.Array], layer,
                   sched: Schedule) -> tuple:
    """``lhs`` [M, K] rows in group order (M a multiple of ROW_TILE);
    ``mats``: matrices ``[n * E, K, N]`` of one shape and ``lhs``'s
    dtype, the WHOLE stacks — group g of ``sched`` is matrix
    ``layer * E + g`` of each. -> one ``[M, N]`` array a matrix, in
    ``lhs``'s dtype, f32 accumulation. A row no group holds comes out 0
    where its row tile was visited and undefined elsewhere."""
    M, K = lhs.shape
    N = mats[0].shape[2]
    E = sched.offsets.shape[0] - 1
    tk = contraction_block(K, N, mats[0].dtype)
    assert tk is not None and M % ROW_TILE == 0, (lhs.shape, mats[0].shape)
    assert all(m.shape == mats[0].shape and m.dtype == lhs.dtype
               for m in mats), [(m.shape, m.dtype) for m in mats]
    n_k, n_mats = K // tk, len(mats)

    def lhs_map(t, k, layer, off, group, tile):
        return tile[t], k

    def rhs_map(t, k, layer, off, group, tile):
        return layer[0] * E + group[t], k, 0

    def out_map(t, k, layer, off, group, tile):
        return tile[t], 0

    item = lhs.dtype.itemsize
    out = jax.ShapeDtypeStruct((M, N), lhs.dtype)
    return tuple(pl.pallas_call(
        functools.partial(_kernel, n_mats=n_mats, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(sched.visits, n_k),
            in_specs=[pl.BlockSpec((ROW_TILE, tk), lhs_map)] + [
                pl.BlockSpec((None, tk, N), rhs_map)] * n_mats,
            out_specs=[pl.BlockSpec((ROW_TILE, N), out_map)] * n_mats,
            scratch_shapes=[pltpu.VMEM((ROW_TILE, N), jnp.float32)]
            * (n_mats if n_k > 1 else 0)),
        out_shape=[out] * n_mats,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N * n_mats, transcendentals=0,
            bytes_accessed=(E * K * N * n_mats + M * K
                            + M * N * n_mats) * item),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), sched.offsets,
      sched.groups, sched.tiles, lhs, *mats))
