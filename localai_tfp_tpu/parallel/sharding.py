"""Sharding rules for the stacked-scan parameter layout.

TP/DP/SP layout (the GSPMD counterpart of the reference's tensor_split /
tensor_parallel_size knobs — ref: backend.proto:185, vllm/backend.py:106):

- Column-parallel projections (wq/wk/wv/w_gate/w_up): shard the OUTPUT
  feature dim over "model" — each chip computes its own head/ffw slice.
- Row-parallel projections (wo/w_down): shard the INPUT feature dim over
  "model" — XLA inserts the psum (all-reduce) after the matmul, the
  classic Megatron pairing, riding ICI.
- Embedding + lm_head: vocab-sharded over "model".
- Norms/biases on the model dim: replicated (biases on sharded dims follow
  their projection).
- KV cache [L, slots, max_seq, kv_dim] (head-flat): slots over "data",
  the flat head dim over "model". Sequence-dim sharding lives in
  ring_attention.py (prefill/training), not in the serving cache.

All rules are expressed as PartitionSpecs keyed by parameter name so they
apply to any LLMSpec without per-family code.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# name -> spec over [L, ...] stacked leaves
PARAM_RULES: dict[str, P] = {
    "embed": P("model", None),  # [V, D] vocab-sharded
    "lm_head": P(None, "model"),  # [D, V]
    "lm_head_b": P("model"),
    "wq": P(None, None, "model"),  # [L, D, H*Dh] column-parallel
    "wk": P(None, None, "model"),
    "wv": P(None, None, "model"),
    "bq": P(None, "model"),
    "bk": P(None, "model"),
    "bv": P(None, "model"),
    "wo": P(None, "model", None),  # [L, H*Dh, D] row-parallel
    "bo": P(None, None),
    "w_gate": P(None, None, "model"),
    "w_up": P(None, None, "model"),
    "b_up": P(None, "model"),
    "w_down": P(None, "model", None),  # [L, F, D] row-parallel
    "b_down": P(None, None),
    # MoE (mixtral): experts sharded over "model" = expert parallelism;
    # the gate-combine einsum contracts the expert dim, so XLA inserts
    # the psum over ICI
    "router": P(None, None, None),
    "moe_gate": P(None, "model", None, None),  # [L, E, D, F]
    "moe_up": P(None, "model", None, None),
    "moe_down": P(None, "model", None, None),
    # qwen2_moe shared expert: Megatron column/row pairing like the dense
    # MLP; the scalar-gate vector stays replicated
    "shared_gate": P(None, None, "model"),
    "shared_up": P(None, None, "model"),
    "shared_down": P(None, "model", None),
    "shared_router": P(None, None),
    "ln1_w": P(None, None),
    "ln1_b": P(None, None),
    "ln2_w": P(None, None),
    "ln2_b": P(None, None),
    "final_norm_w": P(None),
    "final_norm_b": P(None),
}

# KV cache is [L, n_slots, max_seq, kv_dim] (head-flat — models/transformer
# KVCache): slots ride "data", the flat head dim rides "model"
KV_CACHE_SPEC = P(None, "data", None, "model")
# Paged arena is [L, n_pages, page, kv_dim] (engine/kv_pool.py): pages have
# no slot identity so nothing rides "data" — every device holds its head
# slice of EVERY page and the host-owned int32 page tables stay global.
# int8 scale planes [L, n_pages, page] are per-ROW global-amax (no head
# axis), so they replicate; every model shard writes identical values
# (same contract as ops/decode_attention.sharded_append_attend).
PAGED_KV_SPEC = P(None, None, None, "model")
TOKENS_SPEC = P("data", "seq")
BATCH_SPEC = P("data")
# Replicated operands: global per-row-amax scale planes, scalars and
# the host-owned int32 page tables when passed as shard_map inputs.
# The page tables themselves must NEVER be device_put/constrained onto
# a mesh axis (sharding-contract lint rule): they are host-owned
# scheduler state and every device reads the full table.
REPLICATED = P()
# dense per-slot scale cache [L, slots, seq]: rows over "data"
DENSE_SCALE_SPEC = P(None, "data", None)
# dense decode rows [S, F]: slots over "data", head-flat F over "model"
DENSE_ROW_SPEC = P("data", "model")
# dense decode q [S, H, Dh]: heads over "model"
DENSE_Q_SPEC = P("data", "model", None)
# ragged batch rows [B, T, F]: F over "model" (pages carry no slot
# identity, so nothing rides "data" — matches PAGED_KV_SPEC)
RAGGED_ROW_SPEC = P(None, None, "model")
# ragged q [B, T, H, Dh]: heads over "model"
RAGGED_Q_SPEC = P(None, None, "model", None)

# Every shard_map in/out spec and every paged-fallback window pin in
# engine/ and ops/ must be built from the named constants above — the
# sharding-contract rule bans inline P(...) literals there, so a spec
# cannot silently drift from the arena/cache layout it must match.


def _mesh_is_multiprocess(mesh: Mesh) -> bool:
    pi = jax.process_index()
    return any(d.process_index != pi for d in mesh.devices.flat)


def _assert_load_collective_free(mesh: Mesh) -> None:
    """Pin FollowerRouter's safety argument: an async follower load must
    not issue cross-host collectives, and device_put onto a MULTI-PROCESS
    mesh is exactly that (a compiled cross-host resharding). A future
    loader change that reshards across hosts fails loudly here instead of
    silently deadlocking the lockstep stream (parallel/multihost.py)."""
    from . import multihost

    if multihost.in_follower_load() and _mesh_is_multiprocess(mesh):
        raise RuntimeError(
            "cross-host resharding inside an async follower load would "
            "violate the no-collectives-in-load invariant "
            "(multihost.FollowerRouter)")


def shard_engine_state(cache, sampling, mesh: Mesh, paged: bool = False):
    """Place the serving engine's device state on the mesh: KV cache rows
    over "data"/"model" (dense) or the page arena's head dim over "model"
    (paged), per-slot sampler state over "data" (scalars and vocab-width
    rows follow their leading slot dim).

    The KV head dim MUST divide the tp axis — in BOTH modes (the dense
    [L, slots, seq, kv_dim] cache and the paged arena share the trailing
    kv_dim): falling back to ``_divisible_spec`` replication here would
    silently multiply KV HBM by the tp size — a capacity bug, not a
    fallback — so it errors, and the engine deliberately offers no
    dense carve-out: an indivisible meshed LLMEngine fails construction
    with this message.
    """
    _assert_load_collective_free(mesh)

    tp = mesh.shape.get("model", 1)
    kv_dim = cache.k.shape[-1]
    if kv_dim % tp != 0:
        raise ValueError(
            f"KV cache head dim kv_dim={kv_dim} is not divisible by the "
            f"mesh 'model' axis (tp={tp}); refusing to silently replicate "
            "the KV cache across tensor-parallel shards (each shard would "
            f"hold the FULL cache — a {tp}x HBM capacity regression). Pick "
            "a tp size dividing n_kv_heads*d_head or serve unsharded.")

    def put(arr, spec):
        fixed = _divisible_spec(arr.shape, spec, mesh)
        return jax.device_put(arr, NamedSharding(mesh, fixed))

    if paged:
        kv_spec = PAGED_KV_SPEC
        scale_spec = REPLICATED  # [L, n_pages, page] per-row scales
    else:
        kv_spec = KV_CACHE_SPEC
        scale_spec = DENSE_SCALE_SPEC  # [L, slots, seq] row scales
    cache = type(cache)(
        k=put(cache.k, kv_spec), v=put(cache.v, kv_spec),
        k_scale=(put(cache.k_scale, scale_spec)
                 if cache.quantized else None),
        v_scale=(put(cache.v_scale, scale_spec)
                 if cache.quantized else None),
    )
    leaves, treedef = jax.tree_util.tree_flatten(sampling)
    out = []
    for leaf in leaves:
        # slot-dim state rides "data" in BOTH modes: the paged arena
        # itself is data-replicated, but the per-slot batch of every
        # dispatch must stay data-sharded — it anchors GSPMD to the
        # dense path's (correct) partitioning of the forward. The paged
        # dispatches additionally pin their gathered windows to the
        # same layout (cache_route._pin_win_sharding).
        spec = P(*(("data",) + (None,) * (leaf.ndim - 1))) if leaf.ndim \
            else P()
        out.append(put(leaf, spec))
    return cache, jax.tree_util.tree_unflatten(treedef, out)


def param_specs(params: dict) -> dict[str, P]:
    out = {}
    for name in params:
        spec = PARAM_RULES.get(name)
        if spec is None:
            ndim = getattr(params[name], "ndim", None)
            if ndim is None:  # QTensor outside the rule table
                ndim = params[name].q.ndim
            spec = P(*([None] * ndim))
        out[name] = spec
    return out


def shard_params(params: dict, mesh: Mesh) -> dict:
    """Place parameters onto the mesh per PARAM_RULES. Dims that don't
    divide the axis size fall back to replication on that dim. int8
    QTensor leaves shard their q like the full-precision rule and their
    per-output-channel scale on the matching output dim."""
    from ..models.quant import QTensor

    _assert_load_collective_free(mesh)
    specs = param_specs(params)
    out = {}
    for name, arr in params.items():
        rule = specs.get(name) or P()
        if isinstance(arr, QTensor):
            qspec = _divisible_spec(arr.q.shape, rule, mesh)
            # scale [..., out] follows [..., in, out] minus the in dim
            dims = tuple(rule) + (None,) * (arr.q.ndim - len(tuple(rule)))
            sspec = _divisible_spec(
                arr.scale.shape, P(*(dims[:-2] + (dims[-1],))), mesh)
            out[name] = QTensor(
                q=jax.device_put(arr.q, NamedSharding(mesh, qspec)),
                scale=jax.device_put(arr.scale, NamedSharding(mesh, sspec)),
            )
            continue
        spec = _divisible_spec(arr.shape, rule, mesh)
        out[name] = jax.device_put(arr, NamedSharding(mesh, spec))
    return out


def _divisible_spec(shape, spec: P, mesh: Mesh) -> P:
    fixed = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * len(shape)):
        if axis is None:
            fixed.append(None)
            continue
        size = mesh.shape[axis]
        fixed.append(axis if dim % size == 0 else None)
    return P(*fixed)


def logical_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
