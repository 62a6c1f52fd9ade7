"""The expert layer's grouped-matmul kernel (ops/grouped_matmul.py),
interpreted on the CPU at tiny widths: against ``lax.ragged_dot`` and a
numpy loop over the groups, the schedule's edge cases, the whole-stack
offset, ``_moe_mlp`` on both routes (rows past the last group, a held
share), one row's bits across row counts, and which route the code
picks from what it can see. A time is a chip's (``kernel_check --sweep
--experts``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from localai_tfp_tpu.models import transformer as tr
from localai_tfp_tpu.ops import grouped_matmul as gm
from localai_tfp_tpu.ops.kernel_check import _kernel_layer

T = gm.ROW_TILE
K_IN, N_OUT = 256, 128


def _stack(rng, n_groups, k=K_IN, n=N_OUT, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal((n_groups, k, n)) * k ** -0.5,
                       dtype)


def _kernel(lhs, w, layer, sizes):
    return _kernel_layer(lhs, w, layer, jnp.asarray(sizes, jnp.int32))


def _numpy_loop(lhs, w, layer, sizes):
    """Group by group, in float64."""
    lhs, w = np.asarray(lhs, np.float64), np.asarray(w, np.float64)
    out = np.zeros((lhs.shape[0], w.shape[2]))
    at = 0
    for g, size in enumerate(sizes):
        out[at:at + size] = lhs[at:at + size] @ w[layer * len(sizes) + g]
        at += size
    return out


SIZES = {
    "empty_one_and_around_a_tile": [0, 1, T - 1, T, T + 1],
    "all_rows_in_one_group": [0, 0, 3 * T + 5, 0, 0],
    "no_rows_at_all": [0, 0, 0, 0, 0],
    "a_few_rows_in_some": [3, 0, 2, 0, 1],
    "groups_that_end_on_tile_edges": [T, 0, 2 * T, T, 0],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(SIZES))
def test_kernel_matches_ragged_dot_and_a_numpy_loop(case, dtype):
    """Every row a group holds equals ``lax.ragged_dot``'s and the
    plain loop's; a row past the last group in a visited row tile is
    0; with no rows at all nothing is visited and nothing read."""
    sizes = SIZES[case]
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(len(case))
    total = sum(sizes)
    lhs = jnp.asarray(rng.standard_normal((total + 7, K_IN)), dt)
    w = _stack(rng, len(sizes), dtype=dt)
    sched = gm.schedule(jnp.asarray(sizes, jnp.int32),
                        gm.padded_rows(total + 7))
    n_tiles = [0 if s == 0 else
               (sum(sizes[:g]) + s - 1) // T - sum(sizes[:g]) // T + 1
               for g, s in enumerate(sizes)]
    assert int(sched.visits) == sum(n_tiles)
    got = np.asarray(_kernel(lhs, w, 0, sizes), np.float64)
    want = _numpy_loop(lhs, w, 0, sizes)
    with jax.default_matmul_precision("highest"):
        xla = np.asarray(lax.ragged_dot(
            lhs, w, jnp.asarray(sizes, jnp.int32)), np.float64)
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[:total], want[:total], atol=tol)
    np.testing.assert_allclose(got[:total], xla[:total], atol=tol)
    if total:  # the last visited tile's rows past the last group
        np.testing.assert_array_equal(got[total:gm.padded_rows(total)], 0)


@pytest.mark.parametrize("layer", [0, 1, 3])
def test_kernel_reads_its_layer_of_the_whole_stack(layer):
    """The operand is the whole ``[n * E, in, out]`` stack and a layer
    index: layer 0, a middle one and the last of n = 4 each read their
    own E matrices."""
    rng = np.random.default_rng(layer)
    sizes, n = [2, 0, 5, 1], 4
    lhs = jnp.asarray(rng.standard_normal((8, K_IN)), jnp.float32)
    w = _stack(rng, n * len(sizes))
    got = np.asarray(jax.jit(_kernel)(lhs, w, layer, jnp.asarray(sizes)))
    np.testing.assert_allclose(got[:8], _numpy_loop(lhs, w, layer, sizes),
                               atol=2e-5)


def test_two_matrices_share_one_call():
    """Gate and up in one call: the rows loaded once, a weight stream
    each — the outputs are those of two calls."""
    rng = np.random.default_rng(0)
    sizes = [T + 3, 0, 9]
    lhs = jnp.asarray(rng.standard_normal((2 * T, K_IN)), jnp.float32)
    a, b = _stack(rng, 3), _stack(rng, 3)
    sched = gm.schedule(jnp.asarray(sizes, jnp.int32), 2 * T)
    both = gm.grouped_matmul(lhs, (a, b), 0, sched)
    for one, w in zip(both, (a, b)):
        np.testing.assert_array_equal(
            np.asarray(one), np.asarray(
                gm.grouped_matmul(lhs, (w,), 0, sched)[0]))


def test_a_contraction_in_blocks_accumulates_in_f32(monkeypatch):
    """A matrix larger than a block is read in blocks of whole rows of
    ``out`` and accumulated (the path a 7168-wide contraction takes)."""
    monkeypatch.setattr(gm, "_BLOCK_BYTES", 128 * N_OUT * 4)
    assert gm.contraction_block(K_IN, N_OUT, jnp.float32) == 128
    rng = np.random.default_rng(1)
    sizes = [T - 1, 2, 0, T + 1]
    lhs = jnp.asarray(rng.standard_normal((sum(sizes), K_IN)), jnp.float32)
    w = _stack(rng, len(sizes))
    got = np.asarray(_kernel(lhs, w, 0, sizes))
    np.testing.assert_allclose(got[:sum(sizes)],
                               _numpy_loop(lhs, w, 0, sizes), atol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_rows_bits_do_not_depend_on_the_row_count(dtype):
    """The same row of the same expert among 4, 16 and 528 tokens'
    assignments (other rows, other group sizes): bit-equal — the row
    tile and the contraction's split are fixed."""
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(5)
    E, k, probe_e = 8, 2, 3
    w = _stack(rng, 2 * E, dtype=dt)
    probe = rng.standard_normal((K_IN,))
    outs = []
    for tokens in (4, 16, 528):
        picks = np.concatenate([rng.permutation(E)[:k]
                                for _ in range(tokens)])
        sizes = np.bincount(picks, minlength=E)
        sizes[probe_e] += 1
        at = int(sizes[:probe_e].sum())
        lhs = rng.standard_normal((int(sizes.sum()), K_IN))
        lhs[at] = probe
        got = _kernel(jnp.asarray(lhs, dt), w, 1, [int(s) for s in sizes])
        outs.append(np.asarray(got[at], np.float32))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


# ---------------------------------------------------------------------------
# through _moe_mlp
# ---------------------------------------------------------------------------


def _moe(rng, *, held=None, first=0, dtype=jnp.float32):
    """A spec and one layer of a 3-layer stack for ``_moe_mlp``: 8
    experts top-2 of K_IN -> 128 -> K_IN; ``held`` of them from
    ``first`` for a share."""
    from localai_tfp_tpu.models.llm_spec import LLMSpec

    E = 8
    spec = LLMSpec(
        vocab_size=64, d_model=K_IN, n_layers=3, n_heads=2, n_kv_heads=2,
        d_head=64, d_ff=128, max_position=64, n_experts=E,
        experts_per_token=2, experts_held=held or 0, experts_first=first)
    n = 3
    whole = {
        "moe_gate": _stack(rng, n * spec.n_held, K_IN, 128, dtype).reshape(
            n, spec.n_held, K_IN, 128),
        "moe_up": _stack(rng, n * spec.n_held, K_IN, 128, dtype).reshape(
            n, spec.n_held, K_IN, 128),
        "moe_down": _stack(rng, n * spec.n_held, 128, K_IN, dtype).reshape(
            n, spec.n_held, 128, K_IN)}
    lp = {"router": jnp.asarray(rng.standard_normal((K_IN, E)), dtype)}
    return spec, lp, whole


@pytest.mark.parametrize("share", [False, True],
                         ids=["every_expert_held", "a_held_share"])
def test_moe_mlp_is_the_same_on_both_routes(share):
    """``_moe_mlp`` through the kernel against the ``lax.ragged_dot``
    route: outputs and counts, with positions that carry no token (they
    sort past the last group, read no expert and come out 0 after the
    mask) and, for a share, assignments to experts held elsewhere
    (``experts_first`` > 0)."""
    rng = np.random.default_rng(7)
    spec, lp, whole = _moe(rng, **(
        dict(held=3, first=4) if share else {}))
    x = jnp.asarray(rng.standard_normal((3, 5, K_IN)), jnp.float32)
    valid = jnp.asarray(rng.random((3, 5)) < 0.7)
    for layer in (0, 2):
        want, want_n = tr._moe_mlp(spec, lp, x, valid, (whole, layer))
        got, got_n = tr._moe_mlp(spec, lp, x, valid, (whole, layer, True))
        np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)
        dead = ~np.asarray(valid)
        np.testing.assert_array_equal(np.asarray(got)[dead], 0)
    if share:  # the absent assignments are counted, not computed
        assert int(got_n[-1]) > 0


def test_moe_mlp_with_no_token_at_all_reads_no_expert():
    rng = np.random.default_rng(8)
    spec, lp, whole = _moe(rng)
    x = jnp.asarray(rng.standard_normal((2, 3, K_IN)), jnp.float32)
    got, counts = tr._moe_mlp(spec, lp, x, jnp.zeros((2, 3), bool),
                              (whole, 1, True))
    np.testing.assert_array_equal(np.asarray(got), 0)
    np.testing.assert_array_equal(np.asarray(counts), 0)


# ---------------------------------------------------------------------------
# which route
# ---------------------------------------------------------------------------


def _shape(*dims, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(dims, dtype)


@pytest.fixture
def on_a_chip(monkeypatch):
    """``expert_path`` as a TPU backend would see it."""
    monkeypatch.setattr(gm, "_interpret", lambda: False)


TRINITY = (_shape(6, 128, 2048, 1024), _shape(6, 128, 1024, 2048))
DEEPSEEK = (_shape(5, 16, 7168, 2048), _shape(5, 16, 2048, 7168))


@pytest.mark.parametrize("stacks", [TRINITY, DEEPSEEK],
                         ids=["trinity", "deepseek"])
def test_the_cells_stacks_take_the_kernel_on_a_chip(on_a_chip, stacks):
    assert gm.expert_path(stacks, jnp.bfloat16, None) == gm.GROUPED_KERNEL
    tk = [gm.contraction_block(*w.shape[-2:], w.dtype) for w in stacks]
    # Trinity's matrices are one block each, DeepSeek's four
    assert tk == ([2048, 1024] if stacks is TRINITY else [1792, 512])


@pytest.mark.parametrize("why,stacks,act,mesh", [
    ("a mesh", TRINITY, jnp.bfloat16, object()),
    ("rows of another dtype", TRINITY, jnp.float32, None),
    ("int8 stacks", (_shape(2, 8, 256, 128, dtype=jnp.int8),), jnp.int8,
     None),
    ("widths off the lane tile", (_shape(2, 8, 200, 128),), jnp.bfloat16,
     None),
    ("a block of 128 rows too wide", (_shape(2, 8, 128, 1 << 16),),
     jnp.bfloat16, None),
])
def test_what_the_kernel_does_not_cover_takes_ragged_dot(
        on_a_chip, why, stacks, act, mesh):
    assert gm.expert_path(stacks, act, mesh) == gm.RAGGED_DOT, why


def test_off_the_chip_every_stack_takes_ragged_dot():
    """The CPU backend (these tests): ``lax.ragged_dot`` stays the
    route, whatever the stack."""
    assert gm.expert_path(TRINITY, jnp.bfloat16, None) == gm.RAGGED_DOT


def test_a_model_reports_its_expert_path(on_a_chip):
    """``transformer.expert_path`` — what the engine logs and the
    worker's model info carries — from a model's own leaves: the kernel
    for an expert model of tile-friendly widths, ``ragged_dot`` under a
    mesh, None without experts."""
    from localai_tfp_tpu.models.llm_spec import LLMSpec

    kw = dict(vocab_size=64, d_model=128, n_layers=2, n_heads=2,
              n_kv_heads=2, d_head=64, d_ff=128, max_position=64)
    moe = LLMSpec(**kw, n_experts=4, experts_per_token=2)
    params = jax.eval_shape(lambda: tr.init_params(
        jax.random.PRNGKey(0), moe, jnp.bfloat16))
    assert tr.expert_path(moe, params, None) == gm.GROUPED_KERNEL
    assert tr.expert_path(moe, params, object()) == gm.RAGGED_DOT
    dense = LLMSpec(**kw)
    params = jax.eval_shape(lambda: tr.init_params(
        jax.random.PRNGKey(0), dense, jnp.bfloat16))
    assert tr.expert_path(dense, params, None) is None
