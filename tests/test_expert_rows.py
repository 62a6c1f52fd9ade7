"""The dispatch around the grouped matmul of an expert layer that holds
a share (ops/expert_rows.py), interpreted on the CPU at tiny widths: the
gather's first H rows bit for bit, the combine against XLA's mask,
un-sort and sum, a token's bits whatever step it rides in, rows past H
that hold NaN and are read by nobody, ``_moe_mlp`` through both
dispatches, and which dispatch the code picks from what it can see. A
time is a chip's (``kernel_check --sweep --dispatch``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.models import transformer as tr
from localai_tfp_tpu.ops import expert_rows as er
from localai_tfp_tpu.ops import grouped_matmul as gm
from tests.test_grouped_matmul import K_IN, _moe

K, D = 8, 256


def _step(rng, tokens, held, published, picks=None):
    """A step's routing as ``_moe_mlp`` sorts it: ``tokens`` tokens
    pick K distinct experts of ``published``, 0 .. held - 1 are here.
    ``picks`` {token: expert ids} overrides a token's choice.
    -> (src [R] the token of sorted row r, order [N * K], H)."""
    ids = np.stack([rng.permutation(published)[:K] for _ in range(tokens)])
    for n, own in (picks or {}).items():
        ids[n] = own
    flat = np.where(ids < held, ids, held).reshape(-1)
    order = np.argsort(flat, kind="stable").astype(np.int32)
    rows = gm.padded_rows(tokens * K)
    src = np.pad(order, (0, rows - tokens * K)) // K
    return src.astype(np.int32), order, int(np.sum(flat < held))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("held_rows", [0, 1, 127, 128, 129, 48 * K])
def test_the_gather_fills_the_rows_that_exist_bit_for_bit(held_rows, dtype):
    """Rows r < H are ``x[order[r] // K]`` whatever H is against the
    tile edges; H is the kernel's operand, not the routing's count: the
    rows the bound leaves out are not compared (they may hold
    anything)."""
    rng = np.random.default_rng(held_rows)
    N = 48
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.dtype(dtype))
    src, _, _ = _step(rng, N, 4, 16)
    got = er.gather_rows(x, jnp.asarray(src), held_rows)
    assert got.shape == (gm.padded_rows(N * K), D) and got.dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(got[:held_rows].astype(jnp.float32)),
        np.asarray(x.astype(jnp.float32))[src[:held_rows]])


def _xla_combine(y, order, w, held_rows):
    """The parent's form: mask, un-sort, weighted sum over k."""
    NK = order.shape[0]
    y = jnp.where(jnp.arange(NK)[:, None] < held_rows, y[:NK], 0)
    inv = jnp.zeros((NK,), jnp.int32).at[order].set(jnp.arange(NK))
    return jnp.einsum("nkd,nk->nd",
                      y[inv].reshape(-1, K, D).astype(jnp.float32), w)


@pytest.mark.parametrize("tokens,held,published", [
    (4, 4, 16), (16, 4, 64), (40, 1, 64), (40, 16, 16), (528, 2, 32)],
    ids=["4_tokens", "16_tokens", "nearly_nothing_held",
         "every_assignment_held", "528_tokens"])
def test_the_combine_is_xlas_mask_unsort_and_sum(tokens, held, published):
    rng = np.random.default_rng(tokens + held)
    src, order, H = _step(rng, tokens, held, published)
    y = jnp.asarray(rng.standard_normal((src.shape[0], D)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(0.05, 0.4, (tokens, K)), jnp.float32)
    got = er.combine_rows(y, jnp.asarray(src), w.reshape(-1)[order], H,
                          tokens)
    want = _xla_combine(y, jnp.asarray(order), w, H)
    assert got.shape == (tokens, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    if held == published:
        assert H == tokens * K
    # a token none of whose experts is here is exactly 0
    lonely = np.setdiff1d(np.arange(tokens), src[:H])
    np.testing.assert_array_equal(np.asarray(got)[lonely], 0)


def test_a_step_without_a_held_row_is_zeros():
    rng = np.random.default_rng(3)
    src, order, _ = _step(rng, 16, 4, 16)
    y = jnp.full((src.shape[0], D), jnp.nan, jnp.bfloat16)
    got = er.combine_rows(y, jnp.asarray(src), jnp.ones((16 * K,)), 0, 16)
    np.testing.assert_array_equal(np.asarray(got), 0)


@pytest.mark.parametrize("own", [(0, 3, 9, 20, 21, 22, 23, 24),
                                 (1, 2, 3, 0, 30, 31, 29, 28),
                                 (8, 9, 10, 11, 12, 13, 14, 15)],
                         ids=["two_held", "four_held_out_of_order",
                              "none_held"])
def test_a_tokens_bits_do_not_depend_on_the_step_it_rides_in(own):
    """The same token — its K picks, its weights, its experts' output
    rows — among 4, 16 and 528 tokens that route as they like, at
    another index each time: gathered and combined to the same bits
    (each row moves alone; a token's sum runs over its own held
    experts in the order of their ids)."""
    rng = np.random.default_rng(11)
    held, published = 4, 32
    x_probe = rng.standard_normal((D,))
    y_probe = rng.standard_normal((K, D))  # by pick, as its experts answer
    w_probe = rng.uniform(0.05, 0.4, (K,))
    outs, rows_in = [], []
    for tokens, at in ((4, 2), (16, 0), (528, 301)):
        src, order, H = _step(rng, tokens, held, published, {at: own})
        x = rng.standard_normal((tokens, D))
        x[at] = x_probe
        xs = er.gather_rows(jnp.asarray(x, jnp.bfloat16), jnp.asarray(src),
                            H)
        mine = np.flatnonzero(src[:H] == at)  # its sorted rows
        assert len(mine) == sum(e < held for e in own)
        rows_in.append(np.asarray(xs.astype(jnp.float32))[mine])
        y = rng.standard_normal((src.shape[0], D))
        w = rng.uniform(0.05, 0.4, (tokens, K))
        w[at] = w_probe
        for r in mine:
            y[r] = y_probe[order[r] % K]
        got = er.combine_rows(jnp.asarray(y, jnp.bfloat16), jnp.asarray(src),
                              jnp.asarray(w, jnp.float32).reshape(-1)[order],
                              H, tokens)
        outs.append(np.asarray(got)[at])
    for other_in, other in zip(rows_in[1:], outs[1:]):
        np.testing.assert_array_equal(rows_in[0], other_in)
        np.testing.assert_array_equal(outs[0], other)
    if not any(e < held for e in own):
        np.testing.assert_array_equal(outs[0], 0)


# ---------------------------------------------------------------------------
# through _moe_mlp
# ---------------------------------------------------------------------------


def _share_layer(seed, held=3, first=4, shape=(3, 5)):
    rng = np.random.default_rng(seed)
    spec, lp, whole = _moe(rng, held=held, first=first)
    x = jnp.asarray(rng.standard_normal((*shape, K_IN)), jnp.float32)
    valid = jnp.asarray(rng.random(shape) < 0.7)
    return spec, lp, whole, x, valid


@pytest.mark.parametrize("first", [0, 4, 5])
@pytest.mark.parametrize("masked", [False, True],
                         ids=["every_position_a_token", "some_without"])
def test_moe_mlp_with_a_share_through_both_dispatches(monkeypatch, first,
                                                      masked):
    """The same layer, the same inputs, the repo's grouped kernel
    multiplying both times: the row kernels against XLA's gather, mask
    and un-sort (what a step whose rows do not fit still takes)."""
    spec, lp, whole, x, valid = _share_layer(20 + first, first=first)
    valid = valid if masked else None
    assert tr.held_rows_dispatch(spec, True, 15)
    got, got_n = tr._moe_mlp(spec, lp, x, valid, (whole, 1, True))
    with monkeypatch.context() as mp:
        mp.setattr(er, "fits", lambda n, d: False)
        assert not tr.held_rows_dispatch(spec, True, 15)
        want, want_n = tr._moe_mlp(spec, lp, x, valid, (whole, 1, True))
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    if masked:
        np.testing.assert_array_equal(np.asarray(got)[~np.asarray(valid)], 0)


def test_rows_past_the_held_ones_may_hold_anything(monkeypatch):
    """Every row from H on of what goes into and comes out of the
    grouped matmuls — xs, g, u, their product, y — set to NaN: the
    layer's output does not change, because nothing reads them."""
    spec, lp, whole, x, valid = _share_layer(31, shape=(4, 40))
    want, _ = tr._moe_mlp(spec, lp, x, valid, (whole, 0, True))
    real = gm.grouped_matmul
    seen = []

    def poisoned(lhs, mats, layer, sched):
        past = jnp.arange(lhs.shape[0])[:, None] >= sched.offsets[-1]
        seen.append(int(jnp.sum(past)))
        outs = real(jnp.where(past, jnp.nan, lhs), mats, layer, sched)
        return tuple(jnp.where(past, jnp.nan, o) for o in outs)

    monkeypatch.setattr(gm, "grouped_matmul", poisoned)
    got, _ = tr._moe_mlp(spec, lp, x, valid, (whole, 0, True))
    assert len(seen) == 2 and min(seen) > 128  # whole tiles of them
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("held,kernel,tokens,want", [
    (3, True, 528, True),  # a share on the grouped kernel
    (3, False, 528, False),  # the CPU, a mesh, lax.ragged_dot
    (0, True, 528, False),  # every expert held: every row exists
    (3, True, 1 << 20, False),  # token rows that do not fit VMEM
])
def test_which_dispatch(held, kernel, tokens, want):
    spec, _, _ = _moe(np.random.default_rng(0), held=held or None)
    assert tr.held_rows_dispatch(spec, kernel, tokens) is want


def test_the_cells_steps_fit():
    """DeepSeek-V3's served steps at the published width: 16 decode
    rows, and a 512-token prompt row beside them."""
    assert er.fits(16, 7168) and er.fits(528, 7168)
    assert not er.fits(4096, 7168)


def test_the_kernels_names_are_not_the_grouped_matmuls():
    """``benchmark/models/*.py`` find the grouped matmul by the prefix
    ``ragged-dot``: the row kernels must stay out of those readers."""
    for name in (er.GATHER_NAME, er.COMBINE_NAME):
        assert not name.startswith("ragged-dot")
        assert not name.startswith(gm.KERNEL_NAME)
