"""The barrier between the q / k / v projections and their split into
heads (models/transformer.py ``_layer_body``, PR 44) changes which
PROGRAM the compiler writes, never a value: with
``lax.optimization_barrier`` replaced by the identity — the form the
tree had before — every path through ``_layer_body`` gives the same
bits, and ``forward_train`` the same gradient.

What the barrier is FOR (no per-layer slice-out of ``wq`` / ``wk`` /
``wv`` in the programs compiled for a v5e) is held by
tests/test_compiled_for_v5e.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from localai_tfp_tpu.models import transformer as tf
from localai_tfp_tpu.models.llm_spec import spec_from_hf_config, tiny_spec
from localai_tfp_tpu.models.quant import quantize_params
from localai_tfp_tpu.models.transformer import (
    KVCache, Rows, forward_rows, forward_train, init_params,
)
from tests.test_afmoe import TINY as AFMOE
from tests.test_olmo_hybrid import TINY as OLMO_HYBRID


def _dense(dtype):
    spec = tiny_spec()
    return spec, init_params(jax.random.PRNGKey(1), spec, dtype)


def _int8():
    spec, params = _dense(jnp.bfloat16)
    return spec, quantize_params(params, embeddings=True)


def _from_config(cfg, dtype=jnp.bfloat16):
    spec = spec_from_hf_config(cfg)
    return spec, init_params(jax.random.PRNGKey(2), spec, dtype)


def _two_groups(spec, params):
    """A mixed step's pass — a decode group beside a prompt chunk — on a
    dense cache, then one decode step on what it left."""
    S, T = 3, 8
    cache = KVCache.create(spec, S + 1, 32, params["ln1_w"].dtype
                           if "ln1_w" in params else jnp.bfloat16)
    rng = np.random.default_rng(0)
    dec = Rows(jnp.asarray(rng.integers(0, 200, (S, 1)), jnp.int32),
               jnp.asarray([5, 0, 9], jnp.int32),
               slot_ids=jnp.arange(S, dtype=jnp.int32),
               q_lens=jnp.ones((S,), jnp.int32),
               live=jnp.asarray([True, False, True]))
    pre = Rows(jnp.asarray(rng.integers(0, 200, (1, T)), jnp.int32),
               jnp.zeros((1,), jnp.int32),
               slot_ids=jnp.asarray([S], jnp.int32),
               q_lens=jnp.asarray([T - 2], jnp.int32))
    (hd, hp), cache, _ = forward_rows(spec, params, (dec, pre), cache)
    (h1,), cache, _ = forward_rows(
        spec, params, (dec._replace(pos0=dec.pos0 + 1),), cache)
    return hd, hp, h1, cache.k, cache.v


def _train_grad(spec, params):
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 200, (2, 12)),
                       jnp.int32)

    def loss(p):
        lg = forward_train(spec, p, toks[:, :-1])
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(lg), toks[:, 1:, None], axis=-1))

    val, grad = jax.value_and_grad(loss)(params)
    return val, grad


CASES = {
    "dense-bf16": (lambda: _dense(jnp.bfloat16), _two_groups),
    "int8-qtensor": (_int8, _two_groups),
    "afmoe-attn-gate": (lambda: _from_config(AFMOE), _two_groups),
    "olmo-hybrid-period": (lambda: _from_config(OLMO_HYBRID), _two_groups),
    "train-gradient": (lambda: _dense(jnp.float32), _train_grad),
}


@pytest.mark.parametrize("case", CASES)
def test_the_barrier_changes_no_bit(monkeypatch, case):
    make, run = CASES[case]
    spec, params = make()
    # a fresh jit each side: the trace is what differs
    with_barrier = jax.jit(lambda p: run(spec, p))(params)
    calls = []

    def identity(x):
        calls.append(1)
        return x

    monkeypatch.setattr(lax, "optimization_barrier", identity)
    assert tf.lax is lax
    before = jax.jit(lambda p: run(spec, p))(params)
    assert calls, "_layer_body no longer passes the barrier"
    for x, y in zip(jax.tree_util.tree_leaves(with_barrier),
                    jax.tree_util.tree_leaves(before)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
