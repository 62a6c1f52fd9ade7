"""The plain numpy reference against the program's forward pass on tiny
mistral and mixtral shapes, and proof that the parity tolerance catches
a zeroed layer, a wrong rope base and a dropped expert. The checkpoint
writer's tensor names load through models/hf_loader.py. JAX is imported
inside the tests only."""

import numpy as np
import pytest

from benchmark.lib import checkpoint, reference

from . import helpers as H

TOL = 0.05  # the configuration files' parity_tol
IDS = [list(range(7, 47)), [500, 3, 3, 9, 250, 17, 101, 44, 44, 2] * 6]


_MADE: dict = {}


def _make(kind, tmp_path_factory):
    if kind not in _MADE:
        config = checkpoint.hf_config(
            H.TINY if kind == "mistral" else H.TINY_MOE)
        d = str(tmp_path_factory.mktemp(kind))
        assert checkpoint.write_hf_checkpoint(d, config, seed=3,
                                              threads=2) > 0
        _MADE[kind] = (d, config)
    return _MADE[kind]


@pytest.fixture(params=["mistral", "mixtral"])
def tiny(request, tmp_path_factory):
    return _make(request.param, tmp_path_factory)


def _program_pooled(ckpt_dir, ids_list):
    import jax.numpy as jnp

    from localai_tfp_tpu.models.hf_loader import load_params
    from localai_tfp_tpu.models.transformer import KVCache, forward_hidden

    spec, params = load_params(ckpt_dir, dtype=jnp.float32)
    out = []
    for ids in ids_list:
        cache = KVCache.create(spec, 1, 128, jnp.float32)
        hidden, _ = forward_hidden(
            spec, params, jnp.asarray([ids], jnp.int32),
            jnp.zeros((1,), jnp.int32), cache, jnp.zeros((1,), jnp.int32))
        out.append(np.asarray(hidden[0], np.float32).mean(axis=0))
    return spec, out


def test_reference_matches_the_program(tiny):
    ckpt, config = tiny
    spec, got = _program_pooled(ckpt, IDS)
    assert spec.n_layers == config["num_hidden_layers"]
    assert bool(spec.n_experts) == ("num_local_experts" in config)
    want = reference.pooled(ckpt, config, IDS)
    for g, w in zip(got, want):
        # float32 both sides: far inside the served tolerance
        assert reference.rel_l2(g, w) < 2e-4


@pytest.mark.parametrize("kind,mutate", [
    ("mistral", {"zero_layer": 1}), ("mistral", {"rope_theta": 1.0e4}),
    ("mixtral", {"zero_layer": 0}), ("mixtral", {"rope_theta": 1.0e4}),
    ("mixtral", {"drop_expert": 0})],
    ids=["mistral_zeroed_layer", "mistral_wrong_rope_base",
         "mixtral_zeroed_layer", "mixtral_wrong_rope_base",
         "mixtral_dropped_expert"])
def test_tolerance_catches_a_broken_model(kind, mutate, tmp_path_factory):
    ckpt, config = _make(kind, tmp_path_factory)
    want = reference.pooled(ckpt, config, IDS)
    broken = reference.pooled(ckpt, config, IDS, mutate)
    worst = max(reference.rel_l2(b, w) for b, w in zip(broken, want))
    assert worst > TOL, (mutate, worst)


def test_router_keeps_top_k_and_renormalises():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 8)).astype(np.float32)
    router = rng.standard_normal((4, 8)).astype(np.float32)
    eye = np.eye(8, dtype=np.float32)
    calls = []

    def experts(e):
        calls.append(e)
        # expert e returns silu(x) * x scaled by (e + 1)
        return eye, eye, eye * (e + 1)

    y = reference.moe(x, router, experts, 2)
    logits = x @ router.T
    top = np.argsort(-logits, axis=-1)[:, :2]
    base = reference.silu(x) * x
    for t in range(5):
        lg = logits[t, top[t]]
        w = np.exp(lg - lg.max())
        w /= w.sum()
        want = sum(w[j] * (top[t, j] + 1) for j in range(2)) * base[t]
        np.testing.assert_allclose(y[t], want, rtol=1e-5, atol=1e-6)
    assert set(calls) <= set(range(4))


def test_shards_read_bf16_exactly(tiny):
    ckpt, config = tiny
    sh = reference.Shards(ckpt)
    w = sh.get("model.layers.0.self_attn.q_proj.weight")
    assert w.dtype == np.float32 and w.shape == (64, 64)
    rms = float(np.sqrt(np.mean(w * w)))
    assert 0.08 < rms < 0.2  # ~ 1 / sqrt(64)
    np.testing.assert_array_equal(sh.get("model.norm.weight"),
                                  np.ones(64, np.float32))


def test_writer_knows_its_model_types(tmp_path):
    with pytest.raises(ValueError, match="no tensor-name table"):
        checkpoint.write_hf_checkpoint(
            str(tmp_path), dict(checkpoint.hf_config(H.TINY),
                                model_type="mamba"), seed=0)
    names = [n for n, _o, _i in checkpoint.LAYER_TENSORS["mixtral"]]
    assert "block_sparse_moe.gate.weight" in names
    assert "block_sparse_moe.experts.{e}.w2.weight" in names


def test_materialise_writes_once_and_keys_by_config(tmp_path):
    import json

    cfg = tmp_path / "tiny.json"
    cfg.write_text(json.dumps(H.TINY))
    said = []
    a = checkpoint.materialise(str(tmp_path / "c"), "tiny", str(cfg),
                               log=said.append)
    b = checkpoint.materialise(str(tmp_path / "c"), "tiny", str(cfg),
                               log=said.append)
    assert a["fresh"] and not b["fresh"] and len(said) == 1
    assert a["ckpt_dir"] == b["ckpt_dir"] and a["bytes"] == b["bytes"]
    yaml = open(a["models_dir"] + "/tiny.yaml").read()
    assert "embeddings: true" in yaml and "max_batch_slots: 4" in yaml
    cfg.write_text(json.dumps(dict(H.TINY, weights_seed=1)))
    c = checkpoint.materialise(str(tmp_path / "c"), "tiny", str(cfg),
                               log=said.append)
    assert c["fresh"] and c["home"] != a["home"]
