"""The plain numpy reference against the program's forward pass on tiny
shapes of every model type that has a file here (and of one that has
none: a fixture brought as a file), and proof that the parity tolerance
catches a zeroed layer, a wrong rope base and a dropped expert. The
checkpoint writer's tensor names load through models/hf_loader.py, and
what it writes for the types the parent knew is the parent's, bit for
bit. JAX is imported inside the tests only."""

import hashlib
import os

import numpy as np
import pytest

from benchmark.lib import checkpoint, models, reference

from . import helpers as H

TOL = 0.05  # the configuration files' parity_tol
IDS = [list(range(7, 47)), [500, 3, 3, 9, 250, 17, 101, 44, 44, 2] * 6]


_MADE: dict = {}
# a type -> (its tiny configuration, where its model file is: the
# benchmark's own models/, or the fixtures' the loader is pointed at)
TINIES = {"mistral": (H.TINY, H.MODELS), "mixtral": (H.TINY_MOE, H.MODELS),
          "qwen2_moe": (H.TINY_QWEN_MOE, H.FIXTURE_MODELS)}


def _make(kind, tmp_path_factory):
    if kind not in _MADE:
        config = checkpoint.hf_config(TINIES[kind][0])
        d = str(tmp_path_factory.mktemp(kind))
        with H.using_models(TINIES[kind][1]):
            assert checkpoint.write_hf_checkpoint(d, config, seed=3,
                                                  threads=2) > 0
        _MADE[kind] = (d, config)
    return _MADE[kind]


@pytest.fixture(params=sorted(TINIES))
def tiny(request, tmp_path_factory):
    with H.using_models(TINIES[request.param][1]):
        yield _make(request.param, tmp_path_factory)


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
    assert spec.n_experts == (config.get("num_local_experts")
                              or config.get("num_experts") or 0)
    assert list(spec.moe_dense_layers) == config.get("mlp_only_layers", [])
    want = reference.pooled(ckpt, config, IDS)
    for g, w in zip(got, want):
        # float32 both sides: far inside the served tolerance
        assert reference.rel_l2(g, w) < 2e-4


@pytest.mark.parametrize("kind,mutate", [
    ("mistral", {"zero_layer": 1}), ("mistral", {"rope_theta": 1.0e4}),
    ("mixtral", {"zero_layer": 0}), ("mixtral", {"rope_theta": 1.0e4}),
    ("mixtral", {"drop_expert": 0}),
    ("qwen2_moe", {"zero_layer": 0}), ("qwen2_moe", {"drop_shared": True})],
    ids=["mistral_zeroed_layer", "mistral_wrong_rope_base",
         "mixtral_zeroed_layer", "mixtral_wrong_rope_base",
         "mixtral_dropped_expert", "fixture_zeroed_dense_layer",
         "fixture_dropped_shared_expert"])
def test_tolerance_catches_a_broken_model(kind, mutate, tmp_path_factory):
    ckpt, config = _make(kind, tmp_path_factory)
    with H.using_models(TINIES[kind][1]):
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


# what models/hf_loader.py reads of a layer, per type that has a file
LOADER_READS = {
    "mistral": ["self_attn.k_proj.weight", "mlp.gate_proj.weight",
                "post_attention_layernorm.weight"],
    "mixtral": ["self_attn.o_proj.weight", "block_sparse_moe.gate.weight",
                "block_sparse_moe.experts.3.w2.weight",
                "input_layernorm.weight"],
}


@pytest.mark.parametrize("kind", ["mamba"] + sorted(LOADER_READS))
def test_a_model_type_is_a_file_found_by_name(kind, tmp_path):
    if kind not in LOADER_READS:  # no file: the error says which to bring
        with pytest.raises(FileNotFoundError,
                           match=rf"benchmark/models/{kind}\.py") as err:
            checkpoint.write_hf_checkpoint(
                str(tmp_path), dict(checkpoint.hf_config(H.TINY),
                                    model_type=kind), seed=0)
        assert "known types" in str(err.value)
        assert "mistral" in str(err.value)  # membership, never the list
        return
    # a test that names a type loads its file, from the root under test
    mod = models.load(kind, H.MODELS)
    assert all(hasattr(mod, k) for k in models.NEEDS)
    assert kind in models.known(H.MODELS)
    config = checkpoint.hf_config(TINIES[kind][0])
    rows = mod.tensors(config)
    names = [n for _s, n, _sh, _dt, _init in rows]
    assert len(set(names)) == len(names)
    for want in LOADER_READS[kind]:
        assert "model.layers.1." + want in names
    assert {"model.embed_tokens.weight", "model.norm.weight",
            "lm_head.weight"} <= set(names)
    assert {s for s, *_ in rows} == set(
        range(config["num_hidden_layers"] + 1))


# sha256 of what the PARENT's writer (commit 4b27234, lib/checkpoint.py
# with LAYER_TENSORS) wrote for helpers.TINY / TINY_MOE at seed 3, taken
# before the table moved into the model files: same draws, same order
PARENT_SHA256 = {
    "mistral": (279168, {
        "model-00001-of-00003.safetensors":
            "f9658f01284f99f4f92d858a58db5f6eea6374a1380a815a207843a2325dcf0a",
        "model-00002-of-00003.safetensors":
            "de72fb64bd1ae00cfe1b11402b36fcf6152ea683703349023013484856d56063",
        "model-00003-of-00003.safetensors":
            "1f560db22e49be23bad07f98278d94546b18c334b5c3df92a7785297db7428a3",
        "model.safetensors.index.json":
            "1591ed7b7747c0be35d6052c34ef4916baa22a47b5187648b9da603bbe90a13d",
        "config.json":
            "dee30b1880684e39f3cff5090088a1d1b00b07e1f00b3976b25a4943d0e61f14",
    }),
    "mixtral": (575104, {
        "model-00001-of-00003.safetensors":
            "659226df62d8feba979a37a271f95c9da249f032f882e9007b3431e5ce531e79",
        "model-00002-of-00003.safetensors":
            "579fcf260441d9251c24df0858d2d27c0dbc0df81039f62091b3867dff8670af",
        "model-00003-of-00003.safetensors":
            "1f560db22e49be23bad07f98278d94546b18c334b5c3df92a7785297db7428a3",
        "model.safetensors.index.json":
            "7161111fa998d02d958385f079bb631deea04146921517cf4bd7e31e16879d04",
        "config.json":
            "21deb55f98fcc60af5ca28ac120ea618baa1d3f3627544aec9127b580c21828a",
    }),
}


@pytest.mark.parametrize("kind", sorted(PARENT_SHA256))
def test_checkpoint_is_the_parents_bit_for_bit(kind, tmp_path):
    total, want = PARENT_SHA256[kind]
    config = checkpoint.hf_config(TINIES[kind][0])
    assert "expect" not in checkpoint.hf_config(
        dict(TINIES[kind][0], expect={"attention_path": "x"}))
    assert checkpoint.write_hf_checkpoint(
        str(tmp_path), config, seed=3, threads=2) == total
    got = {}
    for fn in os.listdir(tmp_path):
        with open(tmp_path / fn, "rb") as f:
            got[fn] = hashlib.sha256(f.read()).hexdigest()
    assert got == want
    assert checkpoint.WRITER_VERSION == "1"


def test_writer_says_a_share_of_an_expert_layer(tmp_path):
    """What the program cannot load yet, so the writer alone is held to
    it: an F32 vector, a router as wide as the published expert count
    over the 4 experts held here with ids 8-11, a 1-D tensor that is no
    layer norm, a leading dense layer — read back exactly."""
    with H.using_models(H.FIXTURE_MODELS):
        total = checkpoint.write_hf_checkpoint(str(tmp_path), H.TINY_HELD,
                                               seed=5, threads=2)
    sh = reference.Shards(str(tmp_path))
    lp = "model.layers.1.mlp."
    held = sorted(int(n.split(".")[5]) for n in sh.weight_map
                  if n.startswith(lp + "experts."))
    assert held == [8, 9, 10, 11]
    assert not any("experts" in n for n in sh.weight_map
                   if n.startswith("model.layers.0."))
    router = sh.get(lp + "gate.weight")
    assert router.shape == (32, 64) and router.dtype == np.float32
    bias = sh.get(lp + "gate.e_score_correction_bias")
    np.testing.assert_array_equal(bias, np.zeros(32, np.float32))
    header, _ = sh._header(sh.weight_map[lp + "gate.scale"])
    assert header[lp + "gate.scale"]["dtype"] == "F32"
    assert header[lp + "gate.weight"]["dtype"] == "BF16"
    # an F32 tensor holds the bf16 draw exactly: the same draws, read
    # back through either dtype, are the same values
    scale = sh.get(lp + "gate.scale")
    rng = np.random.default_rng([5, 1])
    checkpoint._draw(rng, (16,), "BF16", "ones")  # kv_a_layernorm: no draw
    want_router = checkpoint._draw(rng, (32, 64), "BF16", "matrix")
    checkpoint._draw(rng, (32,), "F32", "zeros")
    want_scale = checkpoint._draw(rng, (64,), "BF16", "matrix")
    as_f32 = lambda bits: (bits.astype(np.uint32) << 16).view(np.float32)  # noqa: E731
    np.testing.assert_array_equal(router, as_f32(want_router))
    np.testing.assert_array_equal(scale, as_f32(want_scale))
    assert 0.08 < float(np.sqrt(np.mean(scale * scale))) < 0.2
    np.testing.assert_array_equal(
        sh.get("model.layers.0.self_attn.kv_a_layernorm.weight"),
        np.ones(16, np.float32))
    sizes = {"BF16": 2, "F32": 4}
    assert total == sum(
        int(np.prod(shape)) * sizes[dt] for _s, _n, shape, dt, _i in
        models.load("held_experts", H.FIXTURE_MODELS).tensors(H.TINY_HELD))


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
