"""Subprocess isolation: a wedged backend load must be reclaimable by
killing the child OS process, with the parent still serving (VERDICT r3
next #7; ref: pkg/model/process.go:21-61 process stop semantics)."""

import os
import sys
import time

import pytest

from localai_tfp_tpu.config.model_config import ModelConfig
from localai_tfp_tpu.engine.loader import (
    ModelLoader,
    register_default_backends,
)


def _cfg(name="iso"):
    return ModelConfig.from_dict({
        "name": name,
        "backend": "jax-llm",
        "isolation": "subprocess",
        "parameters": {"model": "tiny-random"},
        "context_size": 128,
    })


def test_wedged_load_is_killed_and_parent_survives(tmp_path):
    """A child that never becomes ready (hung compile stand-in) must be
    SIGKILLed at load_timeout, fail THIS load only, and leave the loader
    able to serve other models."""
    register_default_backends()
    loader = ModelLoader(models_path=str(tmp_path))
    cfg = _cfg()
    # test hook: the child is a process that sleeps forever and never
    # serves /readyz — exactly what a wedged XLA compile looks like
    cfg.extra["_argv"] = [sys.executable, "-c",
                          "import time; time.sleep(600)"]
    cfg.extra["load_timeout_s"] = 3.0
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="wedged"):
        loader.load(cfg)
    assert time.monotonic() - t0 < 30
    # the wedged child is dead: no process holds the tmp dir open
    # (shutdown() killed the process group)
    assert loader.get_loaded("iso") is None

    # parent keeps serving: an in-process model loads fine afterwards
    from localai_tfp_tpu.workers.base import (
        Backend, ModelLoadOptions, Result,
    )
    from localai_tfp_tpu.engine.loader import registry

    class OkBackend(Backend):
        def load_model(self, opts: ModelLoadOptions) -> Result:
            return Result(True, "ok")

        def health(self) -> bool:
            return True

    registry.register("okb", OkBackend)
    ok_cfg = ModelConfig.from_dict({"name": "ok", "backend": "okb",
                                    "parameters": {"model": "x"}})
    assert loader.load(ok_cfg) is not None
    loader.stop_all()


def test_shutdown_kills_child_process_group(tmp_path):
    """shutdown() must take down a live child (watchdog reclaim path)."""
    from localai_tfp_tpu.workers.subprocess_worker import SubprocessBackend
    from localai_tfp_tpu.workers.base import ModelLoadOptions

    b = SubprocessBackend()
    res = b.load_model(ModelLoadOptions(
        model="m", model_path=str(tmp_path),
        extra={"_argv": [sys.executable, "-c",
                         "import time; time.sleep(600)"],
               "load_timeout_s": 1.0,
               "_cfg_raw": {"name": "m"}},
    ))
    assert not res.success  # never served /readyz
    assert b.proc is None  # reclaimed


@pytest.mark.slow
def test_isolated_model_serves_end_to_end(tmp_path):
    """Full path: isolation: subprocess boots a real child server with a
    tiny model; the parent proxies a completion through it; shutdown
    kills the child."""
    from transformers import LlamaConfig, LlamaForCausalLM

    register_default_backends()
    models = tmp_path / "models"
    models.mkdir()
    LlamaForCausalLM(LlamaConfig(
        vocab_size=300, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128,
    )).save_pretrained(models / "llm-ckpt", safe_serialization=True)
    cfg = ModelConfig.from_dict({
        "name": "iso-e2e",
        "backend": "jax-llm",
        "isolation": "subprocess",
        "parameters": {"model": "llm-ckpt"},
        "context_size": 128,
        "max_batch_slots": 2,
        "dtype": "float32",
    })
    cfg.extra["load_timeout_s"] = 240.0
    loader = ModelLoader(models_path=str(models))
    backend = loader.load(cfg)
    try:
        pid = backend.proc.pid
        assert backend.health()
        from localai_tfp_tpu.workers.base import PredictOptions

        reply = backend.predict(PredictOptions(prompt="hello", tokens=4))
        assert not reply.error
        assert isinstance(reply.message, str)
    finally:
        loader.stop_all()
    # child really died
    for _ in range(50):
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            break
        time.sleep(0.1)
    else:
        raise AssertionError("child process still alive after shutdown")


def test_jax_backend_refused_when_parent_holds_the_chip(tmp_path,
                                                        monkeypatch):
    """On a TPU host this server process owns the chip, so a child
    running a jax-* backend could never initialize: the load must fail
    at once with a message that says why — not hang for load_timeout_s.
    Backends that need no chip, and CPU hosts, pass the fence."""
    import jax

    from localai_tfp_tpu.workers import subprocess_worker as sw

    class FakeTPU:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert sw._chip_held_by_parent("jax-llm") == ""  # CPU host: fine
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTPU()])
    assert sw._chip_held_by_parent("remote-openai") == ""
    assert "one process at a time" in sw._chip_held_by_parent("llama")

    register_default_backends()
    loader = ModelLoader(models_path=str(tmp_path))
    cfg = _cfg()
    cfg.extra["load_timeout_s"] = 600.0
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="holds its TPU"):
        loader.load(cfg)
    assert time.monotonic() - t0 < 5
