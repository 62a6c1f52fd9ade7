"""Config system tests (ref test model: core/config/backend_config_test.go)."""

import os
import textwrap

import pytest

from localai_tfp_tpu.config import ConfigLoader, ModelConfig, Usecase


def test_defaults_applied():
    cfg = ModelConfig.from_dict({"name": "m", "backend": "jax-llm"})
    assert cfg.parameters.top_k == 40
    assert cfg.parameters.top_p == 0.95
    assert cfg.parameters.temperature == 0.9
    assert cfg.parameters.max_tokens == 2048
    assert cfg.context_size == 4096


def test_reference_yaml_compat(tmp_path):
    # A LocalAI-style model YAML must load unchanged.
    (tmp_path / "gpt4.yaml").write_text(
        textwrap.dedent(
            """
            name: gpt-4
            backend: llama
            parameters:
              model: testmodel.ggml
              temperature: 0.2
              top_p: 0.8
            context_size: 2048
            stopwords: ["<|im_end|>"]
            gpu_layers: 99      # CUDA-only knob: accepted, ignored
            mmap: true
            template:
              chat: chat_tmpl
            """
        )
    )
    loader = ConfigLoader(tmp_path)
    assert loader.load_configs_from_path() == 1
    cfg = loader.get("gpt-4")
    assert cfg is not None
    assert cfg.model == "testmodel.ggml"
    assert cfg.parameters.temperature == 0.2
    assert cfg.stopwords == ["<|im_end|>"]
    assert cfg.template.chat == "chat_tmpl"
    assert cfg.extra.get("gpu_layers") == 99


def test_multidoc_yaml(tmp_path):
    (tmp_path / "all.yaml").write_text("name: a\n---\nname: b\n")
    loader = ConfigLoader(tmp_path)
    assert loader.load_configs_from_path() == 2
    assert loader.names() == ["a", "b"]


def test_usecase_filtering():
    llm = ModelConfig.from_dict({"name": "l", "backend": "jax-llm"})
    emb = ModelConfig.from_dict({"name": "e", "backend": "sentencetransformers"})
    img = ModelConfig.from_dict({"name": "i", "backend": "diffusers"})
    assert llm.has_usecase(Usecase.CHAT)
    assert not llm.has_usecase(Usecase.IMAGE)
    assert emb.has_usecase(Usecase.EMBEDDINGS)
    assert not emb.has_usecase(Usecase.CHAT)
    assert img.has_usecase(Usecase.IMAGE)


def test_known_usecases_override():
    cfg = ModelConfig.from_dict(
        {"name": "x", "backend": "jax-llm", "known_usecases": ["chat"]}
    )
    assert cfg.has_usecase(Usecase.CHAT)
    assert not cfg.has_usecase(Usecase.COMPLETION)


def test_resolve_and_default(tmp_path):
    loader = ConfigLoader(tmp_path)
    loader.load_config_dict({"name": "only", "backend": "jax-llm"})
    assert loader.resolve(None, Usecase.CHAT).name == "only"
    assert loader.resolve("only").name == "only"
    assert loader.resolve("missing") is None


def test_path_traversal_rejected(tmp_path):
    loader = ConfigLoader(tmp_path)
    try:
        loader.load_config_dict(
            {"name": "evil", "parameters": {"model": "../../etc/passwd"}}
        )
        raised = False
    except ValueError:
        raised = True
    assert raised


def test_sampling_merge():
    cfg = ModelConfig.from_dict(
        {"name": "m", "parameters": {"temperature": 0.1, "top_k": 5}}
    )
    merged = cfg.parameters.merged_with({"temperature": 0.7, "top_k": None})
    assert merged.temperature == 0.7
    assert merged.top_k == 5


def test_app_config_from_env(monkeypatch):
    """LOCALAI_* env parsing incl. galleries/preload (the run command's
    env surface — ref: core/cli/run.go env-bound flags)."""
    from localai_tfp_tpu.config.app_config import ApplicationConfig

    monkeypatch.setenv("LOCALAI_MODELS_PATH", "/mp")
    monkeypatch.setenv("LOCALAI_GALLERIES",
                       '[{"name": "g", "url": "file:///idx.yaml"}]')
    monkeypatch.setenv("LOCALAI_PRELOAD_MODELS", "m1, m2")
    monkeypatch.setenv("LOCALAI_CONTEXT_SIZE", "2048")
    monkeypatch.setenv("LOCALAI_API_KEY", "k1,k2")
    cfg = ApplicationConfig.from_env()
    assert cfg.models_path == "/mp"
    assert cfg.galleries == [{"name": "g", "url": "file:///idx.yaml"}]
    assert cfg.preload_models == ["m1", "m2"]
    assert cfg.context_size == 2048
    assert cfg.api_keys == ["k1", "k2"]


@pytest.fixture
def restore_cache_config():
    """Tests below move jax's cache directory; put it back."""
    import jax

    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", old_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      old_min)


def test_compile_cache_default_is_fixed_in_checkout(
        tmp_path, monkeypatch, restore_cache_config):
    """No JAX_COMPILATION_CACHE_DIR: the server's startup points the
    persistent cache at <checkout>/.jax_cache — the same place on every
    start (a moving directory never hits)."""
    import jax

    from localai_tfp_tpu.config.app_config import ApplicationConfig
    from localai_tfp_tpu.server.state import Application
    from localai_tfp_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.resolve() == (
        os.path.join(repo, ".jax_cache"), False)
    app = Application(ApplicationConfig(
        models_path=str(tmp_path / "models"),
        generated_content_dir=str(tmp_path / "gen"),
        upload_dir=str(tmp_path / "up"),
        config_dir=str(tmp_path / "conf"),
        state_dir=str(tmp_path / "run"),
    ))
    try:
        app.startup()
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        app.shutdown()


def test_compile_cache_env_wins(tmp_path, monkeypatch,
                                restore_cache_config):
    """JAX_COMPILATION_CACHE_DIR set: jax's own reading of it stands and
    the helper sets no other directory."""
    import jax

    from localai_tfp_tpu.utils import compile_cache

    placed = str(tmp_path / "placed")
    monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    # jax read the variable when it was imported; stand in for that
    jax.config.update("jax_compilation_cache_dir", placed)
    assert compile_cache.resolve() == (placed, True)
    assert compile_cache.configure() == placed
    assert jax.config.jax_compilation_cache_dir == placed


def test_no_other_compile_cache_setter():
    """One rule, one place: nothing outside utils/compile_cache.py (and
    the tests that save/restore it) sets jax_compilation_cache_dir, and
    the old names are gone."""
    import re

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setter = re.compile(
        r"""update\(\s*["']jax_compilation_cache_dir["']""")
    # (spelled in pieces so a grep of the tree for the old names stays
    # empty)
    gone = re.compile("localai_" + "xla|LOCALAI_" + "COMPILATION_CACHE_DIR"
                      + r"|compilation_cache_dir\s*=")
    offenders = []
    for base, dirs, files in os.walk(repo):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "chiprun_out"]
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(base, fn)
            rel = os.path.relpath(path, repo)
            with open(path) as f:
                text = f.read()
            if rel.startswith("tests" + os.sep):
                continue  # save/restore and this scan's own patterns
            if rel != os.path.join("localai_tfp_tpu", "utils",
                                   "compile_cache.py") \
                    and setter.search(text):
                offenders.append(rel + ": sets the cache dir")
            if gone.search(text):
                offenders.append(rel + ": old cache name")
    assert not offenders, offenders
