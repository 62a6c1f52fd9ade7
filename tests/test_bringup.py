"""Bring-up contracts (PR 22): what must hold for the chip smoke to mean
something — the smoke refuses a CPU, /backend/monitor names the device
and attention path, the kernel-check entry reports failures through its
exit code, and a foreign native binary is never what runs."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    """JAX_PLATFORMS=cpu: non-zero exit within seconds, no summary on
    stdout, nothing started or written."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=60)
    assert time.monotonic() - t0 < 30
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_chip_smoke_alone_fails_without_the_package(tmp_path):
    """In a directory holding chip_smoke.py and nothing else of the
    repo it exits non-zero and prints no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_chip_smoke_last_line_is_the_verdict_only():
    """The last stdout line carries exactly ok + device{platform, kind,
    count}; the report with everything else is the line before it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    probe = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
             "jax": "0.9.0", "jaxlib": "0.9.0", "libtpu": "0.0.34"}
    line = json.loads(chip_smoke.verdict_line(True, probe))
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert list(line) == ["ok", "device"]
    assert type(line["device"]["count"]) is int
    assert json.loads(chip_smoke.verdict_line(False, probe))["ok"] is False
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        src = f.read()
    # nothing is printed to stdout after the verdict
    tail = src.split("print(verdict_line(", 1)[1]
    assert "print(" not in tail.replace("print(verdict_line(", "")


def test_engine_stats_names_device_and_attention_path():
    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
    from localai_tfp_tpu.models.llm_spec import tiny_spec
    from localai_tfp_tpu.models.transformer import init_params
    from localai_tfp_tpu.workers.llm import JaxLLMBackend

    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    eng = LLMEngine(spec, params, tk, n_slots=2, max_seq=64,
                    prefill_buckets=(8,), cache_dtype=jnp.float32,
                    autostart=False)
    try:
        backend = JaxLLMBackend()
        backend.engine = eng
        stats = backend.engine_stats()
        assert stats["platform"] == "cpu"
        assert stats["device_kind"] == "cpu"
        assert stats["paged"] is True
        # no Mosaic on CPU: the XLA route, and the monitor says why
        assert stats["attention_path"] == "paged_xla_gather"
        assert "Mosaic" in stats["kernel_ineligible"]
        assert stats["expert_path"] is None  # a dense model
        json.dumps(stats)  # what /backend/monitor serializes
    finally:
        eng.close()


def test_kernel_check_entry_exit_code(monkeypatch, capsys):
    """The module entry prints the JSON and turns ok:false into a
    non-zero exit; a crashing check propagates (never {"ok": false})."""
    from localai_tfp_tpu.ops import kernel_check as kc

    monkeypatch.setattr(kc, "run_kernel_checks",
                        lambda geom: {"ok": True, "geometry": geom.page})
    assert kc.main(["--small", "--page", "32"]) == 0
    assert json.loads(capsys.readouterr().out) == {
        "ok": True, "geometry": 32}
    monkeypatch.setattr(kc, "run_kernel_checks",
                        lambda geom: {"ok": False, "failed": ["x"]})
    assert kc.main([]) == 1

    def boom(geom):
        raise RuntimeError("Mosaic said no")

    monkeypatch.setattr(kc, "run_kernel_checks", boom)
    with pytest.raises(RuntimeError, match="Mosaic said no"):
        kc.main([])


def test_foreign_native_library_is_never_loaded(tmp_path, monkeypatch):
    """A build dir carrying someone else's binaries (copied from another
    machine, left by an older Makefile) still ends with a library built
    from the tracked source: the loader only ever opens the file named
    after the current source hash."""
    from localai_tfp_tpu import native

    if not native.build():
        pytest.skip("no C++ toolchain available")
    build_dir = tmp_path / "build"
    build_dir.mkdir()
    for name in ("libgbnf.so", "libgbnf-0123456789ab.so"):
        (build_dir / name).write_bytes(b"not an ELF file")
    monkeypatch.setattr(native, "BUILD_DIR", str(build_dir))
    monkeypatch.setattr(native, "_cache", {})
    lib = native.load_library("gbnf", auto_build=True)
    assert lib is not None
    assert lib._name == os.path.join(
        str(build_dir), f"libgbnf-{native.source_tag()}.so")
    # the foreign files are gone, and no -march=native went into ours
    assert sorted(os.listdir(build_dir)) == sorted(
        f"lib{n}-{native.source_tag()}.so" for n in ("gbnf", "vecstore"))
    with open(os.path.join(native._DIR, "Makefile")) as f:
        assert "-march=native" not in f.read().split("CXXFLAGS ?=")[1] \
            .splitlines()[0]
