"""The one rule for where compiled programs are kept.

Every entry point that compiles serving executables (the server, the
chip smoke, bench.py, the profiling tools) calls :func:`configure`
before its first compile, so all of them share one persistent
compilation cache and the warmup-reuse markers that live beside it
(engine ``_warmup_marker_path``):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
  (or anywhere else in the tree) sets another directory, so whoever
  launches the process decides where the cache goes.
- not set: ``<checkout>/.jax_cache`` (gitignored). The path is part of
  how a later process finds the cache again, so it is a fixed place —
  never a temp name, a pid or a time.

Turning the cache OFF is JAX's own switch (``JAX_ENABLE_COMPILATION_
CACHE=false``, which tests/conftest.py sets); this module never reads
or overrides it.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# localai_tfp_tpu/utils/compile_cache.py -> the checkout root
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_dir() -> str:
    """The fixed in-checkout location used when the environment names
    none."""
    return os.path.join(_CHECKOUT, ".jax_cache")


def resolve() -> tuple[str, bool]:
    """(cache directory, whether the environment chose it) — pure path
    logic, importable without JAX (chip_smoke.py's orchestrator reports
    it without ever touching the chip)."""
    env = os.environ.get(ENV_VAR, "")
    return (env, True) if env else (default_dir(), False)


def configure() -> str:
    """Point JAX's persistent compilation cache at the resolved
    directory and cache every executable, however quick its compile (a
    warm start must find the whole dispatch-variant set, small programs
    included). Returns the directory in effect."""
    import jax

    path, from_env = resolve()
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
