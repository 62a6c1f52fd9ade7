"""What the harness knows of a model type is one file, found by name:

``benchmark/models/<model_type>.py`` — for the ``model_type`` of a
configuration file, as ``layer_metrics/<name>.py`` is for a metric. A
later PR that brings a configuration of a new type brings that file and
edits nothing that is here. The file holds:

  ATTENTION_KERNELS   op-name prefixes of the type's attention calls in
                      a capture, one call per layer per token-step
                      (lib/trace.py counts a decode program's steps by
                      them)
  tensors(config)     every tensor of the checkpoint, in the order the
                      writer draws them, as (shard, name, shape, dtype,
                      init): shard = the layer's index, or
                      num_hidden_layers for the globals; dtype "BF16" |
                      "F32"; init "matrix" (random sign and mantissa,
                      rms 1/sqrt(shape[-1])) | "embed" (rms 0.02) |
                      "ones" | "zeros". What a layer holds is decided
                      per index there: leading dense layers, extra
                      norms and vectors, a router as wide as the
                      published expert count over the experts held
                      here, expert ids that start anywhere
  forward_hidden(shards, config, ids_list, mutate=None)
                      the plain float32 forward pass to the final norm
                      (lib/reference.py has the parts), -> [T_i, D] per
                      sequence; ``mutate`` breaks it for the tests
  decode_weight_bytes(config, rows), kv_bytes_per_token(config, layers=None)
                      what a decode step HAS to read and what a cached
                      token holds (lib/roofline.py dispatches to them;
                      the parameter counts behind them are the file's
                      own business)

Model files are looked for in the ``models/`` beside this ``lib/``
unless ``use()`` names another (run.py: the checkout's own, so a temp
copy of the benchmark brings its own types).
"""

from __future__ import annotations

import importlib.util
import os

NEEDS = ("ATTENTION_KERNELS", "tensors", "forward_hidden",
         "decode_weight_bytes", "kv_bytes_per_token")

_HERE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "models")
_dir = _HERE
_loaded: dict = {}


def use(models_dir: "str | None") -> str:
    """Look for model files in ``models_dir`` from now on (None: the
    directory beside this lib); -> where they were looked for before."""
    global _dir
    before, _dir = _dir, models_dir or _HERE
    return before


def known(models_dir: "str | None" = None) -> list:
    try:
        names = os.listdir(models_dir or _dir)
    except OSError:
        return []
    return sorted(n[:-3] for n in names
                  if n.endswith(".py") and not n.startswith("_"))


def _path(model_type: str, models_dir: "str | None") -> str:
    return os.path.join(models_dir or _dir, f"{model_type}.py")


def find(model_type: str, models_dir: "str | None" = None) -> "str | None":
    p = _path(model_type, models_dir)
    return p if os.path.exists(p) else None


def load(model_type: str, models_dir: "str | None" = None):
    """The model file of ``model_type`` as a module."""
    path = _path(model_type, models_dir)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no model file for model_type {model_type!r}: {path} is "
            f"missing; known types: {known(models_dir)}")
    if path not in _loaded:
        spec = importlib.util.spec_from_file_location(
            "bm_model_" + model_type.replace("-", "_").replace(".", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        lacks = [k for k in NEEDS if not hasattr(mod, k)]
        if lacks:
            raise AttributeError(f"{path} lacks {lacks}")
        _loaded[path] = mod
    return _loaded[path]


def of(config: dict):
    """The model file of a configuration (or of its ``config.json``)."""
    return load(config["model_type"])
