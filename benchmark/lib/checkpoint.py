"""Seeded random checkpoints in the HuggingFace on-disk layout, plus the
tokenizer and the model YAML a user's models dir holds.

The benchmark's own copy of tools/synth_checkpoint.py (numpy only, never
more than one shard per worker thread in host RAM). Which tensors a
checkpoint holds — names, shapes, dtypes, how each is drawn, layer by
layer — is the model type's own table, ``tensors(config)`` of
``benchmark/models/<model_type>.py`` (lib/models.py): a configuration
of a new type brings that file, not a writer. Importing this module
touches neither JAX nor the program.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import models

WRITER_VERSION = "1"
_BF16_ONE = 0x3F80
_EMBED_RMS = 0.02


def _bf16_weight(rng: np.random.Generator, shape: tuple,
                 rms: float) -> np.ndarray:
    """Random bf16 bit patterns (uint16) of ``shape``: random sign and
    mantissa under one fixed exponent, rms near ``rms``."""
    k = round(math.log2(rms / 1.53))  # |w| in [2^k, 2^(k+1)): rms 1.53*2^k
    exp = np.uint16((k + 127) << 7)
    bits = rng.integers(0, 256, shape, dtype=np.uint8)
    out = bits.astype(np.uint16)
    return ((out & np.uint16(0x80)) << np.uint16(8)) | exp \
        | (out & np.uint16(0x7F))


def _draw(rng: np.random.Generator, shape: tuple, dtype: str,
          init: str) -> np.ndarray:
    """One tensor as bit patterns: uint16 for BF16, uint32 for F32 (the
    bf16 pattern in the high half, so both dtypes hold the same values).
    Only "matrix" and "embed" draw from ``rng``."""
    shape = tuple(shape)
    if init == "matrix":
        bits = _bf16_weight(rng, shape, 1.0 / math.sqrt(shape[-1]))
    elif init == "embed":
        bits = _bf16_weight(rng, shape, _EMBED_RMS)
    elif init in ("ones", "zeros"):
        bits = np.full(shape, _BF16_ONE if init == "ones" else 0, np.uint16)
    else:
        raise ValueError(f"unknown initialisation {init!r}")
    if dtype == "BF16":
        return bits
    if dtype == "F32":
        return bits.astype(np.uint32) << np.uint32(16)
    raise ValueError(f"unknown dtype {dtype!r}; the writer knows BF16, F32")


_DTYPE_OF_WIDTH = {2: "BF16", 4: "F32"}


def _save_shard(path: str, tensors: dict) -> dict:
    """One safetensors file of tensors given as bit patterns (uint16 =
    BF16, uint32 = F32); returns {tensor name: byte size}."""
    header: dict = {"__metadata__": {"format": "pt"}}
    offset = 0
    for name, arr in tensors.items():
        header[name] = {"dtype": _DTYPE_OF_WIDTH[arr.itemsize],
                        "shape": list(arr.shape),
                        "data_offsets": [offset, offset + arr.nbytes]}
        offset += arr.nbytes
    blob = json.dumps(header, separators=(",", ":")).encode()
    blob += b" " * (-len(blob) % 8)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(struct.pack("<Q", len(blob)))
        f.write(blob)
        for arr in tensors.values():
            f.write(np.ascontiguousarray(
                arr, dtype=arr.dtype.newbyteorder("<")).data)
    os.replace(tmp, path)
    return {name: arr.nbytes for name, arr in tensors.items()}


def write_hf_checkpoint(dirpath: str, config: dict, *, seed: int,
                        threads: int = 4) -> int:
    """``config.json`` + sharded weights (one shard per layer, one for
    embeddings / final norm / head), the tensors those of the model
    type's table. Every tensor is a pure function of (seed, its shard,
    its place in the shard). Returns the bytes of weights written."""
    by_shard: dict = {}
    for shard, name, shape, dtype, init in models.of(config).tensors(config):
        by_shard.setdefault(shard, []).append((name, shape, dtype, init))
    n = len(by_shard)
    if sorted(by_shard) != list(range(n)):
        raise ValueError(f"shards {sorted(by_shard)} are not 0..{n - 1}")
    os.makedirs(dirpath, exist_ok=True)

    def shard_name(i: int) -> str:
        return f"model-{i + 1:05d}-of-{n:05d}.safetensors"

    def write(i: int) -> dict:
        rng = np.random.default_rng([seed, i])
        return _save_shard(os.path.join(dirpath, shard_name(i)), {
            name: _draw(rng, shape, dtype, init)
            for name, shape, dtype, init in by_shard[i]})

    weight_map: dict = {}
    total = 0
    with ThreadPoolExecutor(max_workers=max(1, threads)) as pool:
        jobs = [(shard_name(i), pool.submit(write, i)) for i in range(n)]
        for fname, job in jobs:
            for name, nbytes in job.result().items():
                weight_map[name] = fname
                total += nbytes
    with open(os.path.join(dirpath, "model.safetensors.index.json"),
              "w") as f:
        json.dump({"metadata": {"total_size": total},
                   "weight_map": weight_map}, f)
    with open(os.path.join(dirpath, "config.json"), "w") as f:
        json.dump(config, f, indent=1)
    return total


def build_bpe_tokenizer(dirpath: str, vocab_size: int, *,
                        bos: str = "<s>", eos: str = "</s>") -> None:
    """A byte-level BPE tokenizer covering every id of the model vocab:
    256 byte symbols plus generated merges over printable ASCII, the two
    specials LAST (ids vocab_size-2 / vocab_size-1), so any id a
    random-weight model samples streams as visible text at once."""
    from tokenizers import Tokenizer, decoders, pre_tokenizers
    from tokenizers.models import BPE

    alphabet = sorted(pre_tokenizers.ByteLevel.alphabet())
    vocab = {tok: i for i, tok in enumerate(alphabet)}
    printable = [c for c in alphabet
                 if (len(c) == 1 and 0x21 <= ord(c) <= 0x7E)] + ["Ġ"]
    merges = []
    target = vocab_size - 2
    lvl = list(printable)
    while len(vocab) < target:
        nxt = []
        for a in lvl:
            for b in printable:
                if len(vocab) >= target:
                    break
                if a + b not in vocab:
                    vocab[a + b] = len(vocab)
                    merges.append((a, b))
                    nxt.append(a + b)
        lvl = nxt
    tk = Tokenizer(BPE(vocab=vocab, merges=merges))
    tk.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tk.decoder = decoders.ByteLevel()
    tk.add_special_tokens([bos, eos])
    os.makedirs(dirpath, exist_ok=True)
    tk.save(os.path.join(dirpath, "tokenizer.json"))
    with open(os.path.join(dirpath, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "bos_token": bos, "eos_token": eos}, f)


# keys of a configuration file that are the harness's, not the model's
_HARNESS_KEYS = {
    "source", "serving", "chips", "mesh", "reduced", "assumed",
    "deployment", "weights_seed", "parity_prompts", "parity_tol",
    "parity_tol_reason", "published", "notes", "expect",
}


def hf_config(config: dict) -> dict:
    """The model's own ``config.json``: the configuration file without
    the harness's keys."""
    return {k: v for k, v in config.items() if k not in _HARNESS_KEYS}


def config_key(config_path: str) -> str:
    with open(config_path, "rb") as f:
        blob = f.read()
    return hashlib.sha256(
        blob + WRITER_VERSION.encode()).hexdigest()[:12]


def write_yaml(models_dir: str, name: str, serving: dict,
               mesh: "dict | None" = None) -> str:
    lines = [f"name: {name}"]
    lines += [f"{k}: {json.dumps(v) if isinstance(v, bool) else v}"
              for k, v in serving.items()]
    lines += ["parameters:", f"  model: {name}"]
    if mesh:
        lines += ["mesh:"] + [f"  {k}: {v}" for k, v in mesh.items()]
    lines += ["template:",
              '  chat_message: "{{.RoleName}}: {{.Content}}"',
              '  chat: "{{.Input}}\\nassistant:"']
    path = os.path.join(models_dir, name + ".yaml")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return path


def materialise(cache_dir: str, name: str, config_path: str,
                log=print) -> dict:
    """Checkpoint + tokenizer + YAML under ``cache_dir`` unless the
    marker for (configuration file hash, weights_seed) is there.
    -> {home, models_dir, ckpt_dir, fresh, bytes}."""
    import time

    with open(config_path) as f:
        config = json.load(f)
    home = os.path.join(cache_dir, "models",
                        f"{name}-{config_key(config_path)}")
    models_dir = os.path.join(home, "models")
    ckpt = os.path.join(models_dir, name)
    marker = os.path.join(home, "marker.json")
    out = {"home": home, "models_dir": models_dir, "ckpt_dir": ckpt,
           "fresh": False}
    if os.path.exists(marker):
        with open(marker) as f:
            out["bytes"] = json.load(f).get("bytes")
        return out
    t0 = time.monotonic()
    os.makedirs(models_dir, exist_ok=True)
    hf = hf_config(config)
    nbytes = write_hf_checkpoint(
        ckpt, hf, seed=int(config["weights_seed"]),
        threads=min(8, os.cpu_count() or 1))
    build_bpe_tokenizer(ckpt, hf["vocab_size"])
    write_yaml(models_dir, name, config["serving"], config.get("mesh"))
    with open(marker, "w") as f:
        json.dump({"bytes": nbytes, "weights_seed": config["weights_seed"],
                   "writer": WRITER_VERSION}, f)
    log(f"checkpoint: wrote {nbytes / 1e9:.2f} GB for {name} in "
        f"{time.monotonic() - t0:.1f}s -> {ckpt}")
    out.update(fresh=True, bytes=nbytes)
    return out
