"""Seeded random checkpoints in the real HuggingFace on-disk layout.

A chip run has no network, so a model at its published widths is made
from its ``config.json`` and a seed: ``write_hf_checkpoint`` writes
sharded bf16 ``model-XXXXX-of-YYYYY.safetensors`` files (torch
``[out, in]`` layout, llama/mistral tensor names) plus the index, and
``build_bpe_tokenizer`` a byte-level BPE ``tokenizer.json`` covering
every vocab id. The result loads through the normal loader exactly like
a downloaded checkpoint (chip_smoke.py serves one; benchmark cells are
the next user).

The weight writer needs numpy only and never holds more than one shard
(one layer) per worker thread in host RAM: a 7B checkpoint is 14.5 GB on
disk and a few hundred MB at a time in memory. Importing this module
touches neither JAX nor the package.
"""

from __future__ import annotations

import json
import math
import os
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_BF16_ONE = 0x3F80


def _bf16_weight(rng: np.random.Generator, out_d: int, in_d: int,
                 rms: float) -> np.ndarray:
    """Random bf16 bit patterns (as uint16) of shape [out_d, in_d]:
    random sign and mantissa under one fixed exponent, scaled so the
    root-mean-square lands near ``rms``. One pass over 8 random bits per
    weight — no float32 staging of multi-GB tensors."""
    # |w| uniform in [2^k, 2^(k+1)) has rms ~1.53 * 2^k
    k = round(math.log2(rms / 1.53))
    exp = np.uint16((k + 127) << 7)
    bits = rng.integers(0, 256, (out_d, in_d), dtype=np.uint8)
    out = bits.astype(np.uint16)
    out = ((out & np.uint16(0x80)) << np.uint16(8)) | exp \
        | (out & np.uint16(0x7F))
    return out


def _save_shard(path: str, tensors: dict[str, np.ndarray]) -> dict:
    """Write one safetensors file (8-byte header length, JSON header,
    raw little-endian data) of bf16 tensors given as uint16 bit
    patterns; returns {tensor name: byte size}."""
    header: dict = {"__metadata__": {"format": "pt"}}
    offset = 0
    for name, arr in tensors.items():
        n = arr.size * 2
        header[name] = {"dtype": "BF16", "shape": list(arr.shape),
                        "data_offsets": [offset, offset + n]}
        offset += n
    blob = json.dumps(header, separators=(",", ":")).encode()
    blob += b" " * (-len(blob) % 8)  # data starts 8-byte aligned
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(struct.pack("<Q", len(blob)))
        f.write(blob)
        for arr in tensors.values():
            f.write(np.ascontiguousarray(arr, dtype="<u2").data)
    os.replace(tmp, path)
    return {name: arr.size * 2 for name, arr in tensors.items()}


def write_hf_checkpoint(dirpath: str, config: dict, *, seed: int = 0,
                        threads: int = 4) -> int:
    """Write ``config.json`` and the sharded weights of a llama/mistral-
    layout dense model (one shard per layer, one for embeddings / final
    norm / head). Every tensor is a pure function of (seed, its shard),
    so the checkpoint is reproducible whatever the thread count.
    Returns the bytes of weights written."""
    D = config["hidden_size"]
    F = config["intermediate_size"]
    V = config["vocab_size"]
    L = config["num_hidden_layers"]
    d_head = config.get("head_dim") or D // config["num_attention_heads"]
    q_dim = config["num_attention_heads"] * d_head
    kv_dim = config["num_key_value_heads"] * d_head
    os.makedirs(dirpath, exist_ok=True)
    n_shards = L + 1

    def shard_name(i: int) -> str:
        return f"model-{i + 1:05d}-of-{n_shards:05d}.safetensors"

    def ones(n: int) -> np.ndarray:
        return np.full((n,), _BF16_ONE, np.uint16)

    def layer(i: int) -> dict:
        rng = np.random.default_rng([seed, i])
        lp = f"model.layers.{i}."

        def w(out_d, in_d):
            return _bf16_weight(rng, out_d, in_d, 1.0 / math.sqrt(in_d))

        return _save_shard(os.path.join(dirpath, shard_name(i)), {
            lp + "self_attn.q_proj.weight": w(q_dim, D),
            lp + "self_attn.k_proj.weight": w(kv_dim, D),
            lp + "self_attn.v_proj.weight": w(kv_dim, D),
            lp + "self_attn.o_proj.weight": w(D, q_dim),
            lp + "mlp.gate_proj.weight": w(F, D),
            lp + "mlp.up_proj.weight": w(F, D),
            lp + "mlp.down_proj.weight": w(D, F),
            lp + "input_layernorm.weight": ones(D),
            lp + "post_attention_layernorm.weight": ones(D),
        })

    def globals_() -> dict:
        rng = np.random.default_rng([seed, L])
        return _save_shard(os.path.join(dirpath, shard_name(L)), {
            "model.embed_tokens.weight": _bf16_weight(rng, V, D, 0.02),
            "model.norm.weight": ones(D),
            "lm_head.weight": _bf16_weight(
                rng, V, D, 1.0 / math.sqrt(D)),
        })

    weight_map: dict[str, str] = {}
    total = 0
    with ThreadPoolExecutor(max_workers=max(1, threads)) as pool:
        jobs = [(shard_name(i), pool.submit(layer, i)) for i in range(L)]
        jobs.append((shard_name(L), pool.submit(globals_)))
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
    """A real byte-level BPE tokenizer covering every id of the model
    vocab: 256 byte symbols plus generated merges, the two specials
    LAST (ids vocab_size-2 / vocab_size-1). Merges run only over symbols
    that decode to printable ASCII, so any id a random-weight model
    samples streams as visible text at once instead of sitting in the
    incremental UTF-8 decoder waiting for continuation bytes."""
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
