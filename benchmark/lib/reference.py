"""The plain reference: a decoder-only transformer's forward pass in
numpy float32, read straight from the safetensors shards the harness
wrote, one layer at a time.

The forward pass of a model type is ``forward_hidden`` of its model
file (``benchmark/models/<model_type>.py``, lib/models.py), with its
sources and its departures from them; here are the shard reader and the
plain parts such a file is made of — no kernels, no cache, no batching
tricks, no quantization — and ``pooled()``, which hands a configuration
to its type's pass.
"""

from __future__ import annotations

import json
import os
import struct

import numpy as np

from . import models


class Shards:
    """Tensors of a sharded safetensors checkpoint as float32 arrays."""

    def __init__(self, ckpt_dir: str) -> None:
        self.dir = ckpt_dir
        with open(os.path.join(ckpt_dir,
                               "model.safetensors.index.json")) as f:
            self.weight_map = json.load(f)["weight_map"]
        self._headers: dict = {}

    def _header(self, fname: str):
        if fname not in self._headers:
            with open(os.path.join(self.dir, fname), "rb") as f:
                (n,) = struct.unpack("<Q", f.read(8))
                self._headers[fname] = (json.loads(f.read(n)), 8 + n)
        return self._headers[fname]

    def get(self, name: str) -> np.ndarray:
        fname = self.weight_map[name]
        header, base = self._header(fname)
        meta = header[name]
        if meta["dtype"] not in ("BF16", "F32"):
            raise ValueError(
                f"{name}: dtype {meta['dtype']}, expected BF16 or F32")
        lo, hi = meta["data_offsets"]
        path = os.path.join(self.dir, fname)
        if meta["dtype"] == "F32":
            return np.fromfile(path, dtype="<f4", count=(hi - lo) // 4,
                               offset=base + lo).reshape(meta["shape"])
        raw = np.fromfile(path, dtype="<u2", count=(hi - lo) // 2,
                          offset=base + lo)
        # bf16 is the high half of a float32
        return (raw.astype(np.uint32) << 16).view(np.float32).reshape(
            meta["shape"])


def rms_norm(x: np.ndarray, w: np.ndarray, eps: float) -> np.ndarray:
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x: np.ndarray, theta: float) -> np.ndarray:
    """Rotate-half RoPE; x: [T, H, Dh], positions 0..T-1."""
    T, _, dh = x.shape
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh))
    ang = np.arange(T, dtype=np.float32)[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def silu(x: np.ndarray) -> np.ndarray:
    return x / (1.0 + np.exp(-x))


def attention(x, wq, wk, wv, wo, n_heads, n_kv, d_head, theta, bias=None):
    """Causal grouped-query attention over one sequence; x: [T, D],
    weights in torch [out, in] layout; ``bias``: the (q, k, v)
    projections' own, where a type has them."""
    T = x.shape[0]
    q, k, v = x @ wq.T, x @ wk.T, x @ wv.T
    if bias is not None:
        q, k, v = q + bias[0], k + bias[1], v + bias[2]
    q = rope(q.reshape(T, n_heads, d_head), theta)
    k = rope(k.reshape(T, n_kv, d_head), theta)
    v = v.reshape(T, n_kv, d_head)
    group = n_heads // n_kv
    k = np.repeat(k, group, axis=1)
    v = np.repeat(v, group, axis=1)
    logits = np.einsum("thd,shd->hts", q, k) / np.sqrt(np.float32(d_head))
    mask = np.tril(np.ones((T, T), bool))
    logits = np.where(mask[None], logits, -np.inf)
    logits -= logits.max(axis=-1, keepdims=True)
    p = np.exp(logits)
    p /= p.sum(axis=-1, keepdims=True)
    out = np.einsum("hts,shd->thd", p, v).reshape(T, n_heads * d_head)
    return out @ wo.T


def swiglu(x, w_gate, w_up, w_down):
    return (silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def moe(x, router, experts, k, drop_expert=None, renormalise=True):
    """Top-k mixture; x: [T, D], router [E, D], experts(e) -> (w1, w3,
    w2): softmax over all experts, the top k kept, their weights
    renormalised to sum to 1 unless ``renormalise`` is off.
    ``drop_expert`` (tests only) zeroes one expert's output."""
    logits = x @ router.T  # [T, E]
    logits -= logits.max(axis=-1, keepdims=True)
    probs = np.exp(logits)
    probs /= probs.sum(axis=-1, keepdims=True)
    top = np.argsort(-probs, axis=-1, kind="stable")[:, :k]  # [T, k]
    w = np.take_along_axis(probs, top, axis=-1)
    if renormalise:
        w /= w.sum(axis=-1, keepdims=True)
    out = np.zeros_like(x)
    for e in range(router.shape[0]):
        rows, slot = np.nonzero(top == e)
        if rows.size == 0 or e == drop_expert:
            continue
        w1, w3, w2 = experts(e)
        out[rows] += swiglu(x[rows], w1, w3, w2) * w[rows, slot][:, None]
    return out


def forward_hidden(ckpt_dir: str, config: dict, ids_list: list,
                   mutate: "dict | None" = None) -> list:
    """Final hidden states (after the last norm) of each id sequence by
    the model type's own plain pass: -> list of [T_i, D] float32."""
    return models.of(config).forward_hidden(Shards(ckpt_dir), config,
                                            ids_list, mutate)


def pooled(ckpt_dir: str, config: dict, ids_list: list,
           mutate: "dict | None" = None) -> list:
    """Mean-pooled final hidden state per sequence (what /v1/embeddings
    serves for an LLM backend)."""
    return [h.mean(axis=0) for h in
            forward_hidden(ckpt_dir, config, ids_list, mutate)]


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
