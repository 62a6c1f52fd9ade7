"""The plain reference: a decoder-only transformer's forward pass in
numpy float32, read straight from the bf16 safetensors shards the
harness wrote, one layer at a time.

Follows the published descriptions (Mistral-7B: arXiv:2310.06825;
Mixtral: arXiv:2401.04088; HF ``modeling_mistral`` / ``modeling_mixtral``):
token embedding, per layer RMSNorm -> GQA attention with rotate-half
RoPE (theta from the config) under a causal mask -> residual -> RMSNorm
-> SwiGLU MLP (Mistral) or top-k mixture of SwiGLU experts (Mixtral)
-> residual, then the final RMSNorm. No kernels, no cache, no batching
tricks, no quantization.

Departures from the published description: none in the mathematics.
Mixtral's router is written as softmax over all experts, keep the top
k, renormalise (the paper's form; equal to softmax over the top-k
logits). Only the selected experts are evaluated, so an implementation
that evaluates all of them must still weight the others by zero.

``mutate`` exists for the tests only: it shows that the parity
tolerance catches a zeroed layer, a wrong rope base or a dropped expert.
"""

from __future__ import annotations

import json
import os
import struct

import numpy as np


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
        if meta["dtype"] != "BF16":
            raise ValueError(f"{name}: dtype {meta['dtype']}, expected BF16")
        lo, hi = meta["data_offsets"]
        raw = np.fromfile(os.path.join(self.dir, fname), dtype="<u2",
                          count=(hi - lo) // 2, offset=base + lo)
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


def attention(x, wq, wk, wv, wo, n_heads, n_kv, d_head, theta):
    """Causal grouped-query attention over one sequence; x: [T, D],
    weights in torch [out, in] layout."""
    T = x.shape[0]
    q = rope((x @ wq.T).reshape(T, n_heads, d_head), theta)
    k = rope((x @ wk.T).reshape(T, n_kv, d_head), theta)
    v = (x @ wv.T).reshape(T, n_kv, d_head)
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


def moe(x, router, experts, k, drop_expert=None):
    """Top-k mixture; x: [T, D], router [E, D], experts(e) -> (w1, w3,
    w2). ``drop_expert`` (tests only) zeroes one expert's output."""
    logits = x @ router.T  # [T, E]
    logits -= logits.max(axis=-1, keepdims=True)
    probs = np.exp(logits)
    probs /= probs.sum(axis=-1, keepdims=True)
    top = np.argsort(-probs, axis=-1, kind="stable")[:, :k]  # [T, k]
    w = np.take_along_axis(probs, top, axis=-1)
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
    """Final hidden states (after the last norm) of each id sequence:
    -> list of [T_i, D] float32. Weights are read once per layer for
    all sequences."""
    mutate = mutate or {}
    sh = Shards(ckpt_dir)
    n_heads = config["num_attention_heads"]
    n_kv = config["num_key_value_heads"]
    d_head = config.get("head_dim") or config["hidden_size"] // n_heads
    eps = float(config["rms_norm_eps"])
    theta = float(mutate.get("rope_theta", config["rope_theta"]))
    n_exp = config.get("num_local_experts", 0)
    if config.get("sliding_window"):
        raise NotImplementedError("the reference has no sliding window")
    embed = sh.get("model.embed_tokens.weight")
    xs = [embed[np.asarray(ids)] for ids in ids_list]
    del embed
    for i in range(config["num_hidden_layers"]):
        if i == mutate.get("zero_layer"):
            continue
        lp = f"model.layers.{i}."
        g = lambda n: sh.get(lp + n)  # noqa: E731
        wq, wk, wv, wo = (g(f"self_attn.{p}_proj.weight")
                          for p in "qkvo")
        ln1 = g("input_layernorm.weight")
        ln2 = g("post_attention_layernorm.weight")
        xs = [x + attention(rms_norm(x, ln1, eps), wq, wk, wv, wo,
                            n_heads, n_kv, d_head, theta) for x in xs]
        del wq, wk, wv, wo
        if n_exp:
            router = g("block_sparse_moe.gate.weight")
            lens = [x.shape[0] for x in xs]
            flat = np.concatenate([rms_norm(x, ln2, eps) for x in xs])

            def experts(e):
                b = f"block_sparse_moe.experts.{e}."
                return g(b + "w1.weight"), g(b + "w3.weight"), \
                    g(b + "w2.weight")

            y = moe(flat, router, experts, config["num_experts_per_tok"],
                    drop_expert=mutate.get("drop_expert"))
            parts = np.split(y, np.cumsum(lens)[:-1])
            xs = [x + p for x, p in zip(xs, parts)]
        else:
            w_gate, w_up, w_down = (g(f"mlp.{p}_proj.weight")
                                    for p in ("gate", "up", "down"))
            xs = [x + swiglu(rms_norm(x, ln2, eps), w_gate, w_up, w_down)
                  for x in xs]
            del w_gate, w_up, w_down
    norm = sh.get("model.norm.weight")
    return [rms_norm(x, norm, eps) for x in xs]


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
