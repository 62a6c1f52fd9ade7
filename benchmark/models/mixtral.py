"""model_type ``mixtral``: ``mistral``'s attention, with a top-k
mixture of SwiGLU experts for an MLP (Mixtral: arXiv:2401.04088; HF
``modeling_mixtral``). ``block_sparse_moe.gate`` is the [E, D] router;
w1 = gate, w3 = up, w2 = down (models/hf_loader.py reads exactly these).

Departures from the published description: none in the mathematics.
The router is written as softmax over all experts, keep the top k,
renormalise (the paper's form; equal to softmax over the top-k logits).
Only the selected experts are evaluated, so an implementation that
evaluates all of them must still weight the others by zero.

``mutate`` (tests only): ``mistral``'s, and a dropped expert.
"""

import os

import numpy as np

from benchmark.lib import models, roofline
from benchmark.lib import reference as R

# everything but the MLP is that type's: the file beside this one
_dense = models.load("mistral", os.path.dirname(os.path.abspath(__file__)))
ATTENTION_KERNELS = _dense.ATTENTION_KERNELS


def tensors(config: dict) -> list:
    m, out = _dense.dims(config), []
    experts = range(config["num_local_experts"])
    for i in range(m["L"]):
        lp = f"model.layers.{i}.block_sparse_moe."
        out += _dense.attn_tensors(config, i)
        out.append((i, lp + "gate.weight", (len(experts), m["d"]), "BF16",
                    "matrix"))
        for w, shape in (("w1", (m["f"], m["d"])), ("w3", (m["f"], m["d"])),
                         ("w2", (m["d"], m["f"]))):
            out += [(i, lp + f"experts.{e}.{w}.weight", shape, "BF16",
                     "matrix") for e in experts]
        out += _dense.norm_tensors(config, i)
    return out + _dense.global_tensors(config)


def forward_hidden(shards, config: dict, ids_list: list,
                   mutate: "dict | None" = None) -> list:
    def mlp(g, normed):
        def experts(e):
            b = f"block_sparse_moe.experts.{e}."
            return g(b + "w1.weight"), g(b + "w3.weight"), \
                g(b + "w2.weight")

        y = R.moe(np.concatenate(normed), g("block_sparse_moe.gate.weight"),
                  experts, config["num_experts_per_tok"],
                  drop_expert=(mutate or {}).get("drop_expert"))
        return np.split(y, np.cumsum([n.shape[0] for n in normed])[:-1])

    return _dense.forward_hidden(shards, config, ids_list, mutate, mlp)


def param_counts(config: dict) -> dict:
    """``mistral``'s groups, the MLP's as experts (all of them) and
    router."""
    m, p = _dense.dims(config), _dense.param_counts(config)
    e = config["num_local_experts"]
    return {"attn": p["attn"], "experts": p["mlp"] * e,
            "router": m["L"] * m["d"] * e, "head": p["head"]}


def experts_touched(config: dict, rows: float) -> float:
    """Distinct experts one layer has to read for ``rows`` tokens, each
    routed to k of E. 1 row of top-2 over 8 touches 2, 16 rows 7.9."""
    return roofline.distinct_touched(
        config["num_local_experts"], config["num_experts_per_tok"], rows)


def decode_weight_bytes(config: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step of ``rows`` tokens HAS to read:
    every dense matrix once, and of each layer's experts only those the
    routing touches — what the hardware demands, not what the program
    does today (it evaluates every expert whatever the routing), so a
    program that learns to skip experts cannot read above 100 %."""
    served = config["assumed"]["served_bytes_per_param"]
    p = param_counts(config)
    share = experts_touched(config, rows) / config["num_local_experts"]
    return (p["attn"] + p["head"]) * served["dense"] \
        + p["experts"] * share * served["experts"] + p["router"] * 4


kv_bytes_per_token = _dense.kv_bytes_per_token
