"""Bytes and operations a step has to move, from a configuration's
shapes — the numerators of the roofline shares. Kept with the benchmark
so that no PR that claims a gain can change them.

Bytes per parameter as SERVED come from the configuration file
(``assumed.served_bytes_per_param``: ``dense`` for attention / MLP /
head matrices, ``experts`` for expert stacks), because the program's
``quantization`` decides them, not the published config.
"""

from __future__ import annotations

from .checkpoint import dims as _dims


def param_counts(config: dict) -> dict:
    """Matrix parameters by group: attention, dense MLP, experts (all
    of them), router, head (the output projection; the embedding table
    is gathered, not read whole)."""
    m = _dims(config)
    attn = m["L"] * (2 * m["d"] * m["q"] + 2 * m["d"] * m["kv"])
    mlp = 3 * m["d"] * m["f"] * m["L"]
    return {
        "attn": attn,
        "mlp": 0 if m["e"] else mlp,
        "experts": mlp * m["e"],
        "router": m["L"] * m["d"] * m["e"],
        "head": m["v"] * m["d"],
    }


def experts_touched(config: dict, rows: float) -> float:
    """Distinct experts one layer has to read for ``rows`` tokens, each
    routed to k of E: E * (1 - (1 - k/E) ** rows), routing taken as
    uniform and independent (the seeded random router's is). 1 row of
    top-2 over 8 touches 2, 16 rows 7.9."""
    e, k = config["num_local_experts"], config["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** max(1.0, rows))


def decode_weight_bytes(config: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step of ``rows`` tokens HAS to read:
    every dense matrix once, and of each layer's experts only those the
    routing touches — what the hardware demands, not what the program
    does today (it evaluates every expert whatever the routing), so a
    program that learns to skip experts cannot read above 100 %."""
    served = config["assumed"]["served_bytes_per_param"]
    p = param_counts(config)
    dense = (p["attn"] + p["mlp"] + p["head"]) * served["dense"]
    if not p["experts"]:
        return dense
    share = experts_touched(config, rows) / config["num_local_experts"]
    return dense + p["experts"] * share * served["experts"] \
        + p["router"] * 4


def kv_bytes_per_token(config: dict, layers: "int | None" = None) -> float:
    """K and V bytes one cached token holds (data only; the int8
    cache's per-row scales are left out, so the share reads low rather
    than high)."""
    m = _dims(config)
    n_layers = m["L"] if layers is None else layers
    return 2 * m["kv"] * config["assumed"]["kv_bytes_per_value"] * n_layers


def min_seconds(nbytes: float, flops: float, peak: dict,
                flops_key: str = "bf16_flops") -> "tuple[float, str]":
    """The least time the chip could take and which bound sets it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_cmp = flops / peak[flops_key]
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
