"""Bytes and operations a step has to move — the numerators of the
roofline shares. The counts are a model type's own and live in its
model file (``benchmark/models/<model_type>.py``, lib/models.py): this
module hands a configuration to them. Kept with the benchmark so that
no PR that claims a gain can change them.
"""

from __future__ import annotations

from . import models


def decode_weight_bytes(config: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step of ``rows`` tokens HAS to read."""
    return models.of(config).decode_weight_bytes(config, rows)


def kv_bytes_per_token(config: dict, layers: "int | None" = None) -> float:
    """Bytes one cached token holds, in ``layers`` layers (None: all)."""
    return models.of(config).kv_bytes_per_token(config, layers)


def distinct_touched(e: int, k: int, rows: float) -> float:
    """Distinct experts of E that ``rows`` tokens touch, each routed to
    k of them: E * (1 - (1 - k/E) ** rows), routing taken as uniform and
    independent (the seeded random router's is)."""
    return e * (1.0 - (1.0 - k / e) ** max(1.0, rows))


def min_seconds(nbytes: float, flops: float, peak: dict,
                flops_key: str = "bf16_flops") -> "tuple[float, str]":
    """The least time the chip could take and which bound sets it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_cmp = flops / peak[flops_key]
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
