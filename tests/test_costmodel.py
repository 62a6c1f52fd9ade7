"""Warmup-captured XLA cost model (telemetry/costmodel.py): dispatch-key
stability, capture during warmup, hot-path accounting totals, the
analytic 2*params*tokens cross-check, the MFU EWMA, and compute- vs
bandwidth-bound roofline classification with knob-overridden peaks."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
from localai_tfp_tpu.models.llm_spec import tiny_spec
from localai_tfp_tpu.models.transformer import init_params
from localai_tfp_tpu.telemetry import costmodel
from localai_tfp_tpu.telemetry.registry import REGISTRY


@pytest.fixture(scope="module")
def model():
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    return spec, params, tk


@pytest.fixture(scope="module")
def served_engine(model):
    """ONE warmed engine with real traffic, shared by the read-only
    assertions below — warmup (the capture pass) is the expensive part,
    so it runs once per module."""
    spec, params, tk = model
    eng = LLMEngine(spec, params, tk, n_slots=4, max_seq=128,
                    prefill_buckets=(8, 32),
                    cache_dtype=jnp.float32, tag="costmodel-test")
    eng.warmup()
    for i in range(2):
        ev = eng.generate(GenRequest(
            prompt_ids=tk.encode(f"probe {i} " * 4),
            max_tokens=8, ignore_eos=True))
        assert ev.finish_reason == "length"
    yield eng
    eng.close()


# ------------------------------------------------------- key stability


def test_dispatch_key_tracks_jit_cache_signature():
    toks = np.zeros((4, 32), np.int32)
    # a mixed step is keyed by its prompt group's [rows, bucket]
    assert costmodel.dispatch_key(
        "mixed", {"toks": toks, "window": 64}) == ("mixed", (4, 32), 64)
    assert costmodel.dispatch_key(
        "decodek", {"k": 4, "window": 128, "depth": 1}) == \
        ("decodek", 4, 128, 1)
    assert costmodel.dispatch_key(
        "prefill", {"toks": np.zeros((8,), np.int32), "window": 128}) == \
        ("prefill", 8, 128)
    assert costmodel.dispatch_key("kvcopy", {"n": 3}) == ("kvcopy", 3)
    assert costmodel.dispatch_key("decode1", {"x": 1}) == ("decode1",)
    # the row count forks the variant, so it forks the key
    assert costmodel.dispatch_key(
        "mixed", {"toks": toks[:2], "window": 64}
    ) != costmodel.dispatch_key("mixed", {"toks": toks, "window": 64})


def test_peak_rates_device_kind_table_and_overrides(monkeypatch):
    monkeypatch.delenv("LOCALAI_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("LOCALAI_PEAK_HBM_GBS", raising=False)
    assert costmodel.peak_rates("cpu") == (50e9, 50e9)
    assert costmodel.peak_rates("TPU v5 lite") == (197e12, 819e9)
    # a device the table does not know is an error — never the v5e row
    # (these peaks size dispatches) and never the CPU row
    for kind in ("TPU v9 mega", "tpu", "NVIDIA H100"):
        with pytest.raises(ValueError, match="device_kind"):
            costmodel.peak_rates(kind)
        with pytest.raises(ValueError, match="device_kind"):
            costmodel.CostModel("m", kind)
    # one knob alone cannot stand in for an unknown device
    monkeypatch.setenv("LOCALAI_PEAK_FLOPS", "1e12")
    with pytest.raises(ValueError, match="device_kind"):
        costmodel.peak_rates("TPU v9 mega")
    monkeypatch.setenv("LOCALAI_PEAK_HBM_GBS", "100")
    assert costmodel.peak_rates("cpu") == (1e12, 100e9)
    assert costmodel.peak_rates("TPU v9 mega") == (1e12, 100e9)


# --------------------------------------------- capture and accounting


def test_warmup_captures_every_variant(served_engine):
    cm = served_engine._costmodel
    assert cm is not None
    capt = cm.captured()
    # the full dispatch ladder: 3 buckets x row rungs + decode paths
    assert len(capt) >= 7
    kinds = {k[0] for k in capt}
    assert {"mixed", "decodek"} <= kinds
    # every captured row carries a real bytes-accessed estimate
    assert all(by > 0 for _, by in capt.values())


def test_serving_traffic_accounts_flops_and_mfu(served_engine):
    stats = served_engine.cost_stats()
    assert stats is not None
    traffic = {k: v for k, v in stats["kinds"].items()
               if v["dispatches"] > 0}
    assert traffic, stats["kinds"]
    assert all(v["flops"] > 0 and v["bytes"] > 0
               for v in traffic.values())
    # flight harvests fed the EWMA
    assert stats["mfu_samples"] > 0
    assert stats["mfu_ewma"] is not None
    assert 0.0 < stats["mfu_ewma"] <= 1.0
    # and the scrape surface has the new families with this engine's tag
    text = REGISTRY.render()
    assert re.search(
        r'engine_device_flops_total\{model="costmodel-test",kind="\w+"\}'
        r" [1-9]", text)
    assert re.search(
        r'engine_device_bytes_total\{model="costmodel-test",kind="\w+"\}'
        r" [1-9]", text)
    assert re.search(
        r'engine_mfu_ratio\{model="costmodel-test"\} 0\.\d+', text)


def test_captured_decode_matches_analytic_flops(served_engine):
    """The XLA estimate for one decode token must agree with the
    first-principles 2*matrix-params count to a generous band (XLA
    additionally counts attention/norm work and may fold constants)."""
    cm = served_engine._costmodel
    analytic = costmodel.analytic_flops_per_token(served_engine.params)
    assert analytic > 0
    row = cm.captured().get(("decode1",))
    assert row is not None, "decode1 variant never captured"
    ratio = row[0] / analytic
    assert 0.2 <= ratio <= 5.0, (row[0], analytic)


def test_warmup_pads_are_not_traffic(model):
    """Capture mode records cost rows but must not count the warmup pad
    dispatches as served traffic (dispatch/harvest accounting no-ops
    while capturing)."""
    cm = costmodel.CostModel("pads", "cpu")
    cm._table[("decode1",)] = (100.0, 400.0)
    cm.capturing = True
    cm.on_dispatch("decode1", ("decode1",))
    assert cm._totals == {}
    cm.capturing = False
    cm.on_dispatch("decode1", ("decode1",))
    assert cm._totals["decode1"] == [100.0, 400.0, 1.0]
    # unknown variant: accounted as a silent miss, never a crash
    cm.on_dispatch("decode1", ("decode1", "no-such-variant"))
    assert cm._totals["decode1"][2] == 1.0


# ----------------------------------------------------------- roofline


def test_roofline_classifies_decode_vs_prefill(served_engine,
                                               monkeypatch):
    # a device whose ridge (0.5 FLOP/byte) lies between the two: at this
    # toy width the activations are half of a step's bytes, so a step
    # that carries a whole bucket reads 0.97 — a hair under the CPU
    # row's ridge of 1.0 — against 0.13 for a decode step
    monkeypatch.setenv("LOCALAI_PEAK_FLOPS", "25e9")
    monkeypatch.setenv("LOCALAI_PEAK_HBM_GBS", "50")
    roof = served_engine._costmodel.roofline()
    decode = {k: v for k, v in roof.items() if k.startswith("decode")}
    prefill = {k: v for k, v in roof.items()
               if k.startswith("prefill") or k == "mixed"}
    assert decode and prefill
    # decode re-reads the weights per token: under the ridge
    assert all(v["bound"] == "bandwidth" for v in decode.values()), roof
    # batched prefill amortizes them per bucket: over the ridge
    assert any(v["bound"] == "compute" for v in prefill.values()), roof


def test_roofline_ridge_follows_peak_knobs(served_engine, monkeypatch):
    # a near-zero ridge: every kind classifies compute-bound
    monkeypatch.setenv("LOCALAI_PEAK_FLOPS", "50e9")
    monkeypatch.setenv("LOCALAI_PEAK_HBM_GBS", "1e9")
    roof = served_engine._costmodel.roofline()
    assert all(v["bound"] == "compute"
               for k, v in roof.items() if v["flops"] > 0), roof
    # a huge ridge: everything is bandwidth-bound
    monkeypatch.setenv("LOCALAI_PEAK_FLOPS", "1e18")
    monkeypatch.setenv("LOCALAI_PEAK_HBM_GBS", "1")
    roof = served_engine._costmodel.roofline()
    assert all(v["bound"] == "bandwidth" for v in roof.values()), roof


def test_costmodel_disabled_by_knob(model, monkeypatch):
    monkeypatch.setenv("LOCALAI_COSTMODEL", "off")
    spec, params, tk = model
    eng = LLMEngine(spec, params, tk, n_slots=2, max_seq=64,
                    prefill_buckets=(8,), cache_dtype=jnp.float32)
    try:
        assert eng._costmodel is None
        assert eng.cost_stats() is None
    finally:
        eng.close()
