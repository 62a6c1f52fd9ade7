"""Cost-model-driven scheduling (engine._mixed_shape / _itl_budget_ms
+ telemetry/costmodel.predict_ms): dispatch budgets expressed in
PREDICTED device microseconds instead of token counts.

Invariants enforced here:
- cost-scheduling is a pure packing change: an identical request
  schedule (seeded sampling included) yields byte-identical streams
  with LOCALAI_COST_SCHED on (ITL budget armed) vs off (legacy token
  budget) — shrinking a mixed bucket may change dispatch composition
  but never output bytes;
- predictions live in flight META only: the device payload carries the
  exact same key set either way, so multihost follower replay (which
  re-derives dispatches from broadcast payloads) is byte-compatible
  and scalar-payload discipline holds;
- predict_ms falls back conservatively before calibration warms:
  bare analytic roofline until the variant has >=2 harvests (or the
  kind has >=_CALIB_MIN_SAMPLES), None for never-captured variants;
- repeated harvests with a stable measured span converge predict_ms
  to that span (EWMA calibration closes the analytic-vs-wall gap);
- under flood with an explicit ITL budget armed, decode never starves:
  every fused dispatch that carries prefill tokens while a slot
  decodes still advances >=1 decode row, and the cost packer only ever
  selects warmed buckets no larger than the token-budget choice;
- the three knobs are registered with the documented defaults and the
  engine honors LOCALAI_PREFILL_GROUP_TOKENS at construction.
"""

import jax.numpy as jnp
import pytest

from localai_tfp_tpu.config import knobs
from localai_tfp_tpu.engine.engine import LLMEngine
from localai_tfp_tpu.telemetry.costmodel import (
    _CALIB_MIN_SAMPLES, CostModel)
from tests.test_mixed_dispatch import (  # noqa: F401  (model fixture)
    DispatchSpy, _engine, _mixed_schedule, model)

# ---------------------------------------------------------------------------
# byte-identity + scalar-payload invariant


class PayloadKeySpy:
    """Records, per dispatch, the kind and the sorted payload key set —
    the multihost replay surface. Predictions must never leak here."""

    def __init__(self, eng):
        self.records = []
        self._orig = eng._run
        eng._run = self._run_wrap
        self._eng = eng

    def _run_wrap(self, kind, payload):
        self.records.append((kind, tuple(sorted(payload))))
        return self._orig(kind, payload)

    def keysets(self):
        return {(k, ks) for k, ks in self.records}


def test_cost_sched_on_off_byte_identical(model, monkeypatch):
    """The headline invariant: with a tight ITL budget armed, the cost
    packer may shrink mixed buckets, but an identical seeded schedule
    produces byte-identical streams vs the legacy token budget — AND
    the device payload key sets are identical (predictions ride flight
    meta, never the replayable payload)."""
    spec, params, tk = model
    monkeypatch.setenv("LOCALAI_ITL_BUDGET_MS", "5")
    monkeypatch.setenv("LOCALAI_COST_SCHED", "off")
    eng_off = _engine(model)
    try:
        spy_off = PayloadKeySpy(eng_off)
        want = _mixed_schedule(eng_off, tk)
    finally:
        eng_off.close()
    monkeypatch.setenv("LOCALAI_COST_SCHED", "on")
    eng_on = _engine(model)
    try:
        assert eng_on._itl_budget_ms() == 5.0
        spy_on = PayloadKeySpy(eng_on)
        got = _mixed_schedule(eng_on, tk)
    finally:
        eng_on.close()
    for name in want:
        assert got[name][0] == want[name][0], f"stream {name} diverged"
        assert got[name][1].full_text == want[name][1].full_text
        assert got[name][1].finish_reason == want[name][1].finish_reason
    # scalar-payload / multihost-replay invariant: same key vocabulary
    # per kind on both legs, and nothing prediction-shaped in either
    per_kind_on = {k: ks for k, ks in spy_on.keysets()}
    per_kind_off = {k: ks for k, ks in spy_off.keysets()}
    for kind in set(per_kind_on) & set(per_kind_off):
        assert per_kind_on[kind] == per_kind_off[kind], kind
    for kind, ks in spy_on.keysets() | spy_off.keysets():
        assert not any("pred" in key or "cost" in key for key in ks), (
            f"prediction leaked into the {kind} payload: {ks}")


# ---------------------------------------------------------------------------
# predictor unit tests (bare CostModel, synthetic cost rows)


@pytest.fixture()
def cpu_peaks(monkeypatch):
    """Pin peak_rates to the stock CPU row (50e9, 50e9)."""
    monkeypatch.delenv("LOCALAI_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("LOCALAI_PEAK_HBM_GBS", raising=False)


def test_predictor_fallback_before_warm(cpu_peaks):
    """Prediction trust escalates with evidence: bare analytic bound
    until the variant has 2 harvests, kind-level EWMA only once the
    kind has _CALIB_MIN_SAMPLES, None for never-captured variants."""
    cm = CostModel("t", "cpu")
    key = ("decodek", 8, 128, 1)
    # flops dominates: 5e10 / 50e9 FLOP/s = 1.0 s => 1000 ms analytic
    cm._table[key] = (5e10, 1e9)
    assert cm.predict_ms("decodek", key) == pytest.approx(1000.0)
    assert cm.predict_ms("decodek", ("decodek", 16, 128, 1)) is None
    assert cm.predict_ms("decodek", None) is None
    # one harvest at 2x the analytic bound: variant (1 sample) and kind
    # (1 sample) are both still cold => bare analytic stands
    cm.on_harvest("decodek", key, span_s=2.0)
    assert cm.predict_ms("decodek", key) == pytest.approx(1000.0)
    # second harvest: the variant EWMA (ratio 2.0) is now trusted
    cm.on_harvest("decodek", key, span_s=2.0)
    assert cm.predict_ms("decodek", key) == pytest.approx(2000.0)
    # a sibling variant with its own cost row but no harvests: the kind
    # EWMA has only 2 samples (< _CALIB_MIN_SAMPLES) => bare analytic
    sib = ("decodek", 4, 128, 1)
    cm._table[sib] = (2.5e10, 1e9)  # 500 ms analytic
    assert cm.predict_ms("decodek", sib) == pytest.approx(500.0)
    # third harvest on the warm variant crosses the kind threshold:
    # the cold sibling now borrows the kind-level ratio (2.0)
    cm.on_harvest("decodek", key, span_s=2.0)
    assert _CALIB_MIN_SAMPLES == 3
    assert cm.predict_ms("decodek", sib) == pytest.approx(1000.0)
    # ...while the warm variant keeps preferring its OWN ratio
    assert cm.predict_ms("decodek", key) == pytest.approx(2000.0)


def test_predictor_calibration_converges(cpu_peaks):
    """Repeated harvests with a stable measured span converge the
    prediction to that span (EWMA closes the analytic-vs-wall gap from
    either direction)."""
    cm = CostModel("t", "cpu")
    key = ("mixed", (4, 32), 128)
    cm._table[key] = (5e9, 0.0)  # 100 ms analytic
    for span_s, want_ms in ((0.25, 250.0), (0.04, 40.0)):
        for _ in range(80):
            cm.on_harvest("mixed", key, span_s=span_s)
        assert cm.predict_ms("mixed", key) == pytest.approx(
            want_ms, rel=0.01)
    # warmup pads never calibrate: capture-mode harvests are ignored
    cm.capturing = True
    before = cm.predict_ms("mixed", key)
    for _ in range(20):
        cm.on_harvest("mixed", key, span_s=9.0)
    cm.capturing = False
    assert cm.predict_ms("mixed", key) == pytest.approx(before)


# ---------------------------------------------------------------------------
# flood behaviour with an explicit ITL budget armed


def test_no_decode_starvation_under_itl_budget(model, monkeypatch):
    """With an explicit ITL budget armed, the flood schedule completes
    with no starved stream, the cost packer engages (and only ever
    shrinks within the warmed bucket set), and decode priority holds:
    every fused dispatch carrying prefill tokens while a slot decoded
    also advanced >=1 decode row."""
    spec, params, tk = model
    monkeypatch.setenv("LOCALAI_COST_SCHED", "on")
    monkeypatch.setenv("LOCALAI_ITL_BUDGET_MS", "25")
    eng = _engine(model)
    try:
        assert eng._itl_budget_ms() == 25.0  # the budget really armed
        picks = []
        orig_shape = eng._mixed_shape

        def spy_shape(rems, budget_ms=0.0, window_of=None):
            shape = orig_shape(rems, budget_ms, window_of)
            if budget_ms > 0.0:
                picks.append((orig_shape(rems), shape, budget_ms))
            return shape

        eng._mixed_shape = spy_shape
        dspy = DispatchSpy(eng)
        results = _mixed_schedule(eng, tk)
        warmed = {(r, b) for r, b, _ in eng._mixed_variants()}
    finally:
        eng.close()
    for name, (gen, ev) in results.items():
        assert ev.finish_reason == "length", (name, ev.error)
        assert len(gen) == ev.completion_tokens > 0
    # the packer actually ran against the armed budget...
    assert picks, "ITL budget armed but the packer never consulted"
    for cover, chosen, budget_ms in picks:
        assert budget_ms == 25.0
        assert chosen[1] <= cover[1], "cost packing may only shrink"
        assert chosen in warmed, "picked a never-warmed shape"
    # ...and decode never starved while prefill rode along
    carrying = [r for r in dspy.mixed()
                if r["prefill_tokens"] and r["decoding"]]
    for r in carrying:
        assert r["decode_rows"] >= 1, (
            f"budgeted mixed dispatch starved decode: {r}")


# ---------------------------------------------------------------------------
# knob registration + parsing


def test_cost_sched_knobs_registered():
    for name, kind, default in (
            ("LOCALAI_PREFILL_GROUP_TOKENS", "int", "8192"),
            ("LOCALAI_COST_SCHED", "flag", "on"),
            ("LOCALAI_ITL_BUDGET_MS", "float", "0")):
        k = knobs.REGISTRY[name]
        assert k.kind == kind and k.default == default


def test_cost_sched_knob_parsing(monkeypatch):
    monkeypatch.delenv("LOCALAI_COST_SCHED", raising=False)
    monkeypatch.delenv("LOCALAI_ITL_BUDGET_MS", raising=False)
    monkeypatch.delenv("LOCALAI_PREFILL_GROUP_TOKENS", raising=False)
    assert knobs.flag("LOCALAI_COST_SCHED") is True  # on by default...
    assert knobs.float_("LOCALAI_ITL_BUDGET_MS") == 0.0  # ...but inert
    assert knobs.int_("LOCALAI_PREFILL_GROUP_TOKENS") == 8192
    monkeypatch.setenv("LOCALAI_ITL_BUDGET_MS", "2.5")
    assert knobs.float_("LOCALAI_ITL_BUDGET_MS") == 2.5
    monkeypatch.setenv("LOCALAI_ITL_BUDGET_MS", "nope")  # garbage ->
    assert knobs.float_("LOCALAI_ITL_BUDGET_MS") == 0.0  # default
    monkeypatch.setenv("LOCALAI_PREFILL_GROUP_TOKENS", "bad")
    assert knobs.int_("LOCALAI_PREFILL_GROUP_TOKENS") == 8192


def test_engine_honors_prefill_group_knob(model, monkeypatch):
    """LOCALAI_PREFILL_GROUP_TOKENS is read once at construction and
    caps the prompt group's rows x bucket by lowering the row count; a
    value under every bucket leaves one row a step (no geometry turns
    the admission path off). Budget gating: a negative budget clamps to
    0 and LOCALAI_COST_SCHED=off zeroes the budget regardless."""
    spec, params, tk = model
    monkeypatch.setenv("LOCALAI_PREFILL_GROUP_TOKENS", "64")
    eng = LLMEngine(spec, params, tk, n_slots=4, max_seq=256,
                    prefill_buckets=(8, 32, 128),
                    cache_dtype=jnp.float32, autostart=False)
    try:
        assert eng._prefill_group_tokens == 64
        assert [eng._row_ladder(b) for b in (8, 32, 128)] == [
            (4,), (2,), (1,)]
        assert "LOCALAI_MIXED" + "_DISPATCH" not in knobs.REGISTRY
        monkeypatch.setenv("LOCALAI_ITL_BUDGET_MS", "-5")
        assert eng._itl_budget_ms() == 0.0  # negative clamps to off
        monkeypatch.setenv("LOCALAI_ITL_BUDGET_MS", "5")
        monkeypatch.setenv("LOCALAI_COST_SCHED", "off")
        assert eng._itl_budget_ms() == 0.0  # kill switch wins
        assert not eng._cost_sched_on()
    finally:
        eng.close()
    monkeypatch.setenv("LOCALAI_PREFILL_GROUP_TOKENS", "16")
    eng = LLMEngine(spec, params, tk, n_slots=4, max_seq=256,
                    prefill_buckets=(8, 32, 128),
                    cache_dtype=jnp.float32, autostart=False)
    try:
        # no bucket fits 16 tokens across 4 slots: the same path, one
        # row at a time above the smallest bucket
        assert eng._prefill_group_tokens == 16
        assert [eng._row_ladder(b) for b in (8, 32, 128)] == [
            (2,), (1,), (1,)]
        assert {(r, b) for r, b, _ in eng._mixed_variants()} == {
            (2, 8), (1, 32), (1, 128)}
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# cost-row persistence across warmup reuse


def test_cost_rows_export_import_roundtrip(cpu_peaks):
    """export_rows/import_rows round-trip every dispatch-key shape the
    engine produces (nested tuples, bools, None windows); corrupt
    entries are skipped and existing rows win."""
    cm = CostModel("t", "cpu")
    rows = {
        ("prefill_final", 1, 32, 128, False): (1e9, 2e9),
        ("mixed", (4, 32), 128): (3e9, 4e9),
        ("decodek", 8, 128, 1): (5e9, 6e9),
        ("prefill", 128, None, True): (7e9, 8e9),
    }
    with cm._lock:
        cm._table.update(rows)
    blob = cm.export_rows()
    assert all(isinstance(k, str) for k in blob)

    cm2 = CostModel("t", "cpu")
    assert cm2.import_rows(blob) == len(rows)
    assert cm2.captured() == rows
    # predictions work off the imported rows alone (bytes term
    # dominates this row's roofline: 6e9 B / 50e9 B/s = 120 ms)
    assert cm2.predict_ms(
        "decodek", ("decodek", 8, 128, 1)) == pytest.approx(
        6e9 / 50e9 * 1e3)
    # corrupt keys/values are skipped, existing rows never clobbered
    cm3 = CostModel("t", "cpu")
    with cm3._lock:
        cm3._table[("decodek", 8, 128, 1)] = (9.0, 9.0)
    added = cm3.import_rows({
        "not a tuple literal (": (1.0, 1.0),
        "'just_a_string'": (1.0, 1.0),
        repr(("decodek", 8, 128, 1)): (5e9, 6e9),
        repr(("mixed", (4, 32), 128)): "bad",
    })
    assert added == 0
    assert cm3.captured() == {("decodek", 8, 128, 1): (9.0, 9.0)}


@pytest.mark.slow  # three cold engine builds + two full warmup passes
def test_warmup_reuse_restores_cost_rows(model, tmp_path, monkeypatch):
    """The warmup-reuse skip path (persistent-cache marker) must not
    leave the predictor blind: the first warmup exports its captured
    cost table next to the marker, an identical-signature reuse imports
    it verbatim, and a marker whose sidecar is missing falls through to
    a full re-capturing pass that rewrites both."""
    import os

    import jax

    spec, params, tk = model
    monkeypatch.delenv("LOCALAI_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("LOCALAI_PEAK_HBM_GBS", raising=False)

    def build():
        return LLMEngine(spec, params, tk, n_slots=2, max_seq=64,
                         prefill_buckets=(8,), cache_dtype=jnp.float32,
                         autostart=False)

    prev_cache = jax.config.jax_compilation_cache_dir
    prev_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        eng1 = build()
        try:
            eng1.warmup()
            rows = eng1._costmodel.captured()
            marker = eng1._warmup_marker_path()
        finally:
            eng1.close()
        assert not eng1.warmup_reused
        assert rows, "warmup captured no cost rows"
        assert os.path.exists(marker)
        assert os.path.exists(marker + ".cost.json")

        eng2 = build()
        try:
            eng2.warmup()
            assert eng2.warmup_reused
            assert eng2._costmodel.captured() == rows
        finally:
            eng2.close()

        # marker without sidecar (pre-sidecar format): reuse declined,
        # full pass re-captures and heals the sidecar
        os.remove(marker + ".cost.json")
        eng3 = build()
        try:
            eng3.warmup()
            assert not eng3.warmup_reused
            assert eng3._costmodel.captured() == rows
        finally:
            eng3.close()
        assert os.path.exists(marker + ".cost.json")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_cache)
        jax.config.update("jax_enable_compilation_cache", prev_on)
