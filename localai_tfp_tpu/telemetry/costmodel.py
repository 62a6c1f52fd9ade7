"""Warmup-captured XLA cost model: per-dispatch FLOPs/bytes accounting.

The engine's warmup pass compiles every dispatch variant it will ever
run (that is warmup's whole point). This module rides that pass: while
capture mode is on, each variant's ``lower().compile()`` is repeated
AOT-style purely to read ``compiled.cost_analysis()`` — the XLA cost
model's flops and bytes-accessed estimates — and the result is stored
under the same (kind, shape-signature) key the engine's jit cache uses.
On TPU the persistent compile cache dedupes the second compile; on the
tiny CPU test models it is milliseconds.

From then on the hot path never touches the device for accounting:
every dispatch adds the captured flops/bytes of its variant to
host-held totals (the flightrec contract — zero syncs, zero device
work), exported as ``engine_device_flops_total{kind}`` and
``engine_device_bytes_total{kind}``. Flight-shaped kinds (mixed /
decodek) account at HARVEST, where the flight's wall span is
known, and each harvest also feeds an EWMA MFU estimate:

    mfu = captured_flops / (span_seconds * peak_flops * n_devices)

``roofline()`` classifies each kind compute- vs bandwidth-bound by
comparing its arithmetic intensity (flops / bytes accessed) against the
machine balance point ``peak_flops / peak_bw``; peaks come from a
built-in table keyed by ``device_kind`` (each row with its source),
overridable via ``LOCALAI_PEAK_FLOPS`` / ``LOCALAI_PEAK_HBM_GBS``. A
device that is not in the table is an error, not a default.

``predict_ms()`` turns the same table into a per-dispatch DEVICE-TIME
predictor, which is what cost-model-driven scheduling
(``LOCALAI_COST_SCHED`` + ``LOCALAI_ITL_BUDGET_MS``) packs against:

    analytic_ms = max(flops / peak_flops, bytes / peak_bw) / n_dev
    predicted   = analytic_ms * calibration_ewma[kind]

The analytic term is the roofline lower bound (whichever of compute or
bandwidth dominates); the calibration EWMA is the measured span /
analytic ratio folded at every flight harvest, so the predictor
absorbs dispatch RTT, achievable-fraction-of-peak, and kernel quality.
Calibration is two-level: a variant that has harvested predicts from
its OWN ratio EWMA (each variant's fixed overhead differs), cold
variants borrow the kind-level EWMA once it has
``_CALIB_MIN_SAMPLES`` harvests, and before that predictions fall back
to the bare analytic bound; variants never captured predict ``None``
and callers fall back to the token-budget heuristic. Harvests that carried
a prediction also feed ``engine_dispatch_predicted_seconds`` and the
``engine_dispatch_predicted_ratio`` (predicted / measured) histograms,
so calibration drift is observable on /metrics and in Perfetto.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Optional

from ..config import knobs

log = logging.getLogger("localai.costmodel")

__all__ = ["CostModel", "dispatch_key", "peak_rates",
           "analytic_flops_per_token", "FLIGHT_KINDS"]

# kinds whose device work completes asynchronously as a _Flight; these
# account at harvest (span known), everything else at dispatch
FLIGHT_KINDS = frozenset({"mixed", "decodek"})

# (peak FLOP/s, peak HBM bytes/s) per device, by jax ``device_kind``.
# These peaks feed predict_ms(), which sizes dispatches by default, so
# a device this table does not know is an error (peak_rates), never
# another device's row.
_PEAK_TABLE: dict[str, tuple[float, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM per chip
    "TPU v5 lite": (197e12, 819e9),
    # not a measurement: a laptop-class core (ridge = 50e9/50e9 = 1
    # flop/byte) for the CPU test suite. It puts the tiny f32 test
    # models on both sides of the ridge: XLA measures their decode at
    # ~0.2 flops/byte (weights re-read per token) and their batched
    # prefill at ~2.3 (weights amortized per bucket).
    "cpu": (50e9, 50e9),
}

_EWMA_ALPHA = 0.2

# calibration harvests of a kind before predict_ms() trusts its EWMA;
# below it the bare analytic roofline bound is the prediction (a cold
# EWMA from one outlier span would poison every early prediction)
_CALIB_MIN_SAMPLES = 3

# winsorization bound for calibration samples: measured spans include
# host-side noise (scheduler preemption can turn a 0.3 ms dispatch into
# a 6 ms span), and one 20x outlier shifts an alpha-0.2 EWMA by 4x —
# clip each sample to within this factor of the trusted estimate so a
# spike nudges the EWMA instead of poisoning it
_CALIB_CLIP = 4.0


def peak_rates(device_kind: str) -> tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) per device — knob overrides first,
    then the ``device_kind`` table. Raises for a device the table does
    not know (unless both knobs stand in for it)."""
    flops = knobs.float_("LOCALAI_PEAK_FLOPS")
    bw = knobs.float_("LOCALAI_PEAK_HBM_GBS") * 1e9
    if flops > 0 and bw > 0:
        return flops, bw
    row = _PEAK_TABLE.get(device_kind)
    if row is None:
        raise ValueError(
            f"no peak FLOP/s / HBM bandwidth known for device_kind "
            f"{device_kind!r} (known: {sorted(_PEAK_TABLE)}): add a row "
            "with its source to telemetry/costmodel._PEAK_TABLE, or set "
            "both LOCALAI_PEAK_FLOPS and LOCALAI_PEAK_HBM_GBS")
    return (flops if flops > 0 else row[0],
            bw if bw > 0 else row[1])


def dispatch_key(kind: str, payload: dict) -> tuple:
    """The shape signature that selects a compiled variant — must vary
    exactly when the engine's jit-cache key varies, so each captured
    cost row matches the executable the dispatch actually runs."""
    p = payload
    if kind == "mixed":  # the prompt group's [rows, bucket]
        toks = p["toks"]
        return (kind, tuple(toks.shape), p.get("window"))
    if kind == "decodek":
        return (kind, p["k"], p.get("window"), p.get("depth", 1))
    if kind == "prefill":
        toks = p["toks"]
        return (kind, toks.shape[-1], p.get("window"))
    if kind in ("spec", "spec_s"):
        return (kind, p.get("kd"), p.get("rounds"))
    if kind == "kvcopy":
        return (kind, p.get("n"))
    if kind == "embed":
        return (kind, p.get("bucket"))
    return (kind,)


def variant_key(kind: str, payload: dict) -> tuple:
    """``dispatch_key`` plus the inputs that select another executable
    WITHOUT changing the cost row — what a program-load event has to
    name to tell two loads of one kind apart (telemetry/flightrec.py
    LoadWatch). ``carry``: a decodek or mixed step whose token and
    position inputs are the device-resident carry of the dispatch
    before it (committed arrays) and not fresh host arrays lowers again
    on a meshed engine (an unmeshed one commits what the host feeds:
    one executable either way, LLMEngine.__init__); ``masks``/``soft``:
    None or an array is a different argument tree. Kept out of
    ``dispatch_key`` itself: the cost table is keyed by it, and the
    warmup pass captures rows with ``carry: False`` only."""
    p = payload
    key = dispatch_key(kind, p)
    if kind in ("decodek", "mixed"):
        key += (("carry", bool(p.get("carry"))),)
    if "masks" in p:
        key += (("masks", p["masks"] is not None),)
    if "soft" in p:
        key += (("soft", p["soft"] is not None),)
    return key


def _extract_costs(analysis: dict) -> tuple[float, float]:
    """(flops, bytes accessed) from a ``compiled.cost_analysis()`` dict
    (what the installed jax returns on CPU and TPU alike)."""
    return (float(analysis.get("flops", 0.0)),
            float(analysis.get("bytes accessed", 0.0)))


def analytic_flops_per_token(params: Any) -> float:
    """First-principles decode FLOPs/token: 2 x matrix params (every
    ndim>=2 leaf — one multiply-accumulate per weight per token). The
    tests cross-check the captured cost model against this to a
    generous tolerance; the XLA estimate additionally counts attention
    and norm flops, so captured >= analytic is the expected shape."""
    import jax

    sizes = [int(x.size) for x in jax.tree_util.tree_leaves(params)
             if hasattr(x, "ndim") and x.ndim >= 2]
    return 2.0 * float(sum(sizes))


class CostModel:
    """Per-engine dispatch cost table + host-held accounting.

    Thread contract: ``capture`` runs on the engine thread during
    warmup; ``on_dispatch`` / ``on_harvest`` run on the engine thread
    only; ``stats`` / ``roofline`` may be called from any thread (the
    single lock covers the shared tables).
    """

    def __init__(self, model: str, device_kind: str,
                 n_devices: int = 1) -> None:
        self.model = model
        self.device_kind = device_kind
        peak_rates(device_kind)  # an unknown device fails construction
        self.n_devices = max(1, int(n_devices))
        self.capturing = False
        self._lock = threading.Lock()
        # (kind, sig) -> (flops, bytes)
        self._table: dict[tuple, tuple[float, float]] = {}
        # kind -> [flops, bytes, dispatches]
        self._totals: dict[str, list[float]] = {}
        self._mfu: Optional[float] = None  # EWMA, None until 1st sample
        self._mfu_samples = 0
        # kind -> [measured/analytic EWMA, samples] — the per-kind
        # calibration predict_ms() multiplies onto the analytic bound
        self._calib: dict[str, list] = {}
        # (kind, sig) -> [measured/analytic EWMA, samples] — per-
        # variant refinement: each variant's fixed dispatch overhead
        # differs (a tiny bucket's span is mostly RTT, a big one's
        # mostly compute), so a variant that has harvested predicts
        # from its own ratio and only cold variants borrow the kind's
        self._calib_var: dict[tuple, list] = {}

    # ------------------------------------------------------- capture

    def capture(self, kind: str, key: tuple, fn, args: tuple,
                kwargs: Optional[dict] = None) -> None:
        """AOT-compile one dispatch variant and record its cost row.
        Failures degrade to a missing row (dispatch accounting skips
        it) — the cost model must never break serving."""
        with self._lock:
            if key in self._table:
                return
        try:
            compiled = fn.lower(*args, **(kwargs or {})).compile()
            flops, by = _extract_costs(compiled.cost_analysis())
        except Exception as e:  # pragma: no cover - backend-specific
            log.debug("cost capture failed for %s: %r", key, e)
            from . import metrics as tm

            tm.RECOVERED_ERRORS.labels(site="costmodel.capture").inc()
            return
        with self._lock:
            self._table[key] = (flops, by)

    def captured(self) -> dict[tuple, tuple[float, float]]:
        with self._lock:
            return dict(self._table)

    def export_rows(self) -> dict[str, tuple[float, float]]:
        """JSON-serializable snapshot of the captured cost table.
        Dispatch keys are tuples of primitives, so ``repr`` round-trips
        through ``ast.literal_eval`` in :meth:`import_rows`."""
        with self._lock:
            return {repr(k): v for k, v in self._table.items()}

    def import_rows(self, rows: dict) -> int:
        """Load previously exported cost rows (the warmup-reuse path:
        an identical warmup signature means the variant set — and hence
        each variant's XLA cost row — is identical, so the sidecar
        written by the engine that DID warm up stands in for a fresh
        capture pass). Existing rows win; returns rows added."""
        import ast

        added = 0
        with self._lock:
            for rk, v in rows.items():
                try:
                    key = ast.literal_eval(rk)
                    flops, by = float(v[0]), float(v[1])
                except (ValueError, SyntaxError, TypeError, IndexError):
                    continue
                if not isinstance(key, tuple) or key in self._table:
                    continue
                self._table[key] = (flops, by)
                added += 1
        return added

    # ---------------------------------------------------- accounting

    def _account(self, kind: str, key: Optional[tuple]) -> float:
        """Add one dispatch of ``key`` to the totals; returns its
        flops (0 when the variant was never captured)."""
        if key is None:
            return 0.0
        with self._lock:
            row = self._table.get(key)
            if row is None:
                return 0.0
            t = self._totals.setdefault(kind, [0.0, 0.0, 0.0])
            t[0] += row[0]
            t[1] += row[1]
            t[2] += 1.0
            flops = row[0]
        from . import metrics as tm

        tm.ENGINE_DEVICE_FLOPS.labels(model=self.model,
                                      kind=kind).inc(row[0])
        tm.ENGINE_DEVICE_BYTES.labels(model=self.model,
                                      kind=kind).inc(row[1])
        return flops

    def on_dispatch(self, kind: str, key: Optional[tuple]) -> None:
        """Account a synchronously-completing dispatch (non-flight
        kinds). No-op in capture mode: warmup pads are not traffic."""
        if self.capturing:
            return
        self._account(kind, key)

    def on_harvest(self, kind: str, key: Optional[tuple],
                   span_s: float,
                   predicted_ms: Optional[float] = None) -> None:
        """Account a harvested flight and fold an MFU sample into the
        EWMA (the flight's enqueue-to-ready span is the denominator).
        The measured span also calibrates the device-time predictor for
        this kind, and when the dispatch carried a prediction the
        predicted-vs-measured pair lands on the two observability
        histograms."""
        flops = self._account(kind, key)
        if flops <= 0.0 or span_s <= 0.0:
            return
        peak_flops, _ = peak_rates(self.device_kind)
        sample = min(1.0, flops / (span_s * peak_flops * self.n_devices))
        span_ms = span_s * 1e3
        with self._lock:
            if self._mfu is None:
                self._mfu = sample
            else:
                self._mfu += _EWMA_ALPHA * (sample - self._mfu)
            self._mfu_samples += 1
            mfu = self._mfu
            # calibration: measured span / analytic roofline bound,
            # per kind — warmup pads never calibrate (their spans
            # include compile time)
            if not self.capturing:
                base = self._analytic_ms_locked(key)
                if base is not None and base > 0.0:
                    ratio = span_ms / base
                    kc = self._calib.get(kind)
                    anchor = (kc[0] if kc is not None
                              and kc[1] >= _CALIB_MIN_SAMPLES else None)
                    for table, ck in ((self._calib, kind),
                                      (self._calib_var, key)):
                        c = table.get(ck)
                        # winsorize against this entry's own trusted
                        # EWMA, else the kind's (a variant's FIRST
                        # sample landing on a spike would otherwise
                        # seed its whole refinement history)
                        ref = (c[0] if c is not None and c[1] >= 2
                               else anchor)
                        r = ratio if ref is None else min(
                            max(ratio, ref / _CALIB_CLIP),
                            ref * _CALIB_CLIP)
                        if c is None:
                            table[ck] = [r, 1]
                        else:
                            c[0] += _EWMA_ALPHA * (r - c[0])
                            c[1] += 1
        from . import metrics as tm

        tm.ENGINE_MFU.labels(model=self.model).set(mfu)
        if predicted_ms is not None and predicted_ms > 0.0:
            tm.ENGINE_DISPATCH_PREDICTED.labels(
                model=self.model, kind=kind).observe(predicted_ms / 1e3)
            tm.ENGINE_DISPATCH_PREDICTED_RATIO.labels(
                model=self.model, kind=kind).observe(
                    predicted_ms / span_ms)

    # ------------------------------------------------------ prediction

    def _analytic_ms_locked(self, key: Optional[tuple]
                            ) -> Optional[float]:
        """Roofline lower bound on device ms for one dispatch of
        ``key``: whichever of the compute or bandwidth terms dominates,
        spread across the mesh. None when the variant was never
        captured. Caller holds self._lock."""
        if key is None:
            return None
        row = self._table.get(key)
        if row is None:
            return None
        flops, by = row
        peak_flops, peak_bw = peak_rates(self.device_kind)
        t_s = max(flops / (peak_flops * self.n_devices),
                  by / (peak_bw * self.n_devices))
        return t_s * 1e3 if t_s > 0.0 else None

    def predict_ms(self, kind: str, key: Optional[tuple]
                   ) -> Optional[float]:
        """Predicted device-time (wall ms, enqueue to ready) for one
        dispatch of variant ``key``: the analytic roofline bound scaled
        by the variant's own calibration EWMA once it has harvested,
        else the kind-level EWMA once it has ``_CALIB_MIN_SAMPLES``
        harvests, else the bare analytic bound; ``None`` for a
        never-captured variant (callers fall back to the token-budget
        heuristic)."""
        with self._lock:
            base = self._analytic_ms_locked(key)
            if base is None:
                return None
            cv = self._calib_var.get(key)
            if cv is not None and cv[1] >= 2:
                return base * cv[0]
            c = self._calib.get(kind)
            if c is not None and c[1] >= _CALIB_MIN_SAMPLES:
                return base * c[0]
        return base

    def decode_step_ms(self) -> Optional[float]:
        """Predicted per-token decode ms: the cheapest captured decodek
        variant amortized over its scan length. None until a decodek
        variant is captured. Feeds queue-drain prediction when the
        engine's measured step EWMA has no samples yet."""
        with self._lock:
            keys = [k for k in self._table if k[0] == "decodek"]
        best: Optional[float] = None
        for key in keys:
            p = self.predict_ms("decodek", key)
            if p is None:
                continue
            per = p / max(1, int(key[1]))
            if best is None or per < best:
                best = per
        return best

    def prefill_token_ms(self) -> Optional[float]:
        """Predicted per-token prefill ms: the best (most amortized)
        captured prefill-shaped variant divided by its token capacity.
        Optimistic by construction — queue-drain and queued-deadline
        predictions built on it under-reject rather than over-reject."""
        with self._lock:
            keys = list(self._table)
        best: Optional[float] = None
        for key in keys:
            kind = key[0]
            if kind == "mixed":
                tokens = int(key[1][0]) * int(key[1][1])
            elif kind == "prefill":
                tokens = int(key[1])
            else:
                continue
            p = self.predict_ms(kind, key)
            if p is None or tokens <= 0:
                continue
            per = p / tokens
            if best is None or per < best:
                best = per
        return best

    # ------------------------------------------------------ summaries

    @property
    def mfu(self) -> Optional[float]:
        with self._lock:
            return self._mfu

    def roofline(self) -> dict[str, dict]:
        """Per-kind roofline summary: accounted totals, arithmetic
        intensity, and compute- vs bandwidth-bound classification
        against the machine balance point. Kinds with dispatch traffic
        use accounted totals; kinds only ever captured fall back to
        their captured rows so the classification exists pre-traffic."""
        peak_flops, peak_bw = peak_rates(self.device_kind)
        ridge = peak_flops / max(peak_bw, 1.0)
        with self._lock:
            per_kind: dict[str, list[float]] = {
                k: list(v) for k, v in self._totals.items()}
            with_traffic = set(per_kind)
            for (kind, *_), (fl, by) in self._table.items():
                if kind in with_traffic:
                    continue
                t = per_kind.setdefault(kind, [0.0, 0.0, 0.0])
                t[0] += fl
                t[1] += by
        out: dict[str, dict] = {}
        for kind, (fl, by, n) in sorted(per_kind.items()):
            intensity = fl / by if by > 0 else 0.0
            out[kind] = {
                "flops": fl,
                "bytes": by,
                "dispatches": int(n),
                "intensity_flops_per_byte": round(intensity, 3),
                "bound": ("compute" if intensity >= ridge
                          else "bandwidth"),
            }
        return out

    def stats(self) -> dict:
        """Host-held summary for /backend/monitor and bench."""
        peak_flops, peak_bw = peak_rates(self.device_kind)
        with self._lock:
            mfu = self._mfu
            samples = self._mfu_samples
            variants = len(self._table)
            calib = {k: {"ewma": round(c[0], 4), "samples": int(c[1]),
                         "warm": c[1] >= _CALIB_MIN_SAMPLES}
                     for k, c in sorted(self._calib.items())}
            variants_calibrated = sum(
                1 for c in self._calib_var.values() if c[1] >= 2)
        return {
            "device_kind": self.device_kind,
            "n_devices": self.n_devices,
            "peak_flops_per_device": peak_flops,
            "peak_hbm_bytes_s_per_device": peak_bw,
            "ridge_flops_per_byte": round(
                peak_flops / max(peak_bw, 1.0), 3),
            "mfu_ewma": round(mfu, 6) if mfu is not None else None,
            "mfu_samples": samples,
            "variants_captured": variants,
            # device-time predictor state: per-kind calibration EWMAs
            # plus the derived per-token rates the admission/deadline
            # predictions run on
            "calibration": calib,
            "variants_calibrated": variants_calibrated,
            "predicted_decode_step_ms": self.decode_step_ms(),
            "predicted_prefill_token_ms": self.prefill_token_ms(),
            "kinds": self.roofline(),
        }
