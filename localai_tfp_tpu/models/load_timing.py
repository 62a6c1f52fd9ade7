"""Phase-timing breakdown for model cold starts.

An early bench round reported a checkpoint load far longer than the
load path's own annotation expected, with no way to say where the
difference went. This module is the instrument that makes such a gap
impossible to hide: every load accumulates wall time into named phases

    read_s      host IO: checkpoint/artifact bytes off disk
    dequant_s   host compute: gguf dequantize, host-staged quantize
    transfer_s  host->device placement (incl. the fused on-device
                cast/transpose/quantize commit of the streaming path)
    compile_s   engine construction (jit setup, cache allocation)
    warmup_s    dispatch-variant precompile (engine.warmup)

and the total. Phases are measured as MAIN-THREAD blocking time: when
the streaming loader overlaps a host read with a device transfer, the
overlapped read costs nothing on the wall clock and therefore reports
(correctly) near zero — the breakdown answers "where did the wall time
go", not "how much work happened". The accumulator is thread-safe so
reader-pool threads can bill their wait time too.

Surfaced on the loaded backend as ``load_breakdown``, via
``/backend/monitor``, and in bench.py's
``extra.checkpoint_load_breakdown``.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Optional

PHASES = ("read_s", "dequant_s", "transfer_s", "compile_s", "warmup_s")


class LoadPhases:
    """Thread-safe accumulator of per-phase seconds for one load."""

    def __init__(self) -> None:
        self._t = {p: 0.0 for p in PHASES}
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._tls = threading.local()

    def add(self, phase: str, seconds: float) -> None:
        if seconds <= 0.0 or getattr(self._tls, "muted", False):
            return
        with self._lock:
            self._t[phase] = self._t.get(phase, 0.0) + seconds

    @contextmanager
    def muted(self):
        """Suppress billing from the current thread. The streaming
        committer's reader-pool threads run leaf thunks whose inner
        reads are instrumented (load_params wraps the getter) — but the
        breakdown bills main-thread BLOCKING time, and the main thread
        already bills its wait on those futures. Without muting, an
        overlapped read would be counted twice."""
        prev = getattr(self._tls, "muted", False)
        self._tls.muted = True
        try:
            yield
        finally:
            self._tls.muted = prev

    @contextmanager
    def timed(self, phase: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(phase, time.perf_counter() - t0)

    def get(self, phase: str) -> float:
        with self._lock:
            return self._t.get(phase, 0.0)

    def as_dict(self, total_s: Optional[float] = None) -> dict:
        """Snapshot; ``other_s`` is the unattributed remainder (tokenizer
        load, config parse, ...) so the phases always reconcile against
        the total."""
        with self._lock:
            out = {p: round(v, 2) for p, v in self._t.items()}
        if total_s is None:
            total_s = time.perf_counter() - self._t0
        out["total_s"] = round(total_s, 2)
        out["other_s"] = round(
            max(0.0, total_s - sum(self._t.values())), 2)
        return out
