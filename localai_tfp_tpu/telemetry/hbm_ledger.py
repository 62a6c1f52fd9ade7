"""Component-level HBM byte ledger with OOM forensics.

Every long-lived device allocation the engine owns registers here at
its allocation site — weights, the paged KV arena, int8 scale planes,
the draft cache, sampler state, in-flight staging buffers — as either
a fixed byte count or a zero-argument callable (for components whose
footprint moves, like the staging transfer window). Each scheduler
sweep the engine reconciles the ledger against
``device.memory_stats()['bytes_in_use']``: per-component bytes land on
the ``engine_hbm_bytes{component}`` gauge family and the difference
between what the device reports and what the ledger can attribute goes
on an explicit ``unattributed`` drift row — drift is a signal (a leak,
an untracked buffer, XLA scratch), not something to hide.

On RESOURCE_EXHAUSTED anywhere in the engine/loader paths,
:func:`dump_post_mortem` writes a JSON forensics file (ledger snapshot,
kv_pool/kv_tier stats, per-device memory stats, flight-recorder tail,
the error) under ``state_dir`` and returns its path — today an OOM is
a bare XlaRuntimeError with nothing to autopsy.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Callable, Optional, Union

log = logging.getLogger("localai.hbm")

__all__ = ["HBMLedger", "nbytes_of", "looks_like_oom",
           "default_state_dir", "dump_post_mortem"]

Source = Union[int, float, Callable[[], int], Any]


def nbytes_of(tree: Any) -> int:
    """Total ``.nbytes`` across a pytree's array leaves."""
    import jax

    return sum(int(getattr(x, "nbytes", 0))
               for x in jax.tree_util.tree_leaves(tree))


def looks_like_oom(e: BaseException) -> bool:
    """Is this exception a device allocation failure? Matches the XLA
    RESOURCE_EXHAUSTED status text and the ``engine.hbm_alloc``
    faultinject point that simulates one in tests."""
    r = repr(e)
    return "RESOURCE_EXHAUSTED" in r or "engine.hbm_alloc" in r


def default_state_dir() -> str:
    """Where forensics land when the caller has no configured
    state_dir (STATE_DIR is the server's own env, not a LOCALAI_*
    knob)."""
    return os.environ.get("STATE_DIR") or "run"


class HBMLedger:
    """Byte attribution for one engine's device allocations.

    Sources are registered once per component and may be: a plain byte
    count, a zero-arg callable returning bytes (evaluated at read
    time), or a pytree whose leaves are measured via ``nbytes_of`` at
    registration. Thread-safe: allocation sites register from the
    loader/engine threads while /metrics scrapes snapshot concurrently.
    """

    def __init__(self, model: str = "default") -> None:
        self.model = model
        self._lock = threading.Lock()
        self._sources: dict[str, Source] = {}  # lint: guarded-by self._lock
        self._host: set[str] = set()  # lint: guarded-by self._lock
        self._last_reconcile: Optional[dict] = None  # lint: guarded-by self._lock

    def register(self, component: str, source: Source,
                 host: bool = False) -> None:
        """Attach/replace a component's byte source. Pytrees are
        measured once, now (re-register after reallocating).
        ``host=True`` marks a host-RAM component (the weight pager's
        warm tier): it still lands on the per-component gauge but is
        excluded from the device drift sum — host bytes can never
        explain ``bytes_in_use``."""
        if not (isinstance(source, (int, float)) or callable(source)):
            source = nbytes_of(source)
        with self._lock:
            self._sources[component] = source
            if host:
                self._host.add(component)
            else:
                self._host.discard(component)

    def drop(self, component: str) -> None:
        with self._lock:
            self._sources.pop(component, None)
            self._host.discard(component)

    def attributed(self) -> dict[str, int]:
        """Current bytes per component (callables evaluated outside
        the lock — they may touch other locks, e.g. staging's)."""
        with self._lock:
            items = list(self._sources.items())
        out: dict[str, int] = {}
        for name, src in items:
            try:
                out[name] = int(src() if callable(src) else src)
            except Exception:  # pragma: no cover - source raced close
                log.debug("ledger source %s failed", name,
                          exc_info=True)
                out[name] = 0
        return out

    def reconcile(self,
                  memory_stats: Optional[Callable[[], Optional[dict]]]
                  = None) -> dict:
        """Refresh the ``engine_hbm_bytes`` gauges and compute the
        drift row. ``memory_stats`` is an injectable provider returning
        ``device.memory_stats()``-shaped dicts (None / raising means
        the backend has no stats — CPU — and the drift row is omitted).
        """
        attr = self.attributed()
        in_use: Optional[int] = None
        peak: Optional[int] = None  # allocator high-water mark, where
        # the backend reports one
        provider = (memory_stats if memory_stats is not None
                    else _device_memory_stats)
        try:
            st = provider()
            if st is not None:
                in_use = int(st.get("bytes_in_use", 0))
                if "peak_bytes_in_use" in st:
                    peak = int(st["peak_bytes_in_use"])
        except Exception:  # pragma: no cover - backend-specific
            log.debug("memory_stats provider failed", exc_info=True)
            in_use = None
        from . import metrics as tm

        for name, b in attr.items():
            tm.ENGINE_HBM_BYTES.labels(
                model=self.model, component=name).set(b)
        with self._lock:
            host = set(self._host)
        total = sum(b for n, b in attr.items() if n not in host)
        snap: dict[str, Any] = {"components": attr, "attributed": total,
                                "bytes_in_use": in_use,
                                "peak_bytes_in_use": peak}
        if in_use is not None:
            drift = in_use - total
            tm.ENGINE_HBM_BYTES.labels(
                model=self.model, component="unattributed").set(drift)
            snap["unattributed"] = drift
            snap["drift_ratio"] = (drift / in_use) if in_use else 0.0
        with self._lock:
            self._last_reconcile = snap
        return snap

    def snapshot(self) -> dict:
        """Last reconcile result (or a fresh attribution if none ran),
        for /backend/monitor and post-mortems."""
        with self._lock:
            last = self._last_reconcile
        if last is not None:
            return last
        attr = self.attributed()
        with self._lock:
            host = set(self._host)
        return {"components": attr,
                "attributed": sum(b for n, b in attr.items()
                                  if n not in host),
                "bytes_in_use": None}

    def reset_gauges(self) -> None:
        """Zero this model's component gauges (engine close)."""
        from . import metrics as tm

        attr = self.attributed()
        for name in list(attr) + ["unattributed"]:
            tm.ENGINE_HBM_BYTES.labels(
                model=self.model, component=name).set(0)


def _device_memory_stats() -> Optional[dict]:
    """memory_stats() of the first addressable device, or None where
    the backend does not implement it (CPU)."""
    import jax

    try:
        return jax.local_devices()[0].memory_stats()
    except Exception:  # pragma: no cover - backend-specific
        log.debug("device memory_stats unavailable", exc_info=True)
        return None


def dump_post_mortem(state_dir: str, model: str, error: BaseException,
                     ledger: Optional[HBMLedger] = None,
                     pool_stats: Any = None,
                     tier_stats: Optional[dict] = None,
                     weight_stats: Optional[dict] = None) -> Optional[str]:
    """Write an OOM forensics JSON under ``state_dir`` and return its
    path. Never raises — forensics must not mask the original failure.
    """
    try:
        from ..utils import sysinfo
        from .flightrec import FLIGHT

        trace = FLIGHT.export_chrome_trace()
        events = trace.get("traceEvents", [])
        report = {
            "kind": "hbm_post_mortem",
            "time": time.time(),
            "model": model,
            "error": repr(error),
            "ledger": ledger.snapshot() if ledger is not None else None,
            "kv_pool": (pool_stats._asdict()
                        if hasattr(pool_stats, "_asdict")
                        else pool_stats),
            "kv_tier": tier_stats,
            "weight_pager": weight_stats,
            "devices": sysinfo.device_memory(),
            "flightrec_tail": events[-256:],
        }
        pm_dir = os.path.join(state_dir or default_state_dir(),
                              "post_mortem")
        os.makedirs(pm_dir, exist_ok=True)
        path = os.path.join(pm_dir, f"hbm-{int(time.time() * 1e3)}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=2, default=str)
        log.error("HBM post-mortem written to %s (error: %r)",
                  path, error)
        return path
    except Exception as e:  # pragma: no cover - forensics best-effort
        log.warning("post-mortem dump failed: %r", e)
        from . import metrics as tm

        tm.RECOVERED_ERRORS.labels(site="hbm.post_mortem").inc()
        return None
