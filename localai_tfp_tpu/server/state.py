"""Application wiring: the four singletons + startup sequence.

Ref: core/application/application.go:9-14 (Application holds
BackendConfigLoader + ModelLoader + ApplicationConfig + templates.Evaluator)
and startup.go:20-164 (New: mkdir, config load, watchdog start).
"""

from __future__ import annotations

import logging
import time
from typing import Optional

from ..config.app_config import ApplicationConfig
from ..config.loader import ConfigLoader
from ..engine.loader import ModelLoader, WatchDog, register_default_backends
from ..engine.templating import Evaluator
from ..telemetry.registry import REGISTRY

log = logging.getLogger(__name__)


class Application:
    """The singleton bundle handed to every route handler."""

    def __init__(self, config: Optional[ApplicationConfig] = None) -> None:
        self.config = config or ApplicationConfig.from_env()
        self.config.ensure_dirs()
        self.config_loader = ConfigLoader(self.config.models_path)
        self.model_loader = ModelLoader(
            str(self.config.models_path),
            single_active_backend=self.config.single_active_backend,
        )
        self.evaluator = Evaluator(str(self.config.models_path))
        from ..gallery.service import GalleryService

        self.gallery = GalleryService(
            str(self.config.models_path), self.config.galleries
        )
        # the process-wide telemetry registry (telemetry/ — the
        # successor of the reference's metrics service, core/services/
        # metrics.go): HTTP middleware, engine scheduler, loader and
        # watchdog all record into it; GET /metrics renders it
        self.metrics = REGISTRY
        self.registry = None  # federation membership (when p2p_token set)
        if self.config.p2p_token:
            from ..parallel.federated import NodeRegistry

            self.registry = NodeRegistry(self.config.p2p_token)
        self.started_at = time.time()
        self.watchdog = WatchDog(
            self.model_loader,
            busy_timeout=self.config.watchdog_busy_timeout,
            idle_timeout=self.config.watchdog_idle_timeout,
            enable_busy=self.config.enable_watchdog_busy,
            enable_idle=self.config.enable_watchdog_idle,
        )

    def startup(self) -> None:
        # persistent XLA compile cache: cold-start compiles of the
        # serving executables are paid once per config, not per boot
        # (SURVEY.md §7 hard part #2 — TTFT must hide cold compiles).
        # JAX_COMPILATION_CACHE_DIR places it; see utils/compile_cache
        from ..utils import compile_cache

        log.info("compile cache: %s", compile_cache.configure())
        register_default_backends()
        n = self.config_loader.load_configs_from_path()
        log.info("loaded %d model configs from %s", n,
                 self.config.models_path)
        self.watchdog.start()
        self._start_config_watcher()

    def _start_config_watcher(self) -> None:
        """Hot-reload of api_keys.json / external_backends.json
        (ref: core/application/config_file_watcher.go)."""
        from ..config.watcher import ConfigWatcher

        self.config_watcher = ConfigWatcher(str(self.config.config_dir))
        startup_keys = list(self.config.api_keys)

        def on_api_keys(data) -> None:
            # file keys EXTEND the startup keys; removal restores them
            # (ref: config_file_watcher.go readApiKeysJson — never lets a
            # dropped file disable auth that was configured at boot)
            file_keys = [str(k) for k in data] if isinstance(data, list) \
                else []
            self.config.api_keys = startup_keys + [
                k for k in file_keys if k not in startup_keys
            ]

        external_names: set[str] = set()  # names THIS handler registered

        def on_external_backends(data) -> None:
            from ..engine.loader import ALIASES, registry
            from ..workers.remote import RemoteOpenAIBackend

            wanted: set[str] = set()
            for name, spec in (data or {}).items():
                if isinstance(spec, str):
                    spec = {"base_url": spec}
                url = spec.get("base_url") or spec.get("uri") or ""
                key = spec.get("api_key", "")
                lname = name.strip().lower()
                # refuse to shadow anything that isn't ours: alias names
                # AND already-registered builtin factories
                if lname in ALIASES or (
                    lname in registry.known()
                    and lname not in external_names
                ):
                    log.warning(
                        "external backend name '%s' collides with a "
                        "builtin backend; skipping", name)
                    continue
                # lookups lowercase via resolve_backend, so register the
                # lowercased name
                registry.register(
                    lname,
                    lambda url=url, key=key: RemoteOpenAIBackend(url, key),
                )
                wanted.add(lname)
                log.info("registered external backend '%s' -> %s",
                         name, url)
            # entries dropped from the file (or the whole file removed)
            # are deregistered — a hot-reload removal must actually remove
            for stale in external_names - wanted:
                registry.unregister(stale)
                log.info("removed external backend '%s'", stale)
            external_names.clear()
            external_names.update(wanted)

        self.config_watcher.watch("api_keys.json", on_api_keys)
        self.config_watcher.watch("external_backends.json",
                                  on_external_backends)
        self.config_watcher.start()

    def shutdown(self) -> None:
        watcher = getattr(self, "config_watcher", None)
        if watcher is not None:
            watcher.stop()
        self.watchdog.stop()
        self.model_loader.stop_all()
