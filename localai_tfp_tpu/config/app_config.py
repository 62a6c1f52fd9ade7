"""Application-level configuration.

Ref: core/config/application_config.go — ~40 functional options; here a
single dataclass with env-var loading (LOCALAI_* aliases kept, ref:
core/cli/run.go:22-72).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional


def _env(name: str, default=None, cast=str):
    for key in (f"LOCALAI_{name}", name):
        v = os.environ.get(key)
        if v is not None:
            if cast is bool:
                return v.lower() in ("1", "true", "yes", "on")
            return cast(v)
    return default


@dataclass
class ApplicationConfig:
    models_path: str = "models"
    generated_content_dir: str = "generated_content"
    upload_dir: str = "uploads"
    config_dir: str = "configuration"
    state_dir: str = "run"  # runtime state (server.pid) — NOT the CWD,
    # which an unclean exit would litter with stray pid files
    address: str = "0.0.0.0"
    port: int = 8080
    api_keys: list[str] = field(default_factory=list)
    cors: bool = False
    cors_allow_origins: str = ""
    csrf: bool = False
    upload_limit_mb: int = 15
    threads: int = 0
    context_size: int = 0
    f16: bool = True
    debug: bool = False
    parallel_requests: bool = True
    single_active_backend: bool = False
    preload_models: list[str] = field(default_factory=list)
    galleries: list[dict] = field(default_factory=list)
    autoload_galleries: bool = True
    enable_watchdog_idle: bool = False
    enable_watchdog_busy: bool = False
    watchdog_idle_timeout: float = 15 * 60.0
    watchdog_busy_timeout: float = 5 * 60.0
    disable_metrics: bool = False
    opaque_errors: bool = False
    machine_tag: str = ""
    # federation (ref: run.go p2p flags; core/p2p token/network id)
    p2p_token: str = ""
    federated_server_url: str = ""  # balancer to announce to
    advertise_address: str = ""  # how the balancer should reach us
    node_name: str = ""
    # TPU-native:
    mesh_shape: dict[str, int] = field(default_factory=dict)

    @classmethod
    def from_env(cls) -> "ApplicationConfig":
        cfg = cls()
        cfg.models_path = _env("MODELS_PATH", cfg.models_path)
        cfg.state_dir = _env("STATE_DIR", cfg.state_dir)
        cfg.address = _env("ADDRESS", cfg.address)
        port = _env("PORT", None)
        if port is not None:
            cfg.port = int(port)
        keys = _env("API_KEY", None)
        if keys:
            cfg.api_keys = [k.strip() for k in keys.split(",") if k.strip()]
        cfg.debug = _env("DEBUG", cfg.debug, bool)
        cfg.f16 = _env("F16", cfg.f16, bool)
        cfg.parallel_requests = _env("PARALLEL_REQUESTS", cfg.parallel_requests, bool)
        cfg.single_active_backend = _env(
            "SINGLE_ACTIVE_BACKEND", cfg.single_active_backend, bool
        )
        cfg.enable_watchdog_idle = _env(
            "WATCHDOG_IDLE", cfg.enable_watchdog_idle, bool
        )
        cfg.enable_watchdog_busy = _env(
            "WATCHDOG_BUSY", cfg.enable_watchdog_busy, bool
        )
        cfg.cors = _env("CORS", cfg.cors, bool)
        cfg.cors_allow_origins = _env(
            "CORS_ALLOW_ORIGINS", cfg.cors_allow_origins)
        cfg.disable_metrics = _env("DISABLE_METRICS", cfg.disable_metrics, bool)
        cfg.opaque_errors = _env("OPAQUE_ERRORS", cfg.opaque_errors, bool)
        cfg.machine_tag = _env("MACHINE_TAG", cfg.machine_tag)
        cfg.upload_limit_mb = int(_env("UPLOAD_LIMIT", cfg.upload_limit_mb))
        galleries = _env("GALLERIES", None)
        if galleries:
            import json

            try:
                cfg.galleries = json.loads(galleries)
            except ValueError:
                pass
        preload = _env("PRELOAD_MODELS", None)
        if preload:
            cfg.preload_models = [m.strip() for m in preload.split(",")
                                  if m.strip()]
        ctx = _env("CONTEXT_SIZE", None)
        if ctx is not None:
            cfg.context_size = int(ctx)
        threads = _env("THREADS", None)
        if threads is not None:
            cfg.threads = int(threads)
        cfg.p2p_token = _env("P2P_TOKEN", cfg.p2p_token)
        cfg.federated_server_url = _env(
            "FEDERATED_SERVER", cfg.federated_server_url)
        cfg.advertise_address = _env(
            "ADVERTISE_ADDRESS", cfg.advertise_address)
        cfg.node_name = _env("NODE_NAME", cfg.node_name)
        return cfg

    def ensure_dirs(self) -> None:
        for d in (
            self.models_path,
            self.generated_content_dir,
            self.upload_dir,
            self.config_dir,
            self.state_dir,
        ):
            Path(d).mkdir(parents=True, exist_ok=True)
