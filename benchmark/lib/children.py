"""The processes the harness starts, and how it stops them.

The benchmark's own copy of chip_smoke.py's ``Children`` / ``Server``:
one chip owner at a time, the parent never imports JAX. The server is
``python -m localai_tfp_tpu.server`` exactly as an operator starts it;
everything it writes lands under the benchmark's cache directory.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request


class HarnessFailure(Exception):
    """The run cannot produce a result; the message says why."""


class Children:
    """Every process this harness starts, so all of them can be stopped
    and waited for."""

    def __init__(self) -> None:
        self._procs: list = []
        self._lock = threading.Lock()

    def spawn(self, argv: list, **kw) -> subprocess.Popen:
        proc = subprocess.Popen(argv, start_new_session=True, **kw)
        with self._lock:
            self._procs.append(proc)
        return proc

    def stop_all(self) -> None:
        with self._lock:
            procs = list(self._procs)
        for proc in procs:
            if proc.poll() is None:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                except OSError:
                    pass
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                pass


CHILDREN = Children()


def child_env(root: str, extra: "dict | None" = None) -> dict:
    """This checkout importable; whatever the caller exported left
    alone — nothing here sets JAX_PLATFORMS or the compile cache
    directory (localai_tfp_tpu/utils/compile_cache.py decides: the
    environment's JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
    BENCH_RUN is the driver's own and is not passed on."""
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [root, env.get("PYTHONPATH", "")] if p)
    env.update(extra or {})
    return env


_DEVICE_PROBE = r"""
import json, sys
import jax
devs = jax.devices()
d = devs[0]
print(json.dumps({"platform": d.platform, "kind": d.device_kind,
                  "count": len(devs)}))
"""


def probe_device(root: str, timeout: float = 180) -> dict:
    """What JAX finds, asked in a child (the chip is released when it
    exits)."""
    proc = CHILDREN.spawn([sys.executable, "-c", _DEVICE_PROBE],
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, cwd=root, env=child_env(root))
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        CHILDREN.stop_all()
        raise HarnessFailure("device probe did not finish")
    for line in reversed(out.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise HarnessFailure(f"device probe failed (rc={proc.returncode}): "
                         f"{err[-600:]}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """The server child. cwd is ``home`` so ensure_dirs()'s relative
    defaults land there; state (profiles) goes to ``state_dir``."""

    def __init__(self, root: str, home: str, models_dir: str,
                 state_dir: str, log_path: str,
                 extra_env: "dict | None" = None) -> None:
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = log_path
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        os.makedirs(state_dir, exist_ok=True)
        self._log = open(log_path, "wb")
        env = {"STATE_DIR": state_dir,
               "LOCALAI_QUANT_CACHE_DIR": os.path.join(home, "quant")}
        env.update(extra_env or {})
        self.proc = CHILDREN.spawn(
            [sys.executable, "-m", "localai_tfp_tpu.server",
             "--models-path", models_dir,
             "--address", "127.0.0.1", "--port", str(self.port)],
            cwd=home, stdout=self._log, stderr=subprocess.STDOUT,
            env=child_env(root, env))

    def log_tail(self, n: int = 3000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode(errors="replace")
        except OSError:
            return ""

    def wait_ready(self, timeout: float) -> None:
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if self.proc.poll() is not None:
                raise HarnessFailure(
                    f"server exited rc={self.proc.returncode} before "
                    f"/readyz: {self.log_tail()}")
            try:
                with urllib.request.urlopen(self.base + "/readyz",
                                            timeout=2) as r:
                    if r.status == 200:
                        return
            except (urllib.error.URLError, OSError):
                time.sleep(0.1)
        raise HarnessFailure(f"no /readyz within {timeout:.0f}s: "
                             f"{self.log_tail()}")

    def get(self, path: str, timeout: float = 30) -> bytes:
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return r.read()

    def get_json(self, path: str, timeout: float = 30):
        return json.loads(self.get(path, timeout))

    def post(self, path: str, body: dict, timeout: float):
        """-> (status, parsed JSON | raw text)."""
        req = urllib.request.Request(
            self.base + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode(errors="replace")[:600]

    def stop(self, timeout: float = 90) -> int:
        """SIGTERM, as an operator would; -> exit code."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                CHILDREN.stop_all()
                raise HarnessFailure(
                    f"server ignored SIGTERM for {timeout:.0f}s: "
                    f"{self.log_tail()}")
        self._log.close()
        return self.proc.returncode
