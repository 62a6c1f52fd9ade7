#!/usr/bin/env python3
"""chip_smoke.py — does the serving path start, compile and answer on the chip?

    python3 chip_smoke.py            # from the root of a checkout, on a TPU

Drives the normal path once, through the entry points a user calls, at
the published widths of Mistral-7B-Instruct-v0.3 with every default left
on: a seeded random checkpoint in the real HF layout, a model YAML,
``python -m localai_tfp_tpu.server``, requests over HTTP. Phases, each a
child process so the chip has ONE owner at a time (this process never
imports JAX):

  device      JAX must report platform "tpu" (else exit 2, nothing
              written, no summary)
  kernel      python -m localai_tfp_tpu.ops.kernel_check at this model's
              head geometry: the compiled ragged kernel vs its reference
  checkpoint  sharded safetensors + tokenizer + YAML in a temp dir
  server_cold start the server, load + warm up, 20 requests covering
              every row kind (greedy chat, SSE stream, a 16-deep burst,
              a ~3000-token prompt and its repeat), /metrics and
              /backend/monitor checks, SIGTERM, clean exit
  server_warm same command, same compile cache, new process: the warmup
              must be reused and the greedy completion byte-identical

Stdout ends with two JSON lines. The second to last is the report: device,
versions, model and depth, compile cache, per-phase ok and seconds,
requests, peak HBM, ``"claim": null`` — its figures are observations of
one run, not benchmark metrics. The LAST line is the verdict and nothing
else, ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": 1}}``, with the device as JAX reported it; the exit code is 0
only if every phase passed.

The compile cache goes where JAX_COMPILATION_CACHE_DIR says, else
<checkout>/.jax_cache (localai_tfp_tpu/utils/compile_cache.py).
CHIP_SMOKE_LAYERS cuts or restores depth (widths are never cut); the
summary names the depth used.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# Mistral-7B-Instruct-v0.3, config.json as published (mistralai/
# Mistral-7B-Instruct-v0.3); only num_hidden_layers may be cut, below
MODEL_NAME = "mistral-7b-instruct-v0.3"
PUBLISHED_LAYERS = 32
HF_CONFIG = {
    "architectures": ["MistralForCausalLM"],
    "model_type": "mistral",
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "head_dim": 128,
    "num_hidden_layers": PUBLISHED_LAYERS,
    "vocab_size": 32768,
    "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-05,
    "sliding_window": None,
    "max_position_embeddings": 32768,
    "hidden_act": "silu",
    "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "bos_token_id": 32766,
    "eos_token_id": 32767,
}
# the model YAML a user would write
SERVING = {
    "backend": "jax-llm",
    "quantization": "int8_full",
    "kv_cache_dtype": "int8",
    "context_size": 4096,
    "max_batch_slots": 16,
}
# depth this script serves by default: the whole run, cold compile
# included, has to fit the 1200 s contract (see PERF.md "Bring-up" for
# the full-depth run made by hand)
DEFAULT_LAYERS = 32
DEADLINE_S = 1150.0  # give up (and clean up) before the caller's 1200 s

GREEDY_PROMPT = "Name three uses of a paged KV cache."
GREEDY_TOKENS = 24


class SmokeFailure(Exception):
    """A phase did not pass; the message says what was observed."""


# --------------------------------------------------------------- children


class Children:
    """Every process this script starts, so all of them can be stopped."""

    def __init__(self) -> None:
        self._procs: list[subprocess.Popen] = []
        self._lock = threading.Lock()

    def spawn(self, argv: list[str], **kw) -> subprocess.Popen:
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
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass


CHILDREN = Children()


def child_env(extra: "dict | None" = None) -> dict:
    """The environment children run in: this checkout importable, and
    whatever the caller exported left alone — in particular nothing here
    sets JAX_PLATFORMS or the compile cache directory."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [ROOT, env.get("PYTHONPATH", "")] if p)
    env.update(extra or {})
    return env


def run_child(argv: list[str], timeout: float) -> tuple[int, str, str]:
    proc = CHILDREN.spawn(argv, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True,
                          cwd=ROOT, env=child_env())
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        CHILDREN.stop_all()
        raise SmokeFailure(
            f"{' '.join(argv[:4])} … did not finish in {timeout:.0f}s")
    return proc.returncode, out, err


def last_json_line(text: str) -> dict:
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise SmokeFailure(f"no JSON line in child output: {text[-500:]!r}")


# ----------------------------------------------------------------- phases

_DEVICE_PROBE = r"""
import importlib.metadata as md, json, sys
import jax, jaxlib
devs = jax.devices()
d = devs[0]
try:
    libtpu = md.version("libtpu")
except md.PackageNotFoundError:
    libtpu = None
print(json.dumps({
    "platform": d.platform, "kind": d.device_kind, "count": len(devs),
    "jax": jax.__version__, "jaxlib": jaxlib.__version__,
    "libtpu": libtpu, "python": sys.version.split()[0]}))
sys.exit(0 if d.platform == "tpu" else 3)
"""


def phase_device() -> dict:
    """What JAX finds, in a child (the chip is released when it exits).
    Not a TPU → SmokeFailure before anything is written."""
    rc, out, err = run_child([sys.executable, "-c", _DEVICE_PROBE], 120)
    try:
        info = last_json_line(out)
    except (SmokeFailure, ValueError):
        raise SmokeFailure(
            f"device probe failed (rc={rc}): {err[-800:]}")
    if rc != 0 or info["platform"] != "tpu":
        raise SmokeFailure(
            f"JAX found platform {info['platform']!r} "
            f"({info['kind']}), not a TPU — this smoke only means "
            "something on the chip")
    return info


def phase_kernel(timeout: float) -> dict:
    """The compiled ragged kernel vs its reference at this model's head
    geometry and the engine's page size / row shapes."""
    argv = [sys.executable, "-m", "localai_tfp_tpu.ops.kernel_check",
            "--n-heads", str(HF_CONFIG["num_attention_heads"]),
            "--n-kv-heads", str(HF_CONFIG["num_key_value_heads"]),
            "--d-head", str(HF_CONFIG["head_dim"]),
            "--max-seq", str(SERVING["context_size"]),
            "--n-slots", str(SERVING["max_batch_slots"])]
    rc, out, err = run_child(argv, timeout)
    if rc != 0 and not out.strip():
        raise SmokeFailure(
            f"kernel_check crashed (rc={rc}): {err[-1500:]}")
    res = last_json_line(out)
    if rc != 0 or not res.get("ok") or res.get("platform") != "tpu":
        raise SmokeFailure(f"kernel_check failed (rc={rc}): "
                           f"{json.dumps(res)[:1200]}")
    return res


def write_model(models_dir: str, n_layers: int,
                mesh: "dict | None" = None) -> dict:
    """Checkpoint + tokenizer + YAML, as a user's models dir holds
    them. Returns {bytes, long_prompt} (the ~3000-token prompt is sized
    with the tokenizer just written)."""
    from tools.synth_checkpoint import (
        build_bpe_tokenizer, write_hf_checkpoint,
    )

    ckpt = os.path.join(models_dir, MODEL_NAME)
    config = dict(HF_CONFIG, num_hidden_layers=n_layers)
    nbytes = write_hf_checkpoint(
        ckpt, config, seed=0, threads=min(8, os.cpu_count() or 1))
    build_bpe_tokenizer(ckpt, config["vocab_size"])
    lines = [f"name: {MODEL_NAME}"]
    lines += [f"{k}: {v}" for k, v in SERVING.items()]
    lines += ["parameters:", f"  model: {MODEL_NAME}"]
    if mesh:
        lines += ["mesh:"] + [f"  {k}: {v}" for k, v in mesh.items()]
    lines += ["template:",
              '  chat_message: "{{.RoleName}}: {{.Content}}"',
              '  chat: "{{.Input}}\\nassistant:"']
    with open(os.path.join(models_dir, MODEL_NAME + ".yaml"), "w") as f:
        f.write("\n".join(lines) + "\n")
    # a prompt past the largest prefill bucket (2048) and inside the
    # context: ~3000 tokens by this tokenizer's own count
    from tokenizers import Tokenizer

    tk = Tokenizer.from_file(os.path.join(ckpt, "tokenizer.json"))
    words, text = 0, ""
    while len(tk.encode(text).ids) < 3000:
        text += " ".join(f"section {words + i} of the long report;"
                         for i in range(40)) + " "
        words += 40
    return {"bytes": nbytes, "long_prompt": text,
            "long_prompt_tokens": len(tk.encode(text).ids)}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """``python -m localai_tfp_tpu.server`` as a child, everything it
    writes (state, uploads, generated content, quant artifacts) inside
    the scratch dir: cwd is the scratch dir, so ensure_dirs()'s
    relative defaults land there and never in the checkout."""

    def __init__(self, scratch: str, tag: str) -> None:
        self.port = _free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(scratch, f"server_{tag}.log")
        self._log = open(self.log_path, "wb")
        self.proc = CHILDREN.spawn(
            [sys.executable, "-m", "localai_tfp_tpu.server",
             "--models-path", os.path.join(scratch, "models"),
             "--address", "127.0.0.1", "--port", str(self.port)],
            cwd=scratch, stdout=self._log, stderr=subprocess.STDOUT,
            env=child_env({
                "STATE_DIR": os.path.join(scratch, "run"),
                # the int8 artifact (7.5 GB at full depth) is this
                # run's to delete, not ~/.cache's to keep
                "LOCALAI_QUANT_CACHE_DIR": os.path.join(scratch, "quant"),
            }))

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
                raise SmokeFailure(
                    f"server exited rc={self.proc.returncode} before "
                    f"/readyz: {self.log_tail()}")
            try:
                with urllib.request.urlopen(self.base + "/readyz",
                                            timeout=2) as r:
                    if r.status == 200:
                        return
            except (urllib.error.URLError, OSError):
                time.sleep(0.25)
        raise SmokeFailure(f"no /readyz within {timeout:.0f}s: "
                           f"{self.log_tail()}")

    def get(self, path: str, timeout: float = 30) -> bytes:
        with urllib.request.urlopen(self.base + path,
                                    timeout=timeout) as r:
            return r.read()

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

    def stream(self, path: str, body: dict, timeout: float) -> dict:
        """POST with stream:true; -> {status, chunks (content deltas),
        done ([DONE] seen), finish_reason, completion_tokens}."""
        req = urllib.request.Request(
            self.base + path,
            data=json.dumps(dict(body, stream=True)).encode(),
            headers={"Content-Type": "application/json"})
        out = {"status": 0, "chunks": 0, "done": False,
               "finish_reason": None, "completion_tokens": None,
               "text": ""}
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                out["status"] = r.status
                for raw in r:
                    line = raw.decode(errors="replace").strip()
                    if not line.startswith("data:"):
                        continue
                    data = line[5:].strip()
                    if data == "[DONE]":
                        out["done"] = True
                        break
                    ev = json.loads(data)
                    ch = (ev.get("choices") or [{}])[0]
                    piece = (ch.get("delta") or {}).get("content") \
                        or ch.get("text") or ""
                    if piece:
                        out["chunks"] += 1
                        out["text"] += piece
                    if ch.get("finish_reason"):
                        out["finish_reason"] = ch["finish_reason"]
                    if ev.get("usage"):
                        out["completion_tokens"] = ev["usage"].get(
                            "completion_tokens")
        except urllib.error.HTTPError as e:
            out["status"] = e.code
        return out

    def stop(self, timeout: float = 90) -> int:
        """SIGTERM, as an operator would; -> exit code."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                CHILDREN.stop_all()
                raise SmokeFailure(
                    f"server ignored SIGTERM for {timeout:.0f}s: "
                    f"{self.log_tail()}")
        self._log.close()
        return self.proc.returncode


class Tally:
    """Requests sent / succeeded / failed, with the reason of each
    failure."""

    def __init__(self) -> None:
        self.sent = 0
        self.ok = 0
        self.failures: list[str] = []
        self._lock = threading.Lock()

    def record(self, what: str, problem: "str | None") -> None:
        with self._lock:
            self.sent += 1
            if problem is None:
                self.ok += 1
            else:
                self.failures.append(f"{what}: {problem}")

    def as_dict(self) -> dict:
        return {"sent": self.sent, "succeeded": self.ok,
                "failed": len(self.failures)}


def _check_completion(status, body, want_tokens: int) -> "str | None":
    """None when the response is a 200 that finished "stop"/"length"
    with the asked-for token count; else what was wrong."""
    if status != 200:
        return f"HTTP {status}: {str(body)[:300]}"
    ch = (body.get("choices") or [{}])[0]
    if ch.get("finish_reason") not in ("stop", "length"):
        return f"finish_reason {ch.get('finish_reason')!r}"
    got = (body.get("usage") or {}).get("completion_tokens")
    if got != want_tokens:
        return f"{got} completion tokens, asked for {want_tokens}"
    return None


def greedy_chat(srv: Server, tally: Tally, what: str,
                timeout: float) -> str:
    """The greedy non-stream chat completion; returns its text."""
    status, body = srv.post("/v1/chat/completions", {
        "model": MODEL_NAME, "temperature": 0, "ignore_eos": True,
        "max_tokens": GREEDY_TOKENS,
        "messages": [{"role": "user", "content": GREEDY_PROMPT}],
    }, timeout)
    problem = _check_completion(status, body, GREEDY_TOKENS)
    tally.record(what, problem)
    if problem is not None:
        raise SmokeFailure(f"{what}: {problem}\n{srv.log_tail()}")
    return body["choices"][0]["message"]["content"]


def monitor(srv: Server) -> dict:
    return json.loads(srv.get(f"/backend/monitor?model={MODEL_NAME}"))


def error_requests(metrics_text: str) -> float:
    """Sum of engine_requests_total{...reason="error"...} samples."""
    total = 0.0
    for line in metrics_text.splitlines():
        if line.startswith("engine_requests_total") \
                and 'reason="error"' in line:
            total += float(line.rsplit(" ", 1)[1])
    return total


def _engine_block(mon: dict) -> dict:
    """The monitor's account of the path that ran; SmokeFailure unless
    it is paged + the ragged kernel on platform tpu."""
    eng = mon.get("engine") or {}
    seen = {k: eng.get(k) for k in (
        "platform", "device_kind", "paged", "attention_path",
        "kernel_ineligible", "warmup_variants")}
    if (eng.get("platform") != "tpu" or eng.get("paged") is not True
            or eng.get("attention_path") != "ragged_paged_kernel"):
        raise SmokeFailure(f"/backend/monitor reports {seen}, expected "
                           "paged + ragged_paged_kernel on tpu")
    return seen


def phase_server_cold(scratch: str, long_prompt: str, tally: Tally,
                      budget: float) -> dict:
    t0 = time.monotonic()
    srv = Server(scratch, "cold")
    try:
        srv.wait_ready(120)
        # the first request loads the model: checkpoint read, streamed
        # int8 quantize, engine build, the whole warmup compile pass
        text = greedy_chat(srv, tally, "greedy chat (cold load)", budget)
        mon = monitor(srv)
        out = {"load_s": mon.get("load_s"),
               "load_breakdown": mon.get("load_breakdown"),
               "greedy_text": text, **_engine_block(mon)}
        # one SSE stream: content chunks, then [DONE]
        st = srv.stream("/v1/chat/completions", {
            "model": MODEL_NAME, "temperature": 0, "ignore_eos": True,
            "max_tokens": 32,
            "messages": [{"role": "user",
                          "content": "Stream a short answer."}],
        }, 300)
        problem = None
        if st["status"] != 200:
            problem = f"HTTP {st['status']}"
        elif not st["chunks"] or not st["done"]:
            problem = (f"{st['chunks']} content chunks, "
                       f"[DONE] seen: {st['done']}")
        elif st["finish_reason"] not in ("stop", "length"):
            problem = f"finish_reason {st['finish_reason']!r}"
        elif st["completion_tokens"] != 32:
            problem = f"{st['completion_tokens']} tokens, asked for 32"
        tally.record("SSE stream", problem)
        # a burst of 16 concurrent short completions: admissions land
        # while earlier rows decode (mixed prefill+decode dispatches)
        def one(i: int) -> None:
            status, body = srv.post("/v1/completions", {
                "model": MODEL_NAME, "max_tokens": 16 + i % 3,
                "temperature": 0.8 if i % 2 else 0, "seed": i,
                "ignore_eos": True,
                "prompt": f"Request {i}: " + "tell me more. " * (1 + i),
            }, 300)
            tally.record(f"burst {i}",
                         _check_completion(status, body, 16 + i % 3))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(16)]
        for i, th in enumerate(threads):
            th.start()
            if i == 7:
                time.sleep(0.3)  # second half arrives mid-decode
        for th in threads:
            th.join(timeout=320)
        if any(th.is_alive() for th in threads):
            raise SmokeFailure("burst requests still running after 320s")
        # one prompt past the 2048 bucket: chunked "prefill" dispatches
        status, body = srv.post("/v1/completions", {
            "model": MODEL_NAME, "max_tokens": 8, "temperature": 0,
            "ignore_eos": True, "prompt": long_prompt}, 300)
        problem = _check_completion(status, body, 8)
        if problem is None and not (
                2048 < body["usage"]["prompt_tokens"] < 4096):
            problem = (f"{body['usage']['prompt_tokens']} prompt tokens:"
                       " not past the 2048 bucket")
        tally.record("long prompt", problem)
        # the same long prompt again: its prefix pages are resident, so
        # the prefix cache serves them instead of a second prefill
        before = (monitor(srv).get("engine") or {})["prefix_cache"]
        status2, body2 = srv.post("/v1/completions", {
            "model": MODEL_NAME, "max_tokens": 8, "temperature": 0,
            "ignore_eos": True, "prompt": long_prompt}, 300)
        problem = _check_completion(status2, body2, 8)
        after = (monitor(srv).get("engine") or {})["prefix_cache"]
        reused = after["reused_tokens"] - before["reused_tokens"]
        if problem is None and reused < SERVING["context_size"] // 16:
            problem = (f"repeat of a {body2['usage']['prompt_tokens']}"
                       f"-token prompt reused only {reused} tokens")
        tally.record("long prompt (repeat)", problem)
        out["repeat_reused_tokens"] = reused
        if problem is None and status == 200:
            out["repeat_text_equal"] = (
                body2["choices"][0]["text"] == body["choices"][0]["text"])
        errs = error_requests(srv.get("/metrics").decode())
        if errs:
            raise SmokeFailure(
                f'engine_requests_total{{reason="error"}} = {errs}\n'
                f"{srv.log_tail()}")
        mon = monitor(srv)
        eng = mon.get("engine") or {}
        out["peak_hbm_bytes"] = (eng.get("hbm") or {}).get(
            "peak_bytes_in_use")
        out["prefix_cache"] = eng.get("prefix_cache")
        if tally.failures:
            raise SmokeFailure("; ".join(tally.failures[:4])
                               + "\n" + srv.log_tail())
        out["exit_code"] = srv.stop()
        if out["exit_code"] != 0:
            raise SmokeFailure(
                f"server exit code {out['exit_code']} after SIGTERM: "
                f"{srv.log_tail()}")
        out["s"] = round(time.monotonic() - t0, 1)
        return out
    finally:
        CHILDREN.stop_all()


def phase_server_warm(scratch: str, cold_text: str, tally: Tally,
                      budget: float) -> dict:
    t0 = time.monotonic()
    srv = Server(scratch, "warm")
    try:
        srv.wait_ready(120)
        text = greedy_chat(srv, tally, "greedy chat (warm load)", budget)
        mon = monitor(srv)
        bd = mon.get("load_breakdown") or {}
        out = {"load_s": mon.get("load_s"), "load_breakdown": bd,
               "warmup_reused": bd.get("warmup_reused"),
               "greedy_equal_cold": text == cold_text,
               **_engine_block(mon)}
        if bd.get("warmup_reused") is not True:
            raise SmokeFailure(
                "second start did not reuse the warmup: "
                f"load_breakdown {bd}")
        if text != cold_text:
            raise SmokeFailure(
                "warm-cache greedy completion differs from the cold "
                f"one: {text!r} vs {cold_text!r}")
        out["exit_code"] = srv.stop()
        if out["exit_code"] != 0:
            raise SmokeFailure(
                f"server exit code {out['exit_code']} after SIGTERM")
        out["s"] = round(time.monotonic() - t0, 1)
        return out
    finally:
        CHILDREN.stop_all()


# ------------------------------------------------------------------- main


def verdict_line(ok: bool, device: dict) -> str:
    """The last line of stdout: exactly ``ok`` and ``device`` with
    exactly ``platform``/``kind``/``count`` — whoever runs this script
    reads that line and no other."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main() -> int:
    t_start = time.monotonic()

    def left() -> float:
        return max(1.0, DEADLINE_S - (time.monotonic() - t_start))

    if not os.path.isdir(os.path.join(ROOT, "localai_tfp_tpu")):
        print("chip_smoke.py: no localai_tfp_tpu/ beside this script — "
              "run it from the root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    try:
        device = phase_device()
    except SmokeFailure as e:
        # no accelerator: no summary, nothing written
        print(f"chip_smoke.py: {e}", file=sys.stderr)
        return 2
    device_s = round(time.monotonic() - t_start, 1)

    from localai_tfp_tpu.utils import compile_cache

    cache_dir, cache_from_env = compile_cache.resolve()
    n_layers = int(os.environ.get("CHIP_SMOKE_LAYERS", DEFAULT_LAYERS))
    summary: dict = {
        "ok": False,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
        "versions": {k: device[k]
                     for k in ("jax", "jaxlib", "libtpu", "python")},
        "model": {
            "name": "Mistral-7B-Instruct-v0.3",
            **{k: HF_CONFIG[k] for k in (
                "hidden_size", "intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "vocab_size", "rope_theta", "rms_norm_eps",
                "sliding_window", "max_position_embeddings")},
            "n_layers": n_layers, "published_layers": PUBLISHED_LAYERS,
            "weights": "seeded random (seed 0)", **SERVING},
        "compile_cache": {"dir": cache_dir, "from_env": cache_from_env},
        "phases": {"device": {"ok": True, "s": device_s}},
        "note": "seconds and bytes are observations of one run, not "
                "benchmark metrics",
    }
    phases = summary["phases"]
    tally = Tally()
    scratch = tempfile.mkdtemp(prefix="chip_smoke-")
    current = "kernel"
    try:
        t0 = time.monotonic()
        res = phase_kernel(min(400.0, left()))
        phases["kernel"] = {
            "ok": True, "s": round(time.monotonic() - t0, 1),
            "max_err": {k: v for k, v in res.items()
                        if k.endswith("_err")}}

        current = "checkpoint"
        t0 = time.monotonic()
        os.makedirs(os.path.join(scratch, "models"))
        model = write_model(os.path.join(scratch, "models"), n_layers)
        phases["checkpoint"] = {
            "ok": True, "s": round(time.monotonic() - t0, 1),
            "bytes": model["bytes"],
            "long_prompt_tokens": model["long_prompt_tokens"]}

        current = "server_cold"
        cold = phase_server_cold(scratch, model["long_prompt"], tally,
                                 left())
        cold_text = cold.pop("greedy_text")
        phases["server_cold"] = {"ok": True, **cold}

        current = "server_warm"
        phases["server_warm"] = {
            "ok": True,
            **phase_server_warm(scratch, cold_text, tally, left())}
        summary["ok"] = True
    except SmokeFailure as e:
        phases.setdefault(current, {})
        phases[current].update(ok=False, error=str(e)[-4000:])
        print(f"chip_smoke.py: phase {current} failed: {e}",
              file=sys.stderr)
    finally:
        CHILDREN.stop_all()
        shutil.rmtree(scratch, ignore_errors=True)
    summary["requests"] = tally.as_dict()
    summary["request_failures"] = tally.failures[:8]
    summary["kernel_errors"] = (
        [] if phases.get("kernel", {}).get("ok")
        else [phases.get("kernel", {}).get("error", "not run")])
    summary["peak_hbm_bytes"] = phases.get("server_cold", {}).get(
        "peak_hbm_bytes")
    summary["wall_s"] = round(time.monotonic() - t_start, 1)
    summary["claim"] = None
    print(json.dumps(summary))
    print(verdict_line(summary["ok"], device), flush=True)
    return 0 if summary["ok"] else 1


def _on_signal(signum, frame):  # stop the children, then die as asked
    CHILDREN.stop_all()
    raise SystemExit(128 + signum)


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    sys.exit(main())
