"""TTFT decomposition probe for the 8B serving config (round-4 perf work).

Reconstructs bench.py's 8B leg, then instruments:
  1. engine wave: every _run dispatch (kind, wall ms) during a 64-deep burst
  2. HTTP wave: per-request phase timestamps (handler entry -> body -> load
     -> template -> submit -> first token -> first write)

Prints a JSON report. Not part of the test suite; run manually on the chip:
    python tools/profile_ttft.py [--small]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


class WideByteTok:
    """bench.py's WideByteTok (defined inside its main; re-declared here)."""

    def __new__(cls):
        from localai_tfp_tpu.engine.tokenizer import ByteTokenizer

        class _T(ByteTokenizer):
            def decode(self, ids):
                return "".join(
                    chr(32 + (i % 95)) for i in ids
                    if i not in (self.bos_id, *self.eos_ids)
                )

        return _T()


def build_engine(small: bool):
    from bench import _fast_int8_params  # type: ignore

    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.models.llm_spec import LLMSpec, tiny_spec
    from localai_tfp_tpu.models.transformer import init_params

    tok = WideByteTok()
    if small:
        spec = tiny_spec(vocab_size=258)
        params = init_params(jax.random.PRNGKey(0), spec)
        eng = LLMEngine(spec, params, tok, n_slots=4, max_seq=256,
                        decode_steps=8, cache_dtype=jnp.bfloat16,
                        autostart=False)
        n_req, n_tok = 4, 32
    else:
        spec = LLMSpec(
            vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_head=128, d_ff=14336, max_position=4096,
            rope_theta=500000.0,
        )
        params = _fast_int8_params(spec)
        eng = LLMEngine(spec, params, tok, n_slots=64, max_seq=1024,
                        decode_steps=16, cache_dtype="int8",
                        autostart=False)
        n_req, n_tok = 64, 256
    eng.start()
    eng.warmup()
    return eng, tok, n_req, n_tok


def wave(eng, tok, n_req, n_tok):
    from bench import _run_wave  # type: ignore

    return _run_wave(eng, tok, n_req, n_tok, "benchmark " * 12)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--no-http", action="store_true")
    args = ap.parse_args()

    import sys

    sys.path.insert(0, "/root/repo")
    from localai_tfp_tpu.utils import compile_cache

    compile_cache.configure()

    eng, tok, n_req, n_tok = build_engine(args.small)

    # -------- warmups (compile everything) --------
    for _ in range(2):
        _, _, _, errs = wave(eng, tok, n_req, n_tok)
        if errs:
            raise RuntimeError(errs[0])

    # -------- instrument _run --------
    log = []
    orig_run = eng._run

    def traced_run(kind, payload):
        t0 = time.perf_counter()
        out = orig_run(kind, payload)
        shape = (list(payload["toks"].shape)
                 if kind.startswith("prefill") else payload.get("k"))
        log.append((kind, round((time.perf_counter() - t0) * 1e3, 2),
                    round(t0, 4), shape))
        return out

    rems = []
    orig_assign = eng._assign

    def traced_assign(slot, req, out):
        pre = len(slot.cache_tokens)
        orig_assign(slot, req, out)
        rems.append((slot.idx, pre, slot.n_past,
                     slot.n_prompt - slot.n_past))

    eng._assign = traced_assign
    eng._run = traced_run
    t_wave = time.perf_counter()
    total, wall, ttfts, errs = wave(eng, tok, n_req, n_tok)
    eng._run = orig_run
    eng._assign = orig_assign
    print("ASSIGN (slot, cache_len, n_past, rem):", rems[:10], flush=True)
    if errs:
        print("ENGINE WAVE ERRORS:", errs[:2], flush=True)
    report = {
        "engine_wave": {
            "tok_s": round(total / wall, 1),
            "ttft_p50_ms": round(ttfts[len(ttfts) // 2], 1),
            "ttft_min_ms": round(ttfts[0], 1),
            "ttft_max_ms": round(ttfts[-1], 1),
            "dispatches": [
                {"kind": k, "ms": ms, "at_ms": round((at - t_wave) * 1e3, 1),
                 "shape": sh}
                for k, ms, at, sh in log[:40]
            ],
            "n_dispatches": len(log),
        },
    }
    print(json.dumps(report, indent=1), flush=True)  # engine leg first —
    # the HTTP leg must not be able to lose it
    if args.no_http:
        eng.close()
        return

    # -------- HTTP leg with phase timestamps --------
    import asyncio
    import os
    import tempfile

    from aiohttp import ClientSession, ClientTimeout, TCPConnector, web

    from localai_tfp_tpu.config.app_config import ApplicationConfig
    from localai_tfp_tpu.engine.loader import LoadedModel
    from localai_tfp_tpu.server import openai_routes
    from localai_tfp_tpu.server.app import build_app
    from localai_tfp_tpu.server.state import Application
    from localai_tfp_tpu.workers.llm import JaxLLMBackend

    tmp = tempfile.mkdtemp(prefix="prof-srv-")
    models = os.path.join(tmp, "models")
    os.makedirs(models)
    with open(os.path.join(models, "bench.yaml"), "w") as f:
        f.write(
            "name: bench\nbackend: jax-llm\n"
            "parameters:\n  model: bench\n"
            "template:\n"
            '  chat_message: "{{.RoleName}}: {{.Content}}"\n'
            '  chat: "{{.Input}}\\nassistant:"\n'
        )
    state = Application(ApplicationConfig(
        models_path=models,
        generated_content_dir=os.path.join(tmp, "generated"),
        upload_dir=os.path.join(tmp, "uploads"),
        config_dir=os.path.join(tmp, "configuration"),
    ))
    backend = JaxLLMBackend()
    backend.engine, backend.tokenizer = eng, tok
    backend.spec, backend._state = eng.spec, "READY"
    state.model_loader._models["bench"] = LoadedModel(
        "bench", "jax-llm", backend)
    app = build_app(state)

    # trace engine dispatches during the HTTP waves too
    http_log: list = []
    orig2 = eng._run

    def traced2(kind, payload):
        t0 = time.perf_counter()
        shape = None
        if kind in ("prefill", "mixed"):
            shape = list(payload["toks"].shape)
        out = orig2(kind, payload)
        http_log.append((kind, shape,
                         round((time.perf_counter() - t0) * 1e3, 1), t0))
        return out

    eng._run = traced2

    async def drive():
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}/v1/chat/completions"
        async with ClientSession(
            connector=TCPConnector(limit=0),
            timeout=ClientTimeout(total=3600),
        ) as sess:

            async def one(i, t0, ttfts, first_byte):
                body = {
                    "model": "bench",
                    "messages": [{"role": "user",
                                  "content": "benchmark " * 10 + str(i)}],
                    "max_tokens": n_tok, "stream": True,
                    "temperature": 0.8, "top_k": 40, "top_p": 0.95,
                    "ignore_eos": True,
                }
                total = 0
                t_req = time.perf_counter()
                async with sess.post(url, json=body,
                                     headers={"Extra-Usage": "1"}) as r:
                    assert r.status == 200, await r.text()
                    async for line in r.content:
                        if first_byte[i] is None:
                            first_byte[i] = (time.perf_counter() - t0) * 1e3
                        if not line.startswith(b"data: "):
                            continue
                        if line.strip() == b"data: [DONE]":
                            break
                        d = json.loads(line[6:])
                        ch = d["choices"][0]
                        if (ch["delta"].get("content")
                                and ttfts[i] is None):
                            ttfts[i] = (time.perf_counter() - t0) * 1e3
                        if ch.get("finish_reason"):
                            if ch["finish_reason"] == "error" and i == 0:
                                print("HTTP STREAM ERROR:", d, flush=True)
                            u = d.get("usage") or {}
                            total = u.get("completion_tokens", 0)
                return total, (time.perf_counter() - t_req) * 1e3

            results = {}
            for run in range(3):  # 2 warmup + 1 measured
                ttfts = [None] * n_req
                first_byte = [None] * n_req
                t0 = time.perf_counter()
                totals = await asyncio.gather(
                    *[one(i, t0, ttfts, first_byte) for i in range(n_req)])
                wall = time.perf_counter() - t0
                if run < 2:
                    continue
                tt = sorted(t for t in ttfts if t is not None) or [0.0]
                fb = sorted(t for t in first_byte if t is not None) or [0.0]
                results = {
                    "tok_s": round(sum(t for t, _ in totals) / wall, 1),
                    "ttft_p50_ms": round(tt[len(tt) // 2], 1),
                    "ttft_min_ms": round(tt[0], 1),
                    "ttft_max_ms": round(tt[-1], 1),
                    "first_byte_p50_ms": round(fb[len(fb) // 2], 1),
                    "n_with_content": len([t for t in ttfts
                                           if t is not None]),
                }
            return results

    loop = asyncio.new_event_loop()
    try:
        t_http0 = time.perf_counter()
        report["http_wave"] = loop.run_until_complete(drive())
    finally:
        loop.close()

    eng.close()
    # last ~120 dispatches of the HTTP leg with timestamps
    report["http_dispatches"] = [
        {"kind": k, "shape": s, "ms": ms,
         "at_s": round(at - t_http0, 2)}
        for k, s, ms, at in http_log[-120:]
    ]
    report["http_n_dispatches"] = len(http_log)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
