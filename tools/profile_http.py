"""HTTP 64-burst TTFT phase timeline for the 8B serving config.

BENCH r5 gap: engine-side burst p50 ~237 ms, HTTP-side ~818 ms. This
stamps every stage each request passes through, aggregated across the
wave (all times ms relative to the wave's t0):

  recv    — handler reached (_body awaited): aiohttp accept+parse+route
  built   — PredictOptions ready in the producer thread (template
            render + tokenize done)
  submit  — engine.submit returned (admission queue)
  prefill — the engine dispatched the wave's mixed step(s)
  harvest — first tokens harvested (bridge put)
  write   — client saw the first CONTENT SSE event (TTFT)

Run manually on the chip:  python tools/profile_http.py

Shared-system-prompt burst scenario (cross-slot prefix cache):

  python tools/profile_http.py --shared-prefix [--small] \
      [--requests N] [--prefix-tokens P]

drives two bursts through the stock endpoint — N requests sharing a
P-token prefix, and N fully distinct requests — each with the prefix
cache ON and OFF, reporting client TTFT, prefill tokens actually
dispatched (counted at the dispatch layer), kvcopy count, and the
telemetry counters cross-checked against the dispatch-level ground
truth. ``--small`` runs the tiny CPU config (smoke).

Mixed-dispatch scenario (stall-free prefill+decode fusion):

  python tools/profile_http.py --mixed [--small] \
      [--streams N] [--bursts K] [--burst-size B]

drives N sustained decode streams and injects K admission bursts of B
requests mid-stream, with the fused mixed dispatcher ON and OFF
(the mixed step) — the headline numbers for the scheduler's
prefill/decode de-serialization: per-stream ITL p50/p95, the **max
inter-token gap** any live stream saw while a burst was admitting
(the legacy hold loops spike it to the prefill-group round trip), and
burst TTFT p50 (must hold — the fused path keeps wave coalescing at
dispatch granularity).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


from tools.profile_r5 import pct as _pct  # noqa: E402  (shared helper)


def pct(xs, q):
    return round(_pct(xs, q), 1) if xs else None


def _mk_state(eng, tok):
    """Minimal Application with the in-memory engine registered as
    model "bench" (the scenario measures serving, not the loader)."""
    from localai_tfp_tpu.config.app_config import ApplicationConfig
    from localai_tfp_tpu.engine.loader import LoadedModel
    from localai_tfp_tpu.server.state import Application
    from localai_tfp_tpu.workers.llm import JaxLLMBackend

    tmp = tempfile.mkdtemp(prefix="prof-http-")
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
    return state


class _DispatchSpy:
    """Count REAL prefill tokens (pad rows excluded) and kvcopy
    dispatches at the engine._run layer — ground truth for the
    telemetry cross-check."""

    def __init__(self, eng):
        self.eng = eng
        self.prefill_tokens = 0
        self.copies = 0
        self._orig = eng._run
        eng._run = self._run

    def reset(self):
        self.prefill_tokens = 0
        self.copies = 0

    def _run(self, kind, payload):
        if kind == "mixed":
            self.prefill_tokens += int(sum(
                int(c) for sid, c in zip(payload["slot_ids"],
                                         payload["n_chunk"])
                if int(sid) < self.eng.n_slots))
        elif kind == "kvcopy":
            self.copies += 1
        return self._orig(kind, payload)


def shared_prefix_scenario(small: bool, n_req: int,
                           prefix_tokens: int) -> None:
    from aiohttp import ClientSession, ClientTimeout, TCPConnector, web

    from localai_tfp_tpu.engine.prefix_index import PrefixIndex
    from localai_tfp_tpu.server.app import build_app
    from localai_tfp_tpu.telemetry.registry import REGISTRY

    from tools.profile_ttft import build_engine

    eng, tok, _, _ = build_engine(small)
    if small:
        n_req = min(n_req, eng.n_slots)
        prefix_tokens = min(prefix_tokens, eng.max_seq // 2)
    n_tok = 16 if small else 64
    app = build_app(_mk_state(eng, tok))
    spy = _DispatchSpy(eng)
    # byte-level bench tokenizers: 1 char ~ 1 token
    shared = "S" * prefix_tokens
    scenarios = {
        "shared": [shared + f" req {i:03d}" for i in range(n_req)],
        "distinct": [f"{i:03d} " + os.urandom(8).hex() + " distinct"
                     for i in range(n_req)],
    }

    def reset_engine():
        # drop all resident prefixes so each mode starts cold
        for s in eng.slots:
            s.cache_tokens = []
            s.n_past = 0
        eng._prefix_index = PrefixIndex()
        eng._deferred.clear()
        spy.reset()

    async def drive():
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}/v1/chat/completions"
        out: dict = {}
        async with ClientSession(
            connector=TCPConnector(limit=0),
            timeout=ClientTimeout(total=3600),
        ) as sess:

            async def one(content, ttfts, i, t0):
                body = {
                    "model": "bench",
                    "messages": [{"role": "user", "content": content}],
                    "max_tokens": n_tok, "stream": True,
                    "temperature": 0.0, "ignore_eos": True,
                }
                async with sess.post(url, json=body) as r:
                    assert r.status == 200, await r.text()
                    async for line in r.content:
                        if not line.startswith(b"data: "):
                            continue
                        if line.strip() == b"data: [DONE]":
                            break
                        d = json.loads(line[6:])
                        ch = d["choices"][0]
                        if (ch["delta"].get("content")
                                and ttfts[i] is None):
                            ttfts[i] = time.perf_counter() - t0
                        if ch.get("finish_reason"):
                            break

            async def wave(contents):
                ttfts = [None] * len(contents)
                t0 = time.perf_counter()
                await asyncio.gather(
                    *[one(c, ttfts, i, t0)
                      for i, c in enumerate(contents)])
                return [x * 1e3 for x in ttfts if x is not None]

            # untimed warm waves in BOTH modes: each mode takes
            # different dispatch shapes (full prefill vs copy + tail)
            # and a first-wave compile would be charged to whichever
            # mode ran first
            for warm_mode in ("off", "on"):
                eng._prefix_enabled = (warm_mode == "on")
                reset_engine()
                await wave(scenarios["shared"])
            for name, contents in scenarios.items():
                out[name] = {}
                for mode in ("off", "on"):
                    eng._prefix_enabled = (mode == "on")
                    reset_engine()
                    snap = REGISTRY.snapshot()
                    ttfts = await wave(contents)
                    delta = REGISTRY.delta(snap)
                    reused = sum(
                        v for k, v in delta.items()
                        if k.startswith("engine_prefix_reused_tokens"))
                    prefilled = sum(
                        v for k, v in delta.items()
                        if k.startswith("engine_prompt_tokens_total"))
                    out[name][mode] = {
                        "ttft_p50_ms": pct(ttfts, .5),
                        "ttft_p95_ms": pct(ttfts, .95),
                        "prefill_tokens_dispatched": spy.prefill_tokens,
                        "kv_copies": spy.copies,
                        "telemetry_reused_tokens": int(reused),
                        "telemetry_prefilled_tokens": int(prefilled),
                        "telemetry_matches_dispatch":
                            int(prefilled) == spy.prefill_tokens,
                    }
        s = out["shared"]
        s["prefill_tokens_saved"] = (
            s["off"]["prefill_tokens_dispatched"]
            - s["on"]["prefill_tokens_dispatched"])
        return out

    loop = asyncio.new_event_loop()
    try:
        report = loop.run_until_complete(drive())
    finally:
        loop.close()
    print(json.dumps(report, indent=1), flush=True)
    eng.close()


def mixed_scenario(small: bool, n_streams: int, n_bursts: int,
                   burst_size: int) -> None:
    """Sustained decode streams + admission bursts injected mid-stream
    through the mixed step. Reports per-stream inter-token gaps
    (client-observed SSE event spacing) and burst TTFT."""
    from aiohttp import ClientSession, ClientTimeout, TCPConnector, web

    from localai_tfp_tpu.server.app import build_app
    from localai_tfp_tpu.telemetry.registry import REGISTRY

    from tools.profile_ttft import build_engine

    eng, tok, _, _ = build_engine(small)
    if small:
        n_streams = min(n_streams, max(1, eng.n_slots // 2))
        burst_size = max(1, min(burst_size, eng.n_slots - n_streams))
    stream_tokens = 150 if small else 192
    burst_prompt_chars = 110 if small else 600
    burst_gap_s = 0.25 if small else 0.5
    app = build_app(_mk_state(eng, tok))
    eng._prefix_enabled = False  # isolate scheduling from prefix reuse

    async def drive():
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}/v1/chat/completions"
        out: dict = {}
        async with ClientSession(
            connector=TCPConnector(limit=0),
            timeout=ClientTimeout(total=3600),
        ) as sess:

            async def sse_events(body, on_content):
                async with sess.post(url, json=body) as r:
                    assert r.status == 200, await r.text()
                    async for line in r.content:
                        if not line.startswith(b"data: "):
                            continue
                        if line.strip() == b"data: [DONE]":
                            break
                        d = json.loads(line[6:])
                        ch = d["choices"][0]
                        if ch["delta"].get("content"):
                            on_content()
                        if ch.get("finish_reason"):
                            break

            async def stream_one(i, tag, times, started):
                body = {
                    "model": "bench",
                    "messages": [{"role": "user",
                                  "content": f"sustained stream {tag} "
                                             f"{i:02d}"}],
                    "max_tokens": stream_tokens, "stream": True,
                    "temperature": 0.0, "ignore_eos": True,
                }

                def on_content():
                    times[i].append(time.perf_counter())
                    started[i].set()

                await sse_events(body, on_content)

            async def burst_one(tag, j, ttfts, t0):
                body = {
                    "model": "bench",
                    "messages": [{"role": "user",
                                  "content": "B" * burst_prompt_chars
                                             + f" {tag} {j:02d}"}],
                    "max_tokens": 8, "stream": True,
                    "temperature": 0.0, "ignore_eos": True,
                }
                got = []

                def on_content():
                    if not got:
                        got.append(time.perf_counter() - t0)
                        ttfts.append(got[0] * 1e3)

                await sse_events(body, on_content)

            async def run_once(tag):
                times = [[] for _ in range(n_streams)]
                started = [asyncio.Event() for _ in range(n_streams)]
                burst_ttfts: list[float] = []
                streams = [asyncio.ensure_future(
                    stream_one(i, tag, times, started))
                    for i in range(n_streams)]
                await asyncio.gather(*[e.wait() for e in started])
                burst_tasks = []
                for k in range(n_bursts):
                    t0 = time.perf_counter()
                    burst_tasks += [asyncio.ensure_future(
                        burst_one(f"{tag}-{k}", j, burst_ttfts, t0))
                        for j in range(burst_size)]
                    await asyncio.sleep(burst_gap_s)
                await asyncio.gather(*streams, *burst_tasks)
                return times, burst_ttfts

            for mode in ("on",):  # one admission path (PR 37)
                await run_once(f"warm-{mode}")  # untimed: compiles
                snap = REGISTRY.snapshot()
                times, burst_ttfts = await run_once(f"run-{mode}")
                delta = REGISTRY.delta(snap)
                gaps, max_gaps = [], []
                for ts in times:
                    g = [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
                    if g:
                        gaps += g
                        max_gaps.append(max(g))
                out[mode] = {
                    "itl_p50_ms": pct(gaps, .5),
                    "itl_p95_ms": pct(gaps, .95),
                    "max_gap_p50_ms": pct(max_gaps, .5),
                    "max_gap_max_ms": pct(max_gaps, 1.0),
                    "burst_ttft_p50_ms": pct(burst_ttfts, .5),
                    "burst_ttft_p95_ms": pct(burst_ttfts, .95),
                    "mixed_dispatches": int(sum(
                        v for k, v in delta.items()
                        if k.startswith("engine_mixed_dispatch_total")
                        and 'composition="mixed"' in k)),
                }
        out["summary"] = {
            "streams": n_streams, "bursts": n_bursts,
            "burst_size": burst_size,
        }
        return out

    loop = asyncio.new_event_loop()
    try:
        report = loop.run_until_complete(drive())
    finally:
        loop.close()
    # ragged paged attention: jit-cache variant counts + warmup wall
    # time, on vs off (the compile-variant collapse riding the same
    # mixed-traffic scheduler this scenario stresses)
    from bench import ragged_variant_report

    report["ragged_attn"] = ragged_variant_report()
    print(json.dumps(report, indent=1), flush=True)
    eng.close()


def main() -> None:
    from tools.profile_ttft import build_engine

    from aiohttp import ClientSession, ClientTimeout, TCPConnector, web

    from localai_tfp_tpu.server import openai_routes
    from localai_tfp_tpu.server.app import build_app

    eng, tok, n_req, n_tok = build_engine(False)
    eng.latency_target_ms = 70.0  # bench8b.yaml parity

    state = _mk_state(eng, tok)
    backend = state.model_loader._models["bench"].backend
    app = build_app(state)

    # ---- stage stamps ----
    stamps: dict[str, list[float]] = {
        k: [] for k in ("recv", "built", "submit", "prefill", "harvest")}
    t0_box = [0.0]

    orig_body = openai_routes._body

    async def stamped_body(request):
        stamps["recv"].append(time.perf_counter() - t0_box[0])
        return await orig_body(request)

    openai_routes._body = stamped_body

    orig_to_request = backend._to_request

    def stamped_to_request(opts):
        r = orig_to_request(opts)
        stamps["built"].append(time.perf_counter() - t0_box[0])
        return r

    backend._to_request = stamped_to_request

    orig_submit = eng.submit

    def stamped_submit(req):
        q = orig_submit(req)
        stamps["submit"].append(time.perf_counter() - t0_box[0])
        return q

    eng.submit = stamped_submit

    orig_run = eng._run

    def stamped_run(kind, payload):
        if kind == "mixed":
            stamps["prefill"].append(time.perf_counter() - t0_box[0])
        return orig_run(kind, payload)

    eng._run = stamped_run

    orig_complete = eng._complete_mixed

    def stamped_complete(fl):
        stamps["harvest"].append(time.perf_counter() - t0_box[0])
        return orig_complete(fl)

    eng._complete_mixed = stamped_complete

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

            async def one(i, ttfts, first_byte, sent):
                body = {
                    "model": "bench",
                    "messages": [{"role": "user",
                                  "content": "benchmark " * 2 + str(i)}],
                    "max_tokens": n_tok, "stream": True,
                    "temperature": 0.8, "top_k": 40, "top_p": 0.95,
                    "ignore_eos": True,
                }
                sent[i] = time.perf_counter() - t0_box[0]
                async with sess.post(url, json=body) as r:
                    assert r.status == 200, await r.text()
                    async for line in r.content:
                        now = time.perf_counter() - t0_box[0]
                        if first_byte[i] is None:
                            first_byte[i] = now
                        if not line.startswith(b"data: "):
                            continue
                        if line.strip() == b"data: [DONE]":
                            break
                        d = json.loads(line[6:])
                        ch = d["choices"][0]
                        if (ch["delta"].get("content")
                                and ttfts[i] is None):
                            ttfts[i] = now
                        if ch.get("finish_reason"):
                            break

            out = {}
            for run in range(4):  # 3 warmup (compile + settle), 1 measured
                for v in stamps.values():
                    v.clear()
                ttfts = [None] * 64
                first_byte = [None] * 64
                sent = [None] * 64
                t0_box[0] = time.perf_counter()
                await asyncio.gather(
                    *[one(i, ttfts, first_byte, sent) for i in range(64)])
                if run < 3:
                    continue
                s = {k: [x * 1e3 for x in v] for k, v in stamps.items()}
                out = {
                    "sent": {"p50": pct([x * 1e3 for x in sent], .5),
                             "max": pct([x * 1e3 for x in sent], 1.0)},
                    **{k: {"min": pct(v, 0.0), "p50": pct(v, .5),
                           "max": pct(v, 1.0), "n": len(v)}
                       for k, v in s.items()},
                    "ttft": {"min": pct([x * 1e3 for x in ttfts if x], 0.0),
                             "p50": pct([x * 1e3 for x in ttfts if x], .5),
                             "p95": pct([x * 1e3 for x in ttfts if x], .95)},
                    "first_byte_p50": pct(
                        [x * 1e3 for x in first_byte if x], .5),
                }
            return out

    loop = asyncio.new_event_loop()
    try:
        report = loop.run_until_complete(drive())
    finally:
        loop.close()
    print(json.dumps(report, indent=1), flush=True)
    eng.close()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--shared-prefix", action="store_true",
                    help="shared-system-prompt burst scenario "
                         "(prefix cache on vs off)")
    ap.add_argument("--mixed", action="store_true",
                    help="sustained decode + admission bursts, fused "
                         "mixed dispatch on vs off")
    ap.add_argument("--small", action="store_true",
                    help="tiny CPU config (smoke) instead of 8B")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--prefix-tokens", type=int, default=512)
    ap.add_argument("--streams", type=int, default=48,
                    help="--mixed: sustained decode streams")
    ap.add_argument("--bursts", type=int, default=3,
                    help="--mixed: admission bursts injected mid-stream")
    ap.add_argument("--burst-size", type=int, default=16,
                    help="--mixed: requests per burst")
    args = ap.parse_args()
    from localai_tfp_tpu.utils import compile_cache

    compile_cache.configure()
    if args.shared_prefix:
        shared_prefix_scenario(args.small, args.requests,
                               args.prefix_tokens)
    elif args.mixed:
        mixed_scenario(args.small, args.streams, args.bursts,
                       args.burst_size)
    else:
        main()
