"""Round-5 serving-path profiler: where do HTTP tokens/sec and burst
TTFT go between the engine and the client?

Runs the SAME 8B-geometry engine bench.py uses, once engine-side and
once through the real aiohttp endpoint, with two instruments:

1. A per-request stage timeline (monkeypatched engine hooks): submit ->
   slot assign -> prefill dispatch -> prefill harvest, plus the
   client-observed first-content time, all on one perf_counter clock.
   Reported as percentiles relative to the wave t0.
2. An in-process sampling profiler (sys._current_frames every ~4 ms)
   aggregated per thread-group and top frames, so the one-core host's
   GIL budget is visible: who is burning the core while the wave runs.

Usage: python tools/profile_r5.py [--tokens N] [--slots N]
"""

from __future__ import annotations

import argparse
import collections
import sys
import threading
import time


class Sampler:
    def __init__(self, interval=0.004):
        self.interval = interval
        self.counts: collections.Counter = collections.Counter()
        self.thread_counts: collections.Counter = collections.Counter()
        self._stop = threading.Event()
        self._thread = None
        self._names = {}

    def start(self):
        self._names = {t.ident: t.name for t in threading.enumerate()}
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="profiler-sampler")
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join()

    def _run(self):
        me = threading.get_ident()
        while not self._stop.is_set():
            for t in threading.enumerate():
                self._names.setdefault(t.ident, t.name)
            for ident, frame in sys._current_frames().items():
                if ident == me:
                    continue
                name = self._names.get(ident, str(ident))
                # group thread families
                for pfx in ("srv-blocking", "stream-bridge", "engine",
                            "MainThread", "asyncio"):
                    if name.startswith(pfx):
                        name = pfx
                        break
                # skip idle frames (waits/sleeps don't burn the core)
                top = frame
                code = top.f_code
                key = f"{code.co_filename.rsplit('/', 1)[-1]}:{code.co_name}"
                idle = any(s in key for s in (
                    "wait", "sleep", "select:", "get:", "_run:_run"))
                stack = []
                f = frame
                for _ in range(4):
                    if f is None:
                        break
                    c = f.f_code
                    stack.append(
                        f"{c.co_filename.rsplit('/', 1)[-1]}:"
                        f"{c.co_name}:{f.f_lineno}")
                    f = f.f_back
                sig = " < ".join(stack)
                self.thread_counts[(name, "idle" if idle else "busy")] += 1
                if not idle:
                    self.counts[(name, sig)] += 1
            time.sleep(self.interval)

    def report(self, top_n=25):
        print("\n=== sampler: thread budget (samples) ===")
        for (name, st), c in sorted(self.thread_counts.items(),
                                    key=lambda kv: -kv[1]):
            print(f"  {name:24s} {st:5s} {c}")
        print(f"\n=== sampler: top busy stacks ===")
        for (name, sig), c in self.counts.most_common(top_n):
            print(f"  {c:6d} [{name}] {sig}")


TL = collections.defaultdict(dict)  # req id -> stage -> t
TL_LOCK = threading.Lock()
FLIGHTS = []  # (kind, detail, t_enqueue, t_harvest)


def instrument_engine():
    from localai_tfp_tpu.engine import engine as em

    orig_submit_many = em.LLMEngine.submit_many
    orig_assign = em.LLMEngine._assign
    orig_enq = em.LLMEngine._enqueue_mixed
    orig_cpf = em.LLMEngine._complete_mixed
    orig_harvest = em.LLMEngine._harvest

    def _harvest(self):
        did = False
        while self._flights and self._flights[0].ready():
            fl = self._flights[0]
            detail = (f"k={fl.meta.get('k')}" if fl.kind == "decodek"
                      else f"n={len(fl.meta.get('prompt', []))}")
            FLIGHTS.append((fl.kind, detail, fl.t_enqueue,
                            time.perf_counter()))
            # delegate one completion at a time so we time each pop
            fl2 = self._flights.popleft()
            if fl2.kind == "mixed":
                self._complete_mixed(fl2)
            else:
                self._complete_decodek(fl2)
            did = True
        return did

    em.LLMEngine._harvest = _harvest

    def submit_many(self, reqs):
        t = time.perf_counter()
        with TL_LOCK:
            for r in reqs:
                TL[r.id]["submit"] = t
        return orig_submit_many(self, reqs)

    def _assign(self, slot, req, out):
        TL[req.id]["assign"] = time.perf_counter()
        return orig_assign(self, slot, req, out)

    def _enqueue_mixed(self, prefilling):
        t = time.perf_counter()
        for s in prefilling:
            if s.request is not None:
                TL[s.request.id].setdefault("pf_dispatch", t)
        return orig_enq(self, prefilling)

    def _complete_mixed(self, fl):
        t = time.perf_counter()
        for s, req, is_final in fl.meta["prompt"]:
            if is_final:
                TL[req.id]["pf_harvest"] = t
        return orig_cpf(self, fl)

    em.LLMEngine.submit_many = submit_many
    em.LLMEngine._assign = _assign
    em.LLMEngine._enqueue_mixed = _enqueue_mixed
    em.LLMEngine._complete_mixed = _complete_mixed


def pct(xs, p):
    if not xs:
        return float("nan")
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(len(xs) * p))]


def report_flights(t0, label=""):
    print(f"\n=== flights ({label}): enqueue->harvest, ms after t0 ===")
    rows = [f for f in FLIGHTS if f[3] >= t0]
    for kind, detail, te, th in rows[-48:]:
        print(f"  {kind:14s} {detail:8s} enq={((te - t0) * 1e3):8.1f} "
              f"harv={((th - t0) * 1e3):8.1f} "
              f"dt={((th - te) * 1e3):7.1f}")


def report_timeline(t0, client_first=None, label=""):
    stages = ["submit", "assign", "pf_dispatch", "pf_harvest"]
    with TL_LOCK:
        rows = {k: dict(v) for k, v in TL.items() if "submit" in v
                and v["submit"] >= t0}
    print(f"\n=== timeline ({label}): {len(rows)} requests, "
          f"ms after wave t0 ===")
    for st in stages:
        xs = [(v[st] - t0) * 1e3 for v in rows.values() if st in v]
        if xs:
            print(f"  {st:12s} n={len(xs):3d} p10={pct(xs, .10):7.1f} "
                  f"p50={pct(xs, .50):7.1f} p90={pct(xs, .90):7.1f} "
                  f"max={max(xs):7.1f}")
    if client_first:
        xs = sorted(client_first)
        print(f"  {'client_1st':12s} n={len(xs):3d} p10={pct(xs, .10):7.1f} "
              f"p50={pct(xs, .50):7.1f} p90={pct(xs, .90):7.1f} "
              f"max={max(xs):7.1f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=256)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--skip-engine", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, "/root/repo")
    from localai_tfp_tpu.utils import compile_cache

    compile_cache.configure()
    import bench

    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.models.llm_spec import LLMSpec

    instrument_engine()
    tok = bench.WideByteTok() if hasattr(bench, "WideByteTok") else None
    if tok is None:
        # bench defines it inside main(); replicate
        from localai_tfp_tpu.engine.tokenizer import ByteTokenizer

        class WideByteTok(ByteTokenizer):
            def decode(self, ids):
                return "".join(
                    chr(32 + (i % 95)) for i in ids
                    if i not in (self.bos_id, *self.eos_ids))

        tok = WideByteTok()

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        print("WARNING: not on TPU; numbers are meaningless", flush=True)

    spec8 = LLMSpec(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_head=128, d_ff=14336, max_position=4096,
        rope_theta=500000.0,
    )
    print("building int8 params...", flush=True)
    t = time.perf_counter()
    params8 = bench._fast_int8_params(spec8)
    print(f"params in {time.perf_counter() - t:.1f}s", flush=True)
    eng = LLMEngine(
        spec8, params8, tok, n_slots=args.slots, max_seq=1024,
        decode_steps=16, cache_dtype="int8", autostart=False,
    )
    eng.start()
    t = time.perf_counter()
    eng.warmup()
    print(f"warmup in {time.perf_counter() - t:.1f}s", flush=True)

    # dispatch floor: trivial dispatch -> is_ready latency
    tiny = jnp.zeros((8,), jnp.float32)
    bump = jax.jit(lambda x: x + 1)
    bump(tiny).block_until_ready()
    for trial in range(3):
        t = time.perf_counter()
        y = bump(tiny)
        while not y.is_ready():
            time.sleep(2e-4)
        print(f"rtt_floor[{trial}] = "
              f"{(time.perf_counter() - t) * 1e3:.1f} ms", flush=True)

    n_tok = args.tokens
    # one warmup wave then one measured wave, engine-side
    if not args.skip_engine:
        bench._run_wave(eng, tok, args.slots, n_tok, "benchmark " * 12)
        bench._run_wave(eng, tok, args.slots, n_tok, "benchmark " * 12)
        smp = Sampler()
        t0 = time.perf_counter()
        smp.start()
        total, wall, tt, errs = bench._run_wave(
            eng, tok, args.slots, n_tok, "benchmark " * 12)
        smp.stop()
        print(f"\nENGINE wave: {total} tok in {wall:.2f}s = "
              f"{total / wall:.1f} tok/s; ttft p50="
              f"{tt[len(tt) // 2]:.0f}ms", flush=True)
        report_timeline(t0, [x for x in tt], label="engine")
        report_flights(t0 - 2.0, label="engine (incl 2s before t0)")
        smp.report()

    # HTTP leg: replicate bench._bench_http but with instrumentation
    import asyncio
    import json as _json
    import os
    import tempfile

    from aiohttp import ClientSession, ClientTimeout, TCPConnector, web

    from localai_tfp_tpu.config.app_config import ApplicationConfig
    from localai_tfp_tpu.engine.loader import LoadedModel
    from localai_tfp_tpu.server.app import build_app
    from localai_tfp_tpu.server.state import Application
    from localai_tfp_tpu.workers.llm import JaxLLMBackend

    tmp = tempfile.mkdtemp(prefix="prof-srv-")
    models = os.path.join(tmp, "models")
    os.makedirs(models)
    with open(os.path.join(models, "bench.yaml"), "w") as f:
        f.write(
            "name: bench\n"
            "backend: jax-llm\n"
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

    n_req = args.slots
    smp = Sampler()
    res = {}

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

            async def one(i, t0, ttfts):
                body = {
                    "model": "bench",
                    "messages": [{"role": "user",
                                  "content": "benchmark " * 10 + str(i)}],
                    "max_tokens": n_tok, "stream": True,
                    "temperature": 0.8, "top_k": 40, "top_p": 0.95,
                    "ignore_eos": True,
                }
                total = 0
                async with sess.post(
                    url, json=body, headers={"Extra-Usage": "1"},
                ) as r:
                    assert r.status == 200, await r.text()
                    async for line in r.content:
                        if not line.startswith(b"data: "):
                            continue
                        if line.strip() == b"data: [DONE]":
                            break
                        d = _json.loads(line[6:])
                        ch = d["choices"][0]
                        if (ch["delta"].get("content")
                                and ttfts[i] is None):
                            ttfts[i] = (time.perf_counter() - t0) * 1e3
                        if ch.get("finish_reason"):
                            u = d.get("usage") or {}
                            total = u.get("completion_tokens", 0)
                return total

            for run in range(3):
                ttfts = [None] * n_req
                if run == 2:
                    smp.start()
                t0 = time.perf_counter()
                totals = await asyncio.gather(
                    *[one(i, t0, ttfts) for i in range(n_req)])
                wall = time.perf_counter() - t0
                if run == 2:
                    smp.stop()
                    res["tok_s"] = sum(totals) / wall
                    res["t0"] = t0
                    res["ttfts"] = [t for t in ttfts if t is not None]
                    res["wall"] = wall
        await runner.cleanup()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(drive())
    finally:
        loop.close()

    tt = sorted(res["ttfts"])
    print(f"\nHTTP wave: {res['tok_s']:.1f} tok/s over {res['wall']:.2f}s; "
          f"ttft p50={tt[len(tt) // 2]:.0f}ms p95="
          f"{tt[int(len(tt) * .95)]:.0f}ms", flush=True)
    report_timeline(res["t0"], res["ttfts"], label="http")
    report_flights(res["t0"] - 2.0, label="http (incl 2s before t0)")
    smp.report()
    eng.close()


if __name__ == "__main__":
    main()
