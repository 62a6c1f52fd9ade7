"""Steady-state TTFT decomposition for the 8B serving config.

The scenario BASELINE.md's <200 ms p50 target describes: the engine is
saturated (63/64 slots decoding) and ONE new request arrives. Where do
its ~400 ms go?  This traces, per arrival:

  submit -> assign (scheduler pickup)
  assign -> prefill dispatch enqueue
  dispatch -> flight harvested (device queue ahead + prefill itself)
  harvest -> StreamEvent first token on the client queue

plus the dispatch log (kind, k, host-enqueue wall) between submit and
first token, which shows how much scan work was queued ahead.

Run manually on the chip:  python tools/profile_steady.py [--arrivals N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arrivals", type=int, default=10)
    ap.add_argument("--gap", type=float, default=0.5)
    args = ap.parse_args()

    from localai_tfp_tpu.utils import compile_cache

    compile_cache.configure()

    from bench import _fast_int8_params  # type: ignore
    from tools.profile_ttft import WideByteTok

    from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
    from localai_tfp_tpu.models.llm_spec import LLMSpec

    spec = LLMSpec(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_head=128, d_ff=14336, max_position=4096,
        rope_theta=500000.0)
    tok = WideByteTok()
    params = _fast_int8_params(spec)
    import jax.numpy as jnp

    eng = LLMEngine(spec, params, tok, n_slots=64, max_seq=1024,
                    decode_steps=16, cache_dtype=jnp.int8,
                    latency_target_ms=70.0,  # matches bench8b.yaml
                    autostart=True)
    eng.warmup()

    # ~22 byte-tokens -> the same 32 bucket the bench's real-BPE prompt
    # ("benchmark " * 12 -> ~25 BPE ids) hits, so every prefill variant
    # below is warm in the persistent compile cache
    prompt = tok.encode("benchmark " * 2)

    def req(i: int, n: int) -> GenRequest:
        return GenRequest(
            prompt_ids=prompt + [i % 200], max_tokens=n,
            temperature=0.8, top_k=40, top_p=0.95, ignore_eos=True)

    # same two compile-warmup waves _bench_config runs (cold-prompt,
    # then prefix-reuse variants) so the steady phase measures serving,
    # not compiles
    def warm_wave() -> None:
        qs = eng.submit_many([req(i, 16) for i in range(64)])
        for q in qs:
            while True:
                ev = q.get(timeout=1800)
                if ev.error:
                    raise RuntimeError(ev.error)
                if ev.done:
                    break

    for n in range(2):
        t0 = time.perf_counter()
        warm_wave()
        print(f"warm wave {n}: {time.perf_counter() - t0:.1f}s",
              flush=True)

    # -------- background load: 63 long streams --------
    bg_qs = eng.submit_many([req(i, 900) for i in range(63)])
    bg_stop = threading.Event()

    def drain_bg() -> None:
        done = 0
        while not bg_stop.is_set() and done < len(bg_qs):
            for q in bg_qs:
                try:
                    ev = q.get(timeout=0.05)
                    if ev.done:
                        done += 1
                except Exception:
                    pass

    bg_t = threading.Thread(target=drain_bg, daemon=True)
    bg_t.start()
    # let the wave prefill and settle into pure decode
    time.sleep(6.0)

    # -------- instrumented arrivals --------
    log: list = []
    orig_run = eng._run

    def traced_run(kind, payload):
        t0 = time.perf_counter()
        out = orig_run(kind, payload)
        t1 = time.perf_counter()
        sh = (list(payload["toks"].shape)
              if kind.startswith("prefill") else payload.get("k"))
        log.append((kind, sh, t0, round((t1 - t0) * 1e3, 1)))
        return out

    eng._run = traced_run
    arrivals = []
    for i in range(args.arrivals):
        time.sleep(args.gap)
        mark = len(log)
        t0 = time.perf_counter()
        q = eng.submit(req(1000 + i, 4))
        ttft = None
        while True:
            try:
                # generous: a first-of-shape arrival may sit behind a
                # cold jit; later arrivals of the same shape measure
                # serving
                ev = q.get(timeout=900)
            except Exception:
                states = {}
                for s in eng.slots:
                    states[str(s.state)] = states.get(str(s.state), 0) + 1
                print(json.dumps({
                    "STARVED": i, "slot_states": states,
                    "pending": len(eng._pending),
                    "flights": len(eng._flights),
                    "recent_dispatches": [
                        (k, sh, round((time.perf_counter() - at), 1))
                        for k, sh, at, _ in log[-6:]],
                }), flush=True)
                raise
            if ev.error:
                raise RuntimeError(f"arrival errored: {ev.error}")
            if ev.token_id is not None and ttft is None:
                ttft = (time.perf_counter() - t0) * 1e3
            if ev.done:
                break
        window = [
            {"kind": k, "shape": sh,
             "at_ms": round((at - t0) * 1e3, 1), "host_ms": ms}
            for k, sh, at, ms in log[max(0, mark - 4):]
            if at - t0 < (ttft or 1e9) / 1e3
        ]
        arrivals.append({"ttft_ms": round(ttft, 1), "dispatches": window})
    eng._run = orig_run
    bg_stop.set()

    tt = sorted(a["ttft_ms"] for a in arrivals)
    print(json.dumps({
        "steady_ttft_p50_ms": tt[len(tt) // 2],
        "steady_ttft_min_ms": tt[0],
        "steady_ttft_max_ms": tt[-1],
        "arrivals": arrivals,
    }, indent=1), flush=True)
    eng.close()


if __name__ == "__main__":
    main()
