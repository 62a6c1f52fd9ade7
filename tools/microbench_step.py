"""Where does the 8B decode step's ~28 ms/token-step go?

r5 flight traces: a k=16 decode scan executes in ~450 ms on an idle
chip (64 slots, int8 weights + int8 KV) — ~28 ms per step vs a ~10 ms
weight-read roofline — and the [64, 4] admission program takes
~235 ms. This tool times the pieces in isolation on the real chip:

  forward-only scan  : k steps of forward + argmax (no sampler)
  full scan          : the engine's real _decode_k (forward + sampler)
  sampler-only scan  : k sampler calls on fixed logits
  mixed              : the engine's real [64, W] admission step

Usage: python tools/microbench_step.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(label, fn, n=4):
    # one untimed call to absorb compile / cache load
    out = fn()
    for x in (out if isinstance(out, tuple) else (out,)):
        try:
            x.block_until_ready()
        except Exception:
            pass
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn()
        for x in (out if isinstance(out, tuple) else (out,)):
            try:
                x.block_until_ready()
            except Exception:
                pass
        best = min(best, time.perf_counter() - t0)
    print(f"{label:28s} {best * 1e3:8.1f} ms", flush=True)
    return out, best


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from localai_tfp_tpu.utils import compile_cache

    compile_cache.configure()

    from localai_tfp_tpu.engine.engine import (LLMEngine, _sample_masked)
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
    from localai_tfp_tpu.models.llm_spec import LLMSpec
    from localai_tfp_tpu.models.transformer import forward

    class WideByteTok(ByteTokenizer):
        def decode(self, ids):
            return "".join(chr(32 + (i % 95)) for i in ids
                           if i not in (self.bos_id, *self.eos_ids))

    spec = LLMSpec(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_head=128, d_ff=14336, max_position=4096,
        rope_theta=500000.0,
    )
    print("building params...", flush=True)
    params = bench._fast_int8_params(spec)
    S, K, W = 64, 16, 1024
    eng = LLMEngine(
        spec, params, WideByteTok(), n_slots=S, max_seq=W,
        decode_steps=K, cache_dtype="int8", autostart=False,
    )
    use_kernel = eng._use_kernel
    print(f"use_kernel={use_kernel}", flush=True)

    from functools import partial

    from jax import lax

    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 1000, (S, 1), np.int32))
    pos0 = jnp.full((S,), 128, jnp.int32)
    active = jnp.ones((S,), bool)
    sids = eng._all_slot_ids

    # --- sampler only: k sampler calls on fixed logits
    logits = jnp.asarray(np.random.default_rng(1).standard_normal(
        (S, spec.vocab_size)).astype(np.float32))

    @jax.jit
    def sampler_scan(sampling):
        def step(s, _):
            toks, s = _sample_masked(s, sids, logits, active, None)
            return s, toks

        s, toks = lax.scan(step, sampling, None, length=K)
        return toks, s

    sampling = eng.sampling
    (toks, sampling), dt_samp = timeit("sampler-only scan k=16",
                                       lambda: sampler_scan(sampling))

    # --- forward only (argmax): same window slicing as the real scan
    from localai_tfp_tpu.engine.cache_route import (
        _restore_window, _window_cache,
    )

    @partial(jax.jit, donate_argnums=(2,))
    def fwd_scan(params, tokens, full, pos0):
        cache = _window_cache(full, W)

        def step(carry, _):
            tokens, pos, cache = carry
            logits, cache = forward(spec, params, tokens, pos, cache,
                                    None, use_kernel)
            toks = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
            pos = jnp.where(active, pos + 1, pos)
            return (toks[:, None], pos, cache), toks

        (t2, p2, cache), seq = lax.scan(
            step, (tokens, pos0, cache), None, length=K)
        return seq.T, _restore_window(full, cache)

    cache = eng.cache

    def run_fwd():
        nonlocal cache
        seq, cache = fwd_scan(params, tokens, cache, pos0)
        return (seq,)

    _, dt_fwd = timeit("forward-only scan k=16", run_fwd)

    # --- the engine's real full scan
    fn = eng._decode_k_fn(K, W)
    state = {"cache": cache, "sampling": sampling,
             "tok": tokens, "pos": pos0}

    def run_full():
        seq, t2, p2, state["cache"], state["sampling"] = fn(
            params, state["tok"], state["cache"], state["pos"], sids,
            state["sampling"], active)
        state["tok"], state["pos"] = t2, p2
        return (seq,)

    _, dt_full = timeit("full decode scan k=16", run_full)

    print(f"\nper-step: fwd {dt_fwd / K * 1e3:.1f} ms, "
          f"full {dt_full / K * 1e3:.1f} ms, "
          f"sampler-only {dt_samp / K * 1e3:.1f} ms", flush=True)

    # hand the donated-chain live buffers back to the engine: _dev_exec
    # reads self.cache/self.sampling, and the originals were consumed by
    # the scans above
    eng.cache = state["cache"]
    eng.sampling = state["sampling"]

    # --- the admission step at [64, 4] (the burst-TTFT floor)
    reset = {k: np.asarray(v) for k, v in {
        "temperature": np.full(S, 0.8, np.float32),
        "top_k": np.full(S, 40, np.int32),
        "top_p": np.full(S, 0.95, np.float32),
        "min_p": np.zeros(S, np.float32),
        "repeat_penalty": np.zeros(S, np.float32),
        "freq_penalty": np.zeros(S, np.float32),
        "presence_penalty": np.zeros(S, np.float32),
        "repeat_last_n": np.full(S, 64, np.int32),
        "seeds": np.zeros(S, np.int32),
        "has_seed": np.zeros(S, bool),
        "typical_p": np.ones(S, np.float32),
        "mirostat": np.zeros(S, np.int32),
        "mirostat_tau": np.full(S, 5.0, np.float32),
        "mirostat_eta": np.full(S, 0.1, np.float32),
    }.items()}
    # decompose the prompt group: forward_hidden vs the sampler tail
    from localai_tfp_tpu.models.transformer import _lm_head, forward_hidden
    from localai_tfp_tpu.ops.sampling import (reset_slots, sample,
                                              seed_windows)

    sids_np = jnp.arange(S, dtype=jnp.int32)

    @partial(jax.jit, donate_argnums=(2,))
    def pf_fwd(params, tokens, cache, pos0, slot_ids):
        return forward_hidden(spec, params, tokens, pos0, cache, slot_ids)

    @jax.jit
    def pf_tail(params, sampling, slot_ids, hidden, n_chunk, tails,
                tail_lens, reset_cols):
        sampling = reset_slots(sampling, slot_ids, *reset_cols)
        sampling = seed_windows(sampling, slot_ids, tails, tail_lens)
        last_h = jax.vmap(
            lambda h, n: lax.dynamic_slice_in_dim(h, n - 1, 1, 0)[0]
        )(hidden, n_chunk)
        logits = _lm_head(spec, params, last_h[:, None, :])[:, 0]
        toks, sampling = sample(sampling, slot_ids, logits, mask=None)
        return toks, sampling

    tok4 = jnp.zeros((S, 4), jnp.int32)
    pos4 = jnp.full((S,), 64, jnp.int32)

    def run_pf_fwd():
        hidden, eng.cache = pf_fwd(params, tok4, eng.cache, pos4, sids_np)
        return (hidden,)

    (hidden4,), _ = timeit("pf forward_hidden [64,4]", run_pf_fwd)

    @partial(jax.jit, donate_argnums=(2,))
    def pf_fwd_id(params, tokens, cache, pos0):
        return forward_hidden(spec, params, tokens, pos0, cache, None)

    def run_pf_fwd_id():
        hidden, eng.cache = pf_fwd_id(params, tok4, eng.cache, pos4)
        return (hidden,)

    timeit("pf fwd identity [64,4]", run_pf_fwd_id)

    tok128 = jnp.zeros((S, 128), jnp.int32)

    def run_pf_fwd_id128():
        hidden, eng.cache = pf_fwd_id(params, tok128, eng.cache, pos4)
        return (hidden,)

    timeit("pf fwd identity [64,128]", run_pf_fwd_id128)
    reset_cols = tuple(jnp.asarray(v) for v in (
        np.full(S, 0.8, np.float32), np.full(S, 40, np.int32),
        np.full(S, 0.95, np.float32), np.zeros(S, np.float32),
        np.zeros(S, np.float32), np.zeros(S, np.float32),
        np.zeros(S, np.float32), np.full(S, 64, np.int32),
        np.zeros(S, np.int32), np.zeros(S, bool),
        np.ones(S, np.float32), np.zeros(S, np.int32),
        np.full(S, 5.0, np.float32), np.full(S, 0.1, np.float32)))
    tails_j = jnp.zeros((S, eng.sampling.window), jnp.int32)
    tlens_j = jnp.zeros((S,), jnp.int32)
    nchunk_j = jnp.ones((S,), jnp.int32)

    def run_pf_tail():
        toks, _ = pf_tail(params, eng.sampling, sids_np, hidden4,
                          nchunk_j, tails_j, tlens_j, reset_cols)
        return (toks,)

    timeit("pf sampler tail only", run_pf_tail)

    for Wp in (4, 128):
        payload = {
            "toks": np.zeros((S, Wp), np.int32),
            "pos0": np.full((S,), 64, np.int32),
            "slot_ids": np.arange(S, dtype=np.int32),
            "masks": None, "soft": None,
            "n_chunk": np.full((S,), 1, np.int32),
            "final": np.ones((S,), bool),
            "tails": np.zeros((S, eng.sampling.window), np.int32),
            "tail_lens": np.zeros((S,), np.int32),
            "reset": reset,
            "window": W, "carry": False,
            "dtoks": np.zeros((S, 1), np.int32),
            "dpos": np.zeros((S,), np.int32),
            "active": np.zeros((S,), bool),
        }

        def run_pf(payload=payload):
            return (eng._dev_exec("mixed", payload),)

        timeit(f"mixed, no row decoding [{S},{Wp}]", run_pf)


if __name__ == "__main__":
    main()
