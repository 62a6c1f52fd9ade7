"""Per-component timing of the 8B decode step on TPU: isolates the
transformer forward, lm_head, sampler (top_k vs approx_max_k), and
penalty machinery to find where the ~31ms/step goes.

Chained-timing method (fixed dispatch and download costs cancel):
(N dependent iterations + download) - (1 + download) / (N-1).
"""

import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np

S, V, D = 64, 128256, 4096


def timed(fn, carry0, n=8, reps=3):
    np.asarray(jax.tree_util.tree_leaves(fn(carry0))[0]).reshape(-1)[0]

    def once(n):
        best = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            c = carry0
            for _ in range(n):
                c = fn(c)
            np.asarray(jax.tree_util.tree_leaves(c)[0]).reshape(-1)[:1]
            best = min(best, time.perf_counter() - t0)
        return best

    t1, tn = once(1), once(n)
    return (tn - t1) / (n - 1) * 1e3


def main():
    rng = np.random.default_rng(0)

    # --- sampler-ish ops on [S, V] logits ---
    logits = jnp.asarray(rng.standard_normal((S, V), np.float32))

    @jax.jit
    def f_topk(lg):
        vals, idx = jax.lax.top_k(lg, 128)
        return lg + vals[:, :1] * 1e-9  # chainable

    @jax.jit
    def f_approx(lg):
        vals, idx = jax.lax.approx_max_k(lg, 128)
        return lg + vals[:, :1] * 1e-9

    @jax.jit
    def f_argmax(lg):
        return lg + jnp.max(lg, axis=-1, keepdims=True) * 1e-9

    print(f"top_k(128) on [{S},{V}]: {timed(f_topk, logits):8.2f} ms",
          flush=True)
    print(f"approx_max_k(128):       {timed(f_approx, logits):8.2f} ms",
          flush=True)
    print(f"plain max:               {timed(f_argmax, logits):8.2f} ms",
          flush=True)

    # --- penalties: gather counts + where-chains on [S, V] ---
    counts = jnp.asarray(rng.integers(0, 3, (S, V), np.int32))

    @jax.jit
    def f_pen(lg):
        present = counts > 0
        rp = jnp.full((S, 1), 1.1, jnp.float32)
        pen = jnp.where(lg > 0, lg / rp, lg * rp)
        out = jnp.where(present, pen, lg)
        out = out - counts.astype(jnp.float32) * 0.1
        return out

    print(f"penalty chain [S,V]:     {timed(f_pen, logits):8.2f} ms",
          flush=True)

    # --- full sample() from the repo ---
    from localai_tfp_tpu.ops.sampling import SamplingState, sample

    st = SamplingState.create(S, V, window=256)
    ids = jnp.arange(S, dtype=jnp.int32)

    @jax.jit
    def f_sample(carry):
        lg, st = carry
        tok, st = sample(st, ids, lg)
        return (lg + tok[:, None].astype(jnp.float32) * 1e-9, st)

    print(f"full sample():           {timed(f_sample, (logits, st)):8.2f}"
          " ms", flush=True)

    # --- lm_head int8 [S,D]x[D,V] ---
    q = jnp.asarray(rng.integers(-127, 128, (D, V), np.int8))
    sc = jnp.full((V,), 1e-4, jnp.float32)
    x = jnp.asarray(rng.standard_normal((S, D), np.float32) * .1,
                    jnp.bfloat16)

    @jax.jit
    def f_lmhead(x):
        y = (x @ q.astype(x.dtype)) * sc.astype(x.dtype)
        return x + y[:, :D] * 1e-9

    print(f"lm_head int8 [S,D]@[D,V]:{timed(f_lmhead, x):8.2f} ms",
          flush=True)

    # --- ragged decode-attention kernel, 32 layers, ctx ~384 ---
    from localai_tfp_tpu.models.llm_spec import LLMSpec
    from localai_tfp_tpu.models.transformer import KVCache, forward

    spec = LLMSpec(
        vocab_size=V, d_model=D, n_layers=32, n_heads=32,
        n_kv_heads=8, d_head=128, d_ff=14336, max_position=4096,
        rope_theta=500000.0,
    )
    from bench import _fast_int8_params

    params = _fast_int8_params(spec)
    cache = KVCache.create(spec, S, 1024, "int8")
    pos0 = jnp.full((S,), 384, jnp.int32)

    @jax.jit
    def f_fwd_kernel(carry):
        toks, cache = carry
        lg, cache = forward(spec, params, toks, pos0, cache, None, True)
        nxt = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)[:, None]
        return (nxt, cache)

    @jax.jit
    def f_fwd_xla(carry):
        toks, cache = carry
        lg, cache = forward(spec, params, toks, pos0, cache, None, False)
        nxt = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)[:, None]
        return (nxt, cache)

    toks = jnp.ones((S, 1), jnp.int32)
    print(f"forward+argmax (kernel): {timed(f_fwd_kernel, (toks, cache), n=4):8.2f} ms",
          flush=True)
    cache2 = KVCache.create(spec, S, 1024, "int8")
    print(f"forward+argmax (xla):    {timed(f_fwd_xla, (toks, cache2), n=4):8.2f} ms",
          flush=True)


if __name__ == "__main__":
    main()
