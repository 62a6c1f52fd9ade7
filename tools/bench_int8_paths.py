"""Microbench: 8B-geometry decode-step weight-matmul strategies on TPU.

One decode step at batch B over 32 stacked layers (lax.scan, like the
engine's per-layer scan): q/k/v/o + gate/up/down projections only (no
attention, no sampling) — isolates the weight-read path that dominates
decode. Compares:
  xla_upcast   x @ q.astype(bf16) * scale      (current default path)
  pallas_512   current ops/int8_matmul (BK=BN=512)
  w8a8         dynamic per-row activation int8, int8xint8 dot (native MXU)

Roofline: int8 weights/layer ~218 MB; 32 layers ~7 GB; v5e ~819 GB/s
=> ~8.5 ms/step floor.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

B = 64
D, DQ, DKV, F, L = 4096, 4096, 1024, 14336, 32


def make_params(rng):
    def qt(k, n):
        q = rng.integers(-127, 128, (L, k, n), np.int8)
        s = (rng.random((L, n), np.float32) * 0.01 + 0.005) / 127.0
        return jnp.asarray(q), jnp.asarray(s)

    return {
        "wq": qt(D, DQ), "wk": qt(D, DKV), "wv": qt(D, DKV),
        "wo": qt(DQ, D), "w_gate": qt(D, F), "w_up": qt(D, F),
        "w_down": qt(F, D),
    }


def layer_xla(x, lw):
    def mm(x, w):
        q, s = w
        return (x @ q.astype(x.dtype)) * s.astype(x.dtype)

    h = mm(x, lw["wq"]) + mm(x, lw["wk"]).sum() + mm(x, lw["wv"]).sum()
    h = mm(h, lw["wo"])
    g = jax.nn.silu(mm(h, lw["w_gate"])) * mm(h, lw["w_up"])
    return x + mm(g, lw["w_down"])


def layer_pallas(x, lw):
    from localai_tfp_tpu.ops.int8_matmul import int8_matmul

    def mm(x, w):
        q, s = w
        return int8_matmul(x, q, s, out_dtype=x.dtype)

    h = mm(x, lw["wq"]) + mm(x, lw["wk"]).sum() + mm(x, lw["wv"]).sum()
    h = mm(h, lw["wo"])
    g = jax.nn.silu(mm(h, lw["w_gate"])) * mm(h, lw["w_up"])
    return x + mm(g, lw["w_down"])


def layer_w8a8(x, lw):
    def mm(x, w):
        q, s = w
        # dynamic per-row activation quant
        xs = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-9
        xq = jnp.clip(jnp.round(x / xs), -127, 127).astype(jnp.int8)
        y = jax.lax.dot_general(
            xq, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return (y.astype(jnp.float32) * xs * s).astype(x.dtype)

    h = mm(x, lw["wq"]) + mm(x, lw["wk"]).sum() + mm(x, lw["wv"]).sum()
    h = mm(h, lw["wo"])
    g = jax.nn.silu(mm(h, lw["w_gate"])) * mm(h, lw["w_up"])
    return x + mm(g, lw["w_down"])


def run(name, layer_fn, params, x, n_chain=8):
    """Chained timing: time (n_chain dependent steps + download) and
    (1 step + download); per-step = delta / (n_chain - 1) — the fixed
    dispatch and download costs cancel."""
    @jax.jit
    def step(params, x):
        def body(h, lw):
            return layer_fn(h, lw), ()

        h, _ = jax.lax.scan(body, x, params)
        return jnp.tanh(h)  # keep output bounded across chained steps

    def timed(n):
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            h = x
            for _ in range(n):
                h = step(params, h)
            np.asarray(h[0, 0])
            best = min(best, time.perf_counter() - t0)
        return best

    np.asarray(step(params, x)[0, 0])  # compile
    t1 = timed(1)
    tn = timed(n_chain)
    t = (tn - t1) / (n_chain - 1) * 1e3
    print(f"{name:12s} {t:8.2f} ms/step (chained)   "
          f"1-step+rtt {t1 * 1e3:6.1f} ms   "
          f"({7e9 / 1e9 / (t / 1e3):6.1f} GB/s eff. weight BW)",
          flush=True)
    return t


def main():
    import sys

    sys.path.insert(0, "/root/repo")
    rng = np.random.default_rng(0)
    params = make_params(rng)
    x = jnp.asarray(rng.standard_normal((B, D), np.float32) * 0.1,
                    jnp.bfloat16)
    jax.block_until_ready(params)
    run("xla_upcast", layer_xla, params, x)
    run("w8a8", layer_w8a8, params, x)
    import os

    os.environ["LOCALAI_INT8_KERNEL"] = "1"
    run("pallas_512", layer_pallas, params, x)


if __name__ == "__main__":
    main()
