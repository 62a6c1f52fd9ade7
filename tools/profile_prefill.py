"""Bisect the 8B prefill_final dispatch: which sub-graph costs ~400ms?

Times (enqueue -> result ready) for:
  A. full _prefill_final jit (what the engine dispatches)
  B. forward_hidden only (same shapes)
  C. forward_hidden + lm_head + plain sample
  D. reset_slots + seed_windows only
"""

import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np

from bench import _fast_int8_params  # noqa: E402
from localai_tfp_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

from localai_tfp_tpu.engine.engine import LLMEngine  # noqa: E402
from localai_tfp_tpu.engine.tokenizer import ByteTokenizer  # noqa: E402
from localai_tfp_tpu.models.llm_spec import LLMSpec  # noqa: E402

spec = LLMSpec(
    vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
    n_kv_heads=8, d_head=128, d_ff=14336, max_position=4096,
    rope_theta=500000.0,
)
params = _fast_int8_params(spec)
eng = LLMEngine(spec, params, ByteTokenizer(), n_slots=64, max_seq=1024,
                decode_steps=16, cache_dtype="int8", autostart=False)

B, bucket = 64, 32
W = eng.sampling.window
rng = np.random.default_rng(0)
toks = rng.integers(0, 200, (B, bucket)).astype(np.int32)
pos0 = np.zeros((B,), np.int32)
sids = np.arange(B, dtype=np.int32)
n_chunk = np.full((B,), bucket, np.int32)
tails = rng.integers(0, 200, (B, W)).astype(np.int32)
tail_lens = np.full((B,), 16, np.int32)
reset_np = eng._reset_columns([], 1)
reset = tuple(jnp.asarray(np.repeat(v, B, axis=0))
              for v in reset_np.values())


def flight(make):
    # compile
    out = make()
    jax.block_until_ready(out)
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        out = make()
        leaf = jax.tree_util.tree_leaves(out)[0]
        try:
            leaf.copy_to_host_async()
        except Exception:
            pass
        while not leaf.is_ready():
            time.sleep(0.0005)
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


from localai_tfp_tpu.models.transformer import (  # noqa: E402
    KVCache, _lm_head, forward_hidden,
)
from localai_tfp_tpu.ops.sampling import (  # noqa: E402
    reset_slots, sample, seed_windows,
)

# ---- A: the engine's own compiled prefill_final (cache/sampling donated:
# recreate per call) ----
fn = eng._prefill_final_fn(eng.max_seq)


_state = {"cache": eng.cache, "sampling": eng.sampling}


def run_full():
    out, _state["cache"], _state["sampling"] = fn(
        eng.params, jnp.asarray(toks), _state["cache"],
        jnp.asarray(pos0), _state["sampling"], jnp.asarray(sids),
        jnp.asarray(n_chunk), jnp.asarray(tails),
        jnp.asarray(tail_lens), None, reset, None)
    return out


print(f"A full prefill_final      {flight(run_full):8.1f} ms", flush=True)


@__import__("functools").partial(jax.jit, donate_argnums=(2,))
def fwd_only(params, toks, cache, pos0, sids):
    h, cache = forward_hidden(spec, params, toks, pos0, cache, sids)
    return h[:, -1, :].sum(), cache


def run_fwd():
    out, _state["cache"] = fwd_only(
        eng.params, jnp.asarray(toks), _state["cache"],
        jnp.asarray(pos0), jnp.asarray(sids))
    return out


print(f"B forward_hidden only     {flight(run_fwd):8.1f} ms", flush=True)


@__import__("functools").partial(jax.jit, donate_argnums=(2, 5))
def fwd_head_sample(params, toks, cache, pos0, sids, sampling, n_chunk):
    h, cache = forward_hidden(spec, params, toks, pos0, cache, sids)
    last = jax.vmap(
        lambda hh, n: jax.lax.dynamic_slice_in_dim(hh, n - 1, 1, 0)[0]
    )(h, n_chunk)
    logits = _lm_head(spec, params, last[:, None, :])[:, 0]
    t, sampling = sample(sampling, sids, logits)
    return t, cache, sampling


def run_fhs():
    t, _state["cache"], _state["sampling"] = fwd_head_sample(
        eng.params, jnp.asarray(toks), _state["cache"],
        jnp.asarray(pos0), jnp.asarray(sids), _state["sampling"],
        jnp.asarray(n_chunk))
    return t


print(f"C fwd+head+sample         {flight(run_fhs):8.1f} ms", flush=True)


@__import__("functools").partial(jax.jit, donate_argnums=(0,))
def reset_seed(sampling, sids, tails, tail_lens, reset):
    sampling = reset_slots(sampling, sids, *reset)
    sampling = seed_windows(sampling, sids, tails, tail_lens)
    return sampling.history_pos, sampling


def run_rs():
    out, _state["sampling"] = reset_seed(
        _state["sampling"], jnp.asarray(sids), jnp.asarray(tails),
        jnp.asarray(tail_lens), reset)
    return out


print(f"D reset+seed only         {flight(run_rs):8.1f} ms", flush=True)
