"""Cold-start regression check: load the bench 8B artifact once and
print the phase-timing breakdown table.

The r5 bench reported `checkpoint_load_s = 256.9` in artifact mode
against a ~90 s annotation — 167 unattributed seconds. The loader now
bills every load into phases (models/load_timing.py:
read/dequant/transfer/compile/warmup + other); this tool makes the
breakdown a one-command check so a regression in any single phase is
visible the day it lands, not at the end-of-round bench.

Runs the SAME path bench.py's 8B leg takes: real-format HF checkpoint
(cached across runs) -> Application -> ModelLoader -> JaxLLMBackend
(artifact cache on, so the second run measures the artifact-mode load).
On CPU hosts a tiny geometry is substituted so the tool runs anywhere.

The --gallery mode measures the weight-paging story instead
(engine/weight_pager.py): N models round-robin on one chip with the
HBM weight budget sized for ~2 of them, so every visit to a paged-out
model pays a warm PROMOTION (layer-streamed H2D from the host mirror)
rather than a cold load. Reports cold vs warm vs hot first-token
latency, the HBM high-water mark against the budget, and LRU thrash
(coordinator pressure demotions).

Usage:
  python tools/profile_coldstart.py            # geometry by backend
  python tools/profile_coldstart.py --tiny     # force tiny (CPU smoke)
  python tools/profile_coldstart.py --cold     # drop the quant artifact
                                               # first: measure the full
                                               # (streamed) load
  python tools/profile_coldstart.py --gallery  # N-model paging smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _pctl(xs: list, q: float) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def gallery_shape(n_models: int = 4, rounds: int = 3) -> dict:
    """The gallery contention story on small engines: N models share
    one chip, the weight-HBM budget fits ~2, a round-robin client
    visits them all. First-token latency is bucketed by the pager
    state the visit found (cold = engine build + transfer + first
    step; warm = layer-streamed promotion; hot = resident). Returns
    the JSON-able shape bench.py embeds as ``extra.weight_paging``."""
    import jax
    import jax.numpy as jnp

    from localai_tfp_tpu.engine.engine import GenRequest, LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
    from localai_tfp_tpu.engine.weight_pager import COORD
    from localai_tfp_tpu.models.llm_spec import tiny_spec
    from localai_tfp_tpu.models.transformer import init_params

    tok = ByteTokenizer()
    spec = tiny_spec(vocab_size=tok.vocab_size, max_position=256)
    saved = {k: os.environ.get(k)
             for k in ("LOCALAI_WEIGHT_PAGING", "LOCALAI_WEIGHT_HBM_MB")}
    os.environ["LOCALAI_WEIGHT_PAGING"] = "on"
    os.environ["LOCALAI_WEIGHT_HBM_MB"] = "0"
    engines: list = []
    high_water = 0
    thrash0 = COORD.counters["pressure_demotes"]

    def first_token_s(eng, prompt: str) -> float:
        t0 = time.perf_counter()
        q = eng.submit(GenRequest(prompt_ids=eng.tokenize(prompt),
                                  max_tokens=4, temperature=0.0,
                                  ignore_eos=True))
        t1 = None
        while True:
            ev = q.get(timeout=300)
            if t1 is None and ev.token_id is not None:
                t1 = time.perf_counter()
            if ev.done:
                break
        return (t1 or time.perf_counter()) - t0

    try:
        cold, warm, hot = [], [], []
        budget_mb = 0.0
        for i in range(n_models):
            params = init_params(jax.random.PRNGKey(i), spec,
                                 dtype=jnp.float32)
            t0 = time.perf_counter()
            eng = LLMEngine(spec, params, tok, n_slots=2, max_seq=128,
                            prefill_buckets=(8, 32))
            cold.append(time.perf_counter() - t0
                        + first_token_s(eng, f"gallery model {i}"))
            engines.append(eng)
            if i == 0:
                # budget fits ~2 trees: from the third model on, every
                # arrival pressures the LRU resident out
                budget_mb = (eng._pager.tree_bytes() * 2.5) / (1 << 20)
                os.environ["LOCALAI_WEIGHT_HBM_MB"] = \
                    f"{budget_mb:.6f}"
            high_water = max(high_water, sum(
                e._pager.device_bytes() for e in engines))
        for r in range(rounds):
            for i, eng in enumerate(engines):
                state = eng._pager.state
                dt = first_token_s(eng, f"round {r} model {i}")
                (hot if state == "hot" else warm).append(dt)
                high_water = max(high_water, sum(
                    e._pager.device_bytes() for e in engines))
        # let in-flight pressure demotions land before reading state
        for eng in engines:
            eng._pager.settle(30)
        residency = COORD.residency()
        for eng in engines:
            eng._pager.leak_check()
        cold_p50, warm_p50 = _pctl(cold, 0.5), _pctl(warm, 0.5)
        return {
            "n_models": n_models,
            "rounds": rounds,
            "tree_mb": round(
                engines[0]._pager.tree_bytes() / (1 << 20), 3),
            "hbm_budget_mb": round(budget_mb, 3),
            "cold_first_token_s": {
                "p50": round(cold_p50, 4), "max": round(max(cold), 4),
                "n": len(cold)},
            "warm_first_token_s": {
                "p50": round(warm_p50, 4),
                "max": round(max(warm), 4) if warm else 0.0,
                "n": len(warm)},
            "hot_first_token_s": {
                "p50": round(_pctl(hot, 0.5), 4), "n": len(hot)},
            "warm_vs_cold_speedup": round(
                cold_p50 / max(warm_p50, 1e-9), 2) if warm else None,
            "hbm_high_water_mb": round(high_water / (1 << 20), 3),
            "lru_thrash_demotes":
                COORD.counters["pressure_demotes"] - thrash0,
            "residency": residency,
        }
    finally:
        for eng in engines:
            eng.close()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="force the tiny CPU geometry")
    ap.add_argument("--cold", action="store_true",
                    help="remove the quant artifact first (full load)")
    ap.add_argument("--no-warmup-reuse", action="store_true",
                    help="ignore persistent-cache warmup markers")
    ap.add_argument("--gallery", action="store_true",
                    help="N-model round-robin weight-paging smoke")
    ap.add_argument("--models", type=int, default=4,
                    help="gallery size (with --gallery)")
    ap.add_argument("--rounds", type=int, default=3,
                    help="round-robin passes (with --gallery)")
    args = ap.parse_args()

    if args.no_warmup_reuse:
        os.environ["LOCALAI_WARMUP_REUSE"] = "off"

    if args.gallery:
        g = gallery_shape(n_models=args.models, rounds=args.rounds)
        print(f"\ngallery: {g['n_models']} models x {g['rounds']} "
              f"rounds, {g['tree_mb']:.1f} MB trees under a "
              f"{g['hbm_budget_mb']:.1f} MB weight budget")
        for k in ("cold", "warm", "hot"):
            row = g[f"{k}_first_token_s"]
            print(f"  {k:<5} first token p50 {row['p50'] * 1e3:8.1f} ms"
                  f"   (n={row['n']})")
        print(f"  warm vs cold speedup : {g['warm_vs_cold_speedup']}x")
        print(f"  HBM high water       : {g['hbm_high_water_mb']:.1f} "
              f"MB (budget {g['hbm_budget_mb']:.1f} MB)")
        print(f"  LRU pressure demotes : {g['lru_thrash_demotes']}")
        print(f"  residency at rest    : {g['residency']}")
        print("\nJSON: " + json.dumps(g))
        return

    import jax

    from localai_tfp_tpu.utils import compile_cache

    compile_cache.configure()

    import shutil
    import tempfile
    import time

    from bench import _write_hf_checkpoint
    from localai_tfp_tpu.config.app_config import ApplicationConfig
    from localai_tfp_tpu.engine.loader import register_default_backends
    from localai_tfp_tpu.models.llm_spec import LLMSpec
    from localai_tfp_tpu.server.state import Application

    on_tpu = jax.default_backend() == "tpu" and not args.tiny
    if on_tpu:
        spec = LLMSpec(
            vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_head=128, d_ff=14336, max_position=4096,
            rope_theta=500000.0,
        )
        slots, ctx = 64, 1024
    else:
        spec = LLMSpec(
            vocab_size=512, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, d_head=16, d_ff=128, max_position=256,
        )
        slots, ctx = 2, 128

    import hashlib

    key = hashlib.sha256(
        (repr(spec) + "|writer-v2").encode()).hexdigest()[:16]
    cache_root = os.environ.get(
        "XDG_CACHE_HOME", os.path.expanduser("~/.cache"))
    ckpt = os.path.join(cache_root, f"localai_bench_ckpt_{key}")
    if not os.path.exists(os.path.join(ckpt, ".complete")):
        shutil.rmtree(ckpt, ignore_errors=True)
        print(f"writing checkpoint {ckpt} ...", flush=True)
        _write_hf_checkpoint(ckpt, spec)
        with open(os.path.join(ckpt, ".complete"), "w") as f:
            f.write("ok")

    if args.cold:
        from localai_tfp_tpu.models.artifact_cache import artifact_path

        p = artifact_path(ckpt, "int8_full", "bfloat16")
        if os.path.exists(p):
            os.unlink(p)
            print(f"dropped artifact {p} (cold full load)", flush=True)

    tmp = tempfile.mkdtemp(prefix="coldstart-")
    try:
        models = os.path.join(tmp, "models")
        os.makedirs(models)
        os.symlink(ckpt, os.path.join(models, "ckpt"))
        with open(os.path.join(models, "prof.yaml"), "w") as f:
            f.write(
                "name: prof\n"
                "backend: jax-llm\n"
                "parameters:\n  model: ckpt\n"
                f"context_size: {ctx}\n"
                f"max_batch_slots: {slots}\n"
                "quantization: int8_full\n"
                "kv_cache_dtype: int8\n"
                "decode_steps: 16\n"
                "latency_target_ms: 70\n"
            )
        state = Application(ApplicationConfig(
            models_path=models,
            generated_content_dir=os.path.join(tmp, "generated"),
            upload_dir=os.path.join(tmp, "uploads"),
            config_dir=os.path.join(tmp, "configuration"),
        ))
        register_default_backends()
        state.config_loader.load_configs_from_path()
        t0 = time.perf_counter()
        backend = state.model_loader.load(state.config_loader.get("prof"))
        total = time.perf_counter() - t0
        bd = dict(getattr(backend, "load_breakdown", {}) or {})
        mode = bd.pop("load_mode", getattr(backend, "load_mode", "?"))
        reused = bd.pop("warmup_reused", False)

        print(f"\ncold-start load: {total:.1f}s  mode={mode}  "
              f"warmup_reused={reused}")
        print(f"{'phase':<12}{'seconds':>9}   share")
        tot = bd.get("total_s") or total
        for p in ("read_s", "dequant_s", "transfer_s", "compile_s",
                  "warmup_s", "other_s"):
            v = float(bd.get(p, 0.0))
            bar = "#" * int(40 * v / tot) if tot else ""
            print(f"{p:<12}{v:>9.2f}   {bar}")
        print(f"{'total_s':<12}{float(bd.get('total_s', total)):>9.2f}")
        print("\nJSON: " + json.dumps(
            {**bd, "load_mode": mode, "warmup_reused": reused}))
        # leave the artifact behind so the NEXT run measures artifact
        # mode: the deferred write is abandoned by shutdown(), so wait
        # for it here (idle engine -> starts immediately)
        t = getattr(backend, "_artifact_thread", None)
        if t is not None:
            print("waiting for quant artifact write ...", flush=True)
            t.join(timeout=600)
        backend.shutdown()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
