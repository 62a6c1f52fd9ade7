"""Meshed boot-time check: cold precompile vs artifact+warmup-reuse.

PR 12 routes every meshed dispatch kind through the sharded ragged
branch, which collapses the meshed warmup ladder to one variant per
token-budget shape AND lets a meshed ``engine.warmup()`` participate in
the persistent-cache warmup-reuse path (the marker-skip that
single-chip engines got in the artifact-cache PR). This tool makes the
payoff a one-command number: boot the SAME meshed paged engine twice in
fresh processes sharing one persistent compilation cache dir —

  cold:  empty cache dir, full precompile pass (every jit variant is a
         real compile)
  reuse: warm cache dir, the completed-warmup marker short-circuits the
         whole pass (any variant a request later touches loads from the
         persistent cache instead of compiling)

and print both walls. Each leg is its own process because the in-process
jit cache would make any second warmup trivially fast regardless of the
persistent cache (the thing being measured).

The legs only build + warm up — no decode is served. The persistent
compilation cache on this CPU stack miscompiles donated-buffer reuse
(the test suite never enables it for the same reason), and boot wall is
the measurement anyway.

Usage:
  python tools/profile_boot.py               # 8 virtual CPU devices
  python tools/profile_boot.py --devices 4
  python tools/profile_boot.py --cache-dir D # persist D across runs
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _leg(n_devices: int) -> dict:
    """One boot, in THIS process: force the host device count, enable
    the persistent cache (where JAX_COMPILATION_CACHE_DIR — set by the
    parent — says), construct the meshed paged engine, warm up."""
    from __graft_entry__ import _force_host_devices

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = _force_host_devices(
        os.environ.get("XLA_FLAGS", ""), n_devices)

    import jax
    import jax.numpy as jnp

    from localai_tfp_tpu.utils import compile_cache

    compile_cache.configure()

    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
    from localai_tfp_tpu.models.llm_spec import tiny_spec
    from localai_tfp_tpu.models.transformer import init_params
    from localai_tfp_tpu.parallel.mesh import make_mesh

    devs = jax.devices("cpu")[:n_devices]
    if len(devs) != n_devices:
        raise SystemExit(
            f"needed {n_devices} CPU devices, got {len(devs)}")
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=1024)
    n = len(devs)
    model_ax = next((m for m in (4, 2)
                     if n % m == 0 and spec.kv_dim % m == 0), 1)
    data_ax = 2 if (n // model_ax) % 2 == 0 else 1
    mesh = make_mesh({"data": data_ax, "seq": 1, "model": model_ax},
                     devices=devs[:data_ax * model_ax])
    params = init_params(jax.random.PRNGKey(0), spec,
                         dtype=jnp.float32)
    t0 = time.perf_counter()
    # max_seq above the 256 window floor: a real ladder is what the
    # cold pass pays for and the marker-skip saves
    eng = LLMEngine(spec, params, tk, n_slots=2, max_seq=1024,
                    prefill_buckets=(8, 32), decode_steps=4,
                    cache_dtype=jnp.float32, mesh=mesh,
                    autostart=False)
    build_s = time.perf_counter() - t0
    if not eng._paged:
        raise SystemExit("engine fell back to dense on this mesh")
    t1 = time.perf_counter()
    eng.warmup()
    warmup_s = time.perf_counter() - t1
    out = {
        "boot_s": round(build_s + warmup_s, 2),
        "build_s": round(build_s, 2),
        "warmup_s": round(warmup_s, 2),
        "warmup_variants": int(eng.warmup_variants),
        "warmup_reused": bool(eng.warmup_reused),
        "mesh_devices": data_ax * model_ax,
        "mesh_data": data_ax,
        "mesh_model": model_ax,
    }
    eng.close()
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--cache-dir", default=None,
                    help="persistent compile cache dir shared by both "
                         "legs (default: a fresh temp dir)")
    ap.add_argument("--leg", choices=("cold", "reuse"), default=None,
                    help=argparse.SUPPRESS)  # child-process entry
    args = ap.parse_args()

    if args.leg is not None:
        out = _leg(args.devices)
        out["mode"] = args.leg
        print("BOOT_LEG " + json.dumps(out))
        return

    import shutil
    import tempfile

    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="boot-cache-")
    own_dir = args.cache_dir is None

    def run(leg: str) -> dict:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--leg", leg, "--devices", str(args.devices)],
            # the cold leg needs a cache that is known empty, so this
            # tool places it for its children the way any launcher
            # does: through the environment
            env={**os.environ, "JAX_COMPILATION_CACHE_DIR": cache_dir},
            capture_output=True, text=True, timeout=1800)
        for line in proc.stdout.splitlines():
            if line.startswith("BOOT_LEG "):
                return json.loads(line[len("BOOT_LEG "):])
        raise SystemExit(
            f"{leg} leg failed (rc={proc.returncode}):\n"
            f"{proc.stderr[-2000:]}")

    try:
        cold = run("cold")  # empty dir: every variant really compiles
        reuse = run("reuse")  # same dir: the warmup marker skips the pass
        if cold["warmup_reused"]:
            raise SystemExit("cold leg unexpectedly hit a warmup marker "
                             f"in {cache_dir} — pass a fresh --cache-dir")
        if not reuse["warmup_reused"]:
            raise SystemExit("reuse leg did not hit the warmup marker")
        speedup = cold["boot_s"] / max(reuse["boot_s"], 1e-9)
        print(json.dumps({
            "cold": cold,
            "reuse": reuse,
            "boot_speedup": round(speedup, 2),
        }, indent=2))
    finally:
        if own_dir:
            shutil.rmtree(cache_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
