"""End-of-round benchmark: streaming decode throughput + p50 TTFT of the
serving engine (the metrics behind BASELINE.md's north star: >=2000
tok/s/chip and p50 TTFT < 200 ms on Llama-3.1-8B-class serving).

Prints ONE JSON line whose HEADLINE ("value") is the 8B-geometry
(Llama-3.1-8B: 32L/4096d/128k-vocab, int8 weight-only + int8 KV — the
largest honest single-chip config; bf16 8B exceeds one v5e's HBM)
streaming decode throughput measured THROUGH the stock
/v1/chat/completions endpoint with 64 concurrent SSE streams. "extra"
carries: p50/p95 TTFT for the same HTTP run, the same config measured
engine-side (no HTTP), a 1B-class config kept for cross-round
continuity, and a compiled-kernel parity record
(ops/kernel_check.py — the CPU-pinned test suite only exercises Pallas
kernels in interpret mode, so mosaic parity is validated here, on the
real chip, every round).

Runs the real continuous-batching engine (engine/engine.py) — scheduler,
sampler, detokenizer and all — not a bare forward loop, so the number is
the honest serving throughput a /v1/chat/completions client would see.
Model weights are random-init (zero egress); throughput does not depend
on weight values. On TPU the full configs are used; on CPU (smoke runs)
a tiny config.

Ref measurement primitives mirrored: Reply.timing_prompt_processing /
timing_token_generation (backend/backend.proto:163-164) — TTFT here is
submit->first-content wall time per request, p50 over the wave.
"""

from __future__ import annotations

import json
import time

BASELINE_TOK_S = 2000.0  # BASELINE.md: >=2000 tok/s/chip on v5e
BASELINE_TTFT_MS = 200.0  # BASELINE.md: p50 TTFT < 200 ms


def _run_wave(eng, tok, n_req, n_tok, prompt_text):
    """Submit one admission wave; returns (total_tokens, wall_s,
    sorted per-request TTFT list in ms)."""
    from localai_tfp_tpu.engine.engine import GenRequest

    prompt = tok.encode(prompt_text)
    qs = eng.submit_many([
        GenRequest(
            prompt_ids=prompt + [i % 200],
            max_tokens=n_tok,
            temperature=0.8,
            top_k=40,
            top_p=0.95,
            ignore_eos=True,
        )
        for i in range(n_req)
    ])
    t0 = time.perf_counter()
    ttft = [None] * n_req
    total = 0
    errors: list[str] = []
    # drain all queues round-robin so TTFT is measured per request
    pending = list(enumerate(qs))
    while pending:
        nxt = []
        for i, q in pending:
            finished = False
            while True:
                try:
                    ev = q.get_nowait()
                except Exception:
                    break
                if ev.token_id is not None and ttft[i] is None:
                    ttft[i] = (time.perf_counter() - t0) * 1e3
                if ev.done:
                    total += ev.completion_tokens
                    if ev.error:
                        errors.append(ev.error)
                    finished = True
                    break
            if not finished:
                nxt.append((i, q))
        pending = nxt
        if pending:
            time.sleep(0.001)
    wall = time.perf_counter() - t0
    return total, wall, sorted(t for t in ttft if t is not None), errors


def _bench_config(eng, tok, n_req, n_tok, runs=3):
    """Best-of-N decode throughput + p50/p95 TTFT for one engine.
    Raises if the wave errored (a zeroed number must not pass silently).
    """
    prompt_text = "benchmark " * 12
    # two warmup waves: the first compiles the cold-prompt prefill path,
    # the second compiles the prefix-reuse path (rem=1 bucket) that every
    # measured wave actually takes — so measured TTFT has no compiles
    for _ in range(2):
        _, _, _, errs = _run_wave(eng, tok, n_req, n_tok, prompt_text)
        if errs:
            raise RuntimeError(f"warmup wave errored: {errs[0][:200]}")
    best = 0.0
    ttfts = []
    for _ in range(runs):
        total, wall, tt, errs = _run_wave(eng, tok, n_req, n_tok,
                                          prompt_text)
        if errs:
            raise RuntimeError(f"measured wave errored: {errs[0][:200]}")
        best = max(best, total / wall)
        ttfts.extend(tt)
    ttfts.sort()
    p50 = ttfts[len(ttfts) // 2] if ttfts else 0.0
    p95 = ttfts[int(len(ttfts) * 0.95)] if ttfts else 0.0
    return round(best, 2), round(p50, 1), round(p95, 1)


def _prefix_cache_extra(eng) -> dict:
    """Cross-slot prefix cache effectiveness over the whole bench run:
    tokens reused (resident/copy/disk) vs tokens actually prefilled,
    copy dispatches, and the resulting hit rate."""
    m = eng.metrics
    reused, filled = m.prefix_reused_tokens, m.prefill_tokens
    return {
        "reused_tokens": reused,
        "prefilled_tokens": filled,
        "copies": m.prefix_copies,
        "hit_rate": round(reused / max(reused + filled, 1), 4),
        "enabled": eng._prefix_enabled,
    }


def _paged_kv_extra(eng) -> dict:
    """Paged KV pool effectiveness (extra.paged_kv): arena occupancy,
    zero-copy sharing, HBM-per-live-token, and the headline capacity
    ratio — how many slots this pool's HBM would hold under the dense
    worst-case-per-slot layout vs how many it actually serves. A
    ``slot_capacity_multiple`` of 2.0 means the same HBM budget seats
    2x the residents because pages track EXPECTED context."""
    if not getattr(eng, "_paged", False):
        return {"enabled": False}
    st = eng._pool.stats()
    c = eng.cache
    tok_bytes = 2 * c.k.dtype.itemsize * c.k.shape[0] * c.k.shape[-1]
    if c.quantized:
        tok_bytes += 2 * 4 * c.k.shape[0]
    live = sum(len(s.cache_tokens) for s in eng.slots)
    dense_equiv = (st.total * eng._page) // eng.max_seq
    return {
        "enabled": True,
        "page_tokens": eng._page,
        "pool_pages": st.total,
        "pages_in_use": st.in_use,
        "pages_shared": st.shared,
        "page_refs": st.refs,
        "alloc": dict(eng._pool.allocs),
        "live_tokens": live,
        "hbm_bytes_per_live_token": round(
            st.in_use * eng._page * tok_bytes / max(live, 1), 1),
        "n_slots": eng.n_slots,
        "slots_dense_equivalent": dense_equiv,
        "slot_capacity_multiple": round(
            eng.n_slots / max(dense_equiv, 1), 2),
    }


def _ragged_attn_extra(eng, mixed_itl_block, decode_tok_s) -> dict:
    """Ragged paged attention effectiveness (extra.ragged_attn): the
    serving engine's mode and warmup-precompiled jit-variant count next
    to the decode throughput and mixed ITL p95 measured on the SAME
    engine — the acceptance series for the one-kernel unification
    (variant count collapses; decode tok/s and mixed ITL must not
    regress)."""
    return {
        "enabled": bool(getattr(eng, "_paged", False)),
        "warmup_variants": int(getattr(eng, "warmup_variants", 0)),
        "decode_tok_s": decode_tok_s,
        "mixed_itl_p95_ms": (mixed_itl_block or {}).get("itl_p95_ms"),
    }


def ragged_variant_report() -> dict:
    """Standalone variant report on a tiny model (max_seq above the 256
    window floor): warmup wall time + compiled jit-variant count of the
    route the engine picks. Shared by tools/profile_http.py --mixed and
    tools/profile_kv.py so the variant count is observable without a
    full bench run."""
    import time as _time

    import jax as _jax
    import jax.numpy as _jnp

    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
    from localai_tfp_tpu.models.llm_spec import tiny_spec
    from localai_tfp_tpu.models.transformer import init_params

    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=1024)
    params = init_params(_jax.random.PRNGKey(0), spec,
                         dtype=_jnp.float32)
    eng = LLMEngine(spec, params, tk, n_slots=2, max_seq=1024,
                    prefill_buckets=(8,), decode_steps=2,
                    cache_dtype=_jnp.float32, autostart=False)
    t0 = _time.perf_counter()
    eng.warmup()
    out = {"attention_path": eng.attention_path,
           "variants": eng.warmup_variants,
           "warmup_s": round(_time.perf_counter() - t0, 2)}
    eng.close()
    return out


def meshed_paged_report() -> dict:
    """Pod-scale paged serving block on THIS process's visible devices:
    a dedicated tiny engine pair on a data x model mesh — sharded page
    arena + ragged dispatch shapes ON vs the dense meshed path OFF —
    reporting decode tok/s, warmup wall time + compiled variant count
    (the collapsed ladder must reach meshed engines too), and the mesh
    fan-out. Standalone so the TPU leg and a forced-host-device
    subprocess (CPU smoke) share one code path."""
    import os as _os
    import time as _time

    import jax as _jax
    import jax.numpy as _jnp

    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
    from localai_tfp_tpu.models.llm_spec import tiny_spec
    from localai_tfp_tpu.models.transformer import init_params
    from localai_tfp_tpu.parallel.mesh import make_mesh

    devs = _jax.devices()
    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512)
    n = len(devs)
    model_ax = next((m for m in (4, 2)
                     if n % m == 0 and spec.kv_dim % m == 0), 1)
    if model_ax == 1:
        return {"enabled": False,
                "reason": f"no tensor-parallel factor of kv_dim="
                          f"{spec.kv_dim} fits {n} device(s)"}
    # tp-heavy factoring: the 2-slot batch must divide the data axis
    data_ax = 2 if (n // model_ax) % 2 == 0 else 1
    mesh = make_mesh({"data": data_ax, "seq": 1, "model": model_ax},
                     devices=devs[:data_ax * model_ax])
    params = init_params(_jax.random.PRNGKey(0), spec,
                         dtype=_jnp.float32)
    out: dict = {"enabled": True, "platform": devs[0].platform,
                 "mesh_devices": data_ax * model_ax,
                 "mesh_data": data_ax, "mesh_model": model_ax}
    prev = _os.environ.get("LOCALAI_PAGED_KV")
    try:
        for paged in (True, False):
            _os.environ["LOCALAI_PAGED_KV"] = "on" if paged else "off"
            # max_seq above the 256 window floor so the dense meshed
            # ladder is real and the ragged variant collapse is visible
            eng = LLMEngine(spec, params, tk, n_slots=2, max_seq=1024,
                            prefill_buckets=(8, 32), decode_steps=4,
                            cache_dtype=_jnp.float32, mesh=mesh,
                            autostart=False)
            try:
                if eng._paged != paged:
                    return {"enabled": False,
                            "reason": "engine ignored LOCALAI_PAGED_KV="
                                      f"{'on' if paged else 'off'} on "
                                      "this mesh"}
                t0 = _time.perf_counter()
                eng.warmup()
                wall = round(_time.perf_counter() - t0, 2)
                eng.start()
                tok_s, _, _ = _bench_config(eng, tk, 4, 32, runs=1)
                if paged:
                    eng._pool.leak_check()
                out["paged_on" if paged else "paged_off"] = {
                    "decode_tok_s": tok_s,
                    "warmup_s": wall,
                    "warmup_variants": int(eng.warmup_variants),
                }
            finally:
                eng.close()
    finally:
        if prev is None:
            _os.environ.pop("LOCALAI_PAGED_KV", None)
        else:
            _os.environ["LOCALAI_PAGED_KV"] = prev
    return out


def _meshed_paged_extra() -> dict:
    """Pod-scale acceptance block (extra.meshed_paged): run
    meshed_paged_report in-process when this process already sees >=2
    devices (the TPU leg), else re-enter bench.py in a child with 8
    forced host devices — the backend here is initialized by the time
    extras run, so the device-count force cannot be applied in-process
    (same constraint __graft_entry__._pin_cpu documents)."""
    import jax as _jax

    if len(_jax.devices()) >= 2:
        out = meshed_paged_report()
        out["subprocess"] = False
        return out
    import json as _json
    import os as _os
    import subprocess as _sp
    import sys as _sys

    from __graft_entry__ import _force_host_devices

    env = dict(_os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = _force_host_devices(env.get("XLA_FLAGS", ""), 8)
    code = ("import json, bench; print('MESHED_PAGED ' "
            "+ json.dumps(bench.meshed_paged_report()))")
    try:
        proc = _sp.run(
            [_sys.executable, "-c", code], env=env,
            cwd=_os.path.dirname(_os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=900)
        for line in proc.stdout.splitlines():
            if line.startswith("MESHED_PAGED "):
                out = _json.loads(line[len("MESHED_PAGED "):])
                out["subprocess"] = True
                return out
        return {"enabled": False,
                "reason": f"subprocess leg gave no report (rc="
                          f"{proc.returncode}): {proc.stderr[-400:]}"}
    except Exception as e:  # noqa: BLE001 - bench must emit its line
        return {"enabled": False, "reason": f"subprocess leg died: {e}"}


def _kv_tiering_extra(eng, tok) -> dict:
    """KV tiering acceptance block (extra.kv_tiering): the live
    engine's decode throughput with the tier armed vs disarmed,
    interleaved best-of like _tracing_extra (contract: overhead <= 1%
    — the tick piggybacks on admission and every transfer is async),
    plus the live tier's counters. The capacity story — resident
    sessions vs HBM-only and the returning-user prefetch hit rate —
    runs the tools/profile_kv returning-users workload on a dedicated
    small engine pair, because the bench engine's pool is sized so its
    own traffic never churns slots (a vacuous multiple)."""
    out: dict = {"enabled": eng._tier is not None}
    if eng._tier is not None:
        tier = eng._tier
        tok_s_on = tok_s_off = 0.0
        for _ in range(2):
            on, _, _ = _bench_config(eng, tok, 4, 32, runs=1)
            eng._tier = None  # disarm: every engine hook is a None test
            try:
                off, _, _ = _bench_config(eng, tok, 4, 32, runs=1)
            finally:
                eng._tier = tier
            tok_s_on = max(tok_s_on, on)
            tok_s_off = max(tok_s_off, off)
        overhead = max(0.0, 1.0 - tok_s_on / max(tok_s_off, 1e-9))
        out.update({
            "decode_tok_s_tier_on": tok_s_on,
            "decode_tok_s_tier_off": tok_s_off,
            "tier_overhead_frac": round(overhead, 4),
            "tier_overhead_within_1pct": overhead <= 0.01,
            "host_budget_mb": tier.host_budget >> 20,
            "live_stats": tier.stats(),
        })
    from tools.profile_kv import returning_users_shape

    # 16 users on the 4-slot small engine: enough churn depth for the
    # >=4x resident-capacity headline (8 would cap the multiple at 2x)
    ru = returning_users_shape(True, 16)
    out["capacity_multiple"] = ru["capacity_multiple"]
    out["prefetch_hit_rate"] = ru["on"]["prefetch_hit_rate"]
    out["reprefill_tokens_on_hits"] = \
        ru["on"]["reprefill_tokens_on_hits"]
    out["returning_users"] = ru
    return out


def _disagg_extra() -> dict:
    """Disaggregated-serving acceptance block (extra.disagg): the
    tools/profile_disagg contrast on a dedicated engine pair — decode
    ITL p99 and the max inter-token gap with long prompts flooding the
    same engine vs split across the migration relay (both must be
    STRICTLY better with disagg on), migration wall p50/p95, the
    zero-re-prefill cross-check, and the seeded byte-identity leg.
    Runs on a dedicated pair for the same reason as the tiering
    capacity story: the live bench engine is not disaggregated."""
    from tools.profile_disagg import disagg_contrast

    r = disagg_contrast(True)
    return {
        "ok": r["ok"],
        "itl_p99_ms_off": r["off"]["itl_p99_ms"],
        "itl_p99_ms_on": r["on"]["itl_p99_ms"],
        "max_gap_ms_off": r["off"]["max_gap_ms"],
        "max_gap_ms_on": r["on"]["max_gap_ms"],
        "itl_p99_improved": r["itl_p99_improved"],
        "max_gap_improved": r["max_gap_improved"],
        "migration_ms": r["on"]["migration_ms"],
        "zero_reprefill": r["zero_reprefill"],
        "seeded_identity": r["identity"]["identical"],
        "contrast": r,
    }


def _weight_paging_extra() -> dict:
    """Gallery weight-paging acceptance block (extra.weight_paging):
    the profile_coldstart --gallery round-robin on DEDICATED small
    engines (N models under an HBM weight budget sized for ~2) plus
    the profile_chaos gallery leg. Headlines: a warm model's first
    token must beat a cold build by >= 5x, the HBM high-water mark
    must respect the budget, and both injected weight faults must
    leave the request served and the pager leak-clean. Dedicated
    engines keep this out of the _LIVE_ENGINE_EXTRAS ordering guard."""
    import os

    import jax
    import jax.numpy as jnp

    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
    from localai_tfp_tpu.models.llm_spec import tiny_spec
    from localai_tfp_tpu.models.transformer import init_params
    from tools.profile_chaos import gallery_leg
    from tools.profile_coldstart import gallery_shape

    g = gallery_shape(n_models=4, rounds=3)
    c = gallery_leg()
    speedup = g["warm_vs_cold_speedup"] or 0.0

    # all-hot steady-state overhead: the pager's scheduler hooks are a
    # lock-check per admission pass — interleaved best-of on a
    # dedicated engine pair must stay within 1%
    tok = ByteTokenizer()
    spec = tiny_spec(vocab_size=tok.vocab_size, max_position=512)
    params = init_params(jax.random.PRNGKey(0), spec,
                         dtype=jnp.float32)
    saved = os.environ.get("LOCALAI_WEIGHT_PAGING")
    tok_s_on = tok_s_off = 0.0
    try:
        os.environ["LOCALAI_WEIGHT_PAGING"] = "on"
        e_on = LLMEngine(spec, params, tok, n_slots=4, max_seq=256,
                         prefill_buckets=(8, 32, 128))
        os.environ["LOCALAI_WEIGHT_PAGING"] = "off"
        e_off = LLMEngine(spec, params, tok, n_slots=4, max_seq=256,
                          prefill_buckets=(8, 32, 128))
        try:
            for _ in range(2):
                on, _, _ = _bench_config(e_on, tok, 4, 32, runs=1)
                off, _, _ = _bench_config(e_off, tok, 4, 32, runs=1)
                tok_s_on = max(tok_s_on, on)
                tok_s_off = max(tok_s_off, off)
        finally:
            e_on.close()
            e_off.close()
    finally:
        if saved is None:
            os.environ.pop("LOCALAI_WEIGHT_PAGING", None)
        else:
            os.environ["LOCALAI_WEIGHT_PAGING"] = saved
    overhead = max(0.0, 1.0 - tok_s_on / max(tok_s_off, 1e-9))
    return {
        "ok": (speedup >= 5.0
               and overhead <= 0.01
               and g["hbm_high_water_mb"] <= g["hbm_budget_mb"] * 1.25
               and c["demote_fault"]["served"]
               and c["fetch_fault"]["served"]
               and c["fetch_fault"]["one_terminal"]
               and c["pager_leak_check"] == "clean"),
        "warm_vs_cold_speedup": speedup,
        "decode_tok_s_paging_on": tok_s_on,
        "decode_tok_s_paging_off": tok_s_off,
        "paging_overhead_frac": round(overhead, 4),
        "paging_overhead_within_1pct": overhead <= 0.01,
        "warm_first_token_ms": round(
            g["warm_first_token_s"]["p50"] * 1e3, 1),
        "cold_first_token_ms": round(
            g["cold_first_token_s"]["p50"] * 1e3, 1),
        "hbm_high_water_mb": g["hbm_high_water_mb"],
        "hbm_budget_mb": g["hbm_budget_mb"],
        "lru_thrash_demotes": g["lru_thrash_demotes"],
        "gallery": g,
        "chaos": c,
    }


# extras that measure the LIVE serving engine: _bench_http's teardown
# (runner.cleanup()) fires the app cleanup that CLOSES it, so these must
# be recorded first. _bench_http enforces the order (it was a
# comment-only gotcha through PR 4; measuring a closed engine reports
# garbage silently).
_LIVE_ENGINE_EXTRAS = ("mixed_itl", "paged_kv", "ragged_attn",
                       "kv_tiering", "disagg")


def _mixed_itl_extra(eng, tok, n_tok=96) -> dict:
    """ITL under admission pressure (extra.mixed_itl): sustain decode
    streams on half the slots, inject an admission burst mid-stream,
    and report the live streams' inter-event gaps — p50/p95 and the
    max gap any stream saw — plus burst TTFT. The series the bench
    tracks for the stall-free mixed dispatcher (an admission wave must
    not spike active streams' ITL to the prefill round trip). Must run
    while the engine is LIVE (before _bench_http, whose teardown fires
    the app cleanup that closes the serving engine)."""
    import queue as _queue

    from localai_tfp_tpu.engine.engine import GenRequest

    n_streams = max(1, eng.n_slots // 2)
    burst_size = max(1, eng.n_slots - n_streams)
    bp = "burst " * max(1, min(eng.max_seq // 2, 512) // 6)
    # untimed warm pass: compile the mixed variant (engines without a
    # full warmup() jit it on first mixed dispatch — seconds that would
    # otherwise land in the measured gaps)
    wq = eng.submit_many([GenRequest(
        prompt_ids=tok.encode("warm stream"), max_tokens=24,
        temperature=0.0, ignore_eos=True)])[0]
    ev = wq.get(timeout=300)
    assert not ev.done, ev.error
    wb = eng.submit_many([GenRequest(
        prompt_ids=tok.encode(bp + "w"), max_tokens=4,
        temperature=0.0, ignore_eos=True)])[0]
    for q in (wb, wq):
        while not q.get(timeout=300).done:
            pass
    qs = eng.submit_many([
        GenRequest(prompt_ids=tok.encode(f"sustained stream {i:02d}"),
                   max_tokens=n_tok, temperature=0.0, ignore_eos=True)
        for i in range(n_streams)])
    times: list[list[float]] = [[] for _ in range(n_streams)]
    done = [False] * n_streams
    for i, q in enumerate(qs):  # all streams live before the burst
        ev = q.get(timeout=120)
        assert not ev.done, ev.error
        times[i].append(time.perf_counter())
    t0 = time.perf_counter()
    bqs = eng.submit_many([
        GenRequest(prompt_ids=tok.encode(bp + f"{j:02d}"), max_tokens=8,
                   temperature=0.0, ignore_eos=True)
        for j in range(burst_size)])
    burst_ttft: list[float] = [None] * burst_size
    burst_done = [False] * burst_size
    while not (all(done) and all(burst_done)):
        idle = True
        for i, q in enumerate(qs):
            if done[i]:
                continue
            try:
                ev = q.get_nowait()
            except _queue.Empty:
                continue
            idle = False
            if ev.done:
                done[i] = True
            elif ev.token_id is not None:
                times[i].append(time.perf_counter())
        for j, q in enumerate(bqs):
            if burst_done[j]:
                continue
            try:
                ev = q.get_nowait()
            except _queue.Empty:
                continue
            idle = False
            if ev.done:
                burst_done[j] = True
            elif ev.token_id is not None and burst_ttft[j] is None:
                burst_ttft[j] = (time.perf_counter() - t0) * 1e3
        if idle:
            time.sleep(0.001)
    gaps: list[float] = []
    max_gaps: list[float] = []
    for ts in times:
        g = [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
        if g:
            gaps += g
            max_gaps.append(max(g))
    gaps.sort()
    tt = sorted(t for t in burst_ttft if t is not None)
    return {
        "streams": n_streams,
        "burst_size": burst_size,
        "itl_p50_ms": round(gaps[len(gaps) // 2], 1) if gaps else None,
        "itl_p95_ms": round(gaps[int(len(gaps) * 0.95)], 1)
        if gaps else None,
        "max_gap_ms": round(max(max_gaps), 1) if max_gaps else None,
        "burst_ttft_p50_ms": round(tt[len(tt) // 2], 1) if tt else None,
    }


def _chaos_extra() -> dict:
    """Serving-survival acceptance block (extra.chaos): bounded-admission
    shed rate + Retry-After hint, both deadline stages, a deterministic
    device-step fault storm (terminal-event completeness + KV-pool leak
    check), and the federation breaker's failover latency under active
    probing. Runs on its OWN tiny engine and a localhost balancer pair,
    so it is independent of the serving engine's lifecycle (not subject
    to the _LIVE_ENGINE_EXTRAS ordering guard)."""
    import asyncio as _asyncio

    from tools.profile_chaos import engine_leg, federation_leg

    out = engine_leg(flood=12)
    out["federation"] = _asyncio.run(federation_leg(0.1))
    return out


def _fleet_extra() -> dict:
    """Fleet-telemetry acceptance block (extra.fleet): the
    profile_fleet smoke — N real member subprocesses behind an
    in-process balancer. Tracks the digest-plane contracts: fleet p95
    TTFT from merged digests within one histogram bucket of
    client-measured, digest payloads under the byte cap and fresh at
    probe cadence, and the SLO burn-rate monitor flipping within two
    probe intervals of a member kill while /fleet/metrics keeps
    serving. Runs member subprocesses, so it is independent of the
    serving engine's lifecycle."""
    import asyncio as _asyncio

    from tools.profile_fleet import fleet_leg

    return _asyncio.run(fleet_leg(n_members=3, probe_s=0.5,
                                  n_requests=12))


def _fleet_routing_extra() -> dict:
    """Routing + autoscaling acceptance block (extra.fleet_routing):
    the profile_fleet --routing / --autoscale smokes. Tracks the
    prefix-locality contracts — cross-replica prefix hit rate > 0.5
    and repeat-request TTFT p50 beating blind least-used in the same
    run — and the elastic-scaling contracts: a queue burst boots a
    warmup-reuse replica within ~2 probe intervals, and the idle
    scale-down drains the victim (zero in-flight) before the kill.
    Runs member subprocesses, so it is independent of the serving
    engine's lifecycle."""
    import asyncio as _asyncio

    from tools.profile_fleet import autoscale_leg, routing_leg

    return {
        "routing": _asyncio.run(routing_leg(
            n_members=3, probe_s=0.5, repeats=4)),
        "autoscale": _asyncio.run(autoscale_leg()),
    }


def _tracing_extra() -> dict:
    """Observability-cost acceptance block (extra.tracing): span/trace
    volume on this process, flight-recorder ring occupancy, and the
    recorder's decode overhead — the same wave measured with the
    timeline ring on then off (contract: tok/s delta <= 1%). Runs on
    its OWN tiny engine, like _chaos_extra, so it is independent of
    the serving engine's lifecycle."""
    from localai_tfp_tpu.telemetry.flightrec import FLIGHT
    from localai_tfp_tpu.telemetry.tracing import TRACER
    from tools.profile_chaos import _build_engine

    eng, tk = _build_engine()
    try:
        was_enabled = FLIGHT.enabled
        try:
            # alternate recorder-on/off waves and keep best-of per arm:
            # interleaving cancels the slow drift (thermal, page cache,
            # sibling load) that a sequential A-then-B compare on a CPU
            # smoke would misread as recorder cost
            tok_s_on = tok_s_off = 0.0
            for _ in range(3):
                FLIGHT.enabled = True
                on, _, _ = _bench_config(eng, tk, 4, 32, runs=1)
                FLIGHT.enabled = False
                off, _, _ = _bench_config(eng, tk, 4, 32, runs=1)
                tok_s_on = max(tok_s_on, on)
                tok_s_off = max(tok_s_off, off)
        finally:
            FLIGHT.enabled = was_enabled
    finally:
        eng.close()
    # best-of-N on both sides; clamp at 0 so run-to-run jitter cannot
    # report a nonsensical negative recorder cost
    overhead = max(0.0, 1.0 - tok_s_on / max(tok_s_off, 1e-9))
    rows = TRACER.traces(limit=10_000)
    return {
        "traces_recorded": len(rows),
        "spans_recorded": sum(len(t.get("spans") or ()) for t in rows),
        "span_events_recorded": sum(
            len(t.get("span_events") or ()) for t in rows),
        "ring_occupancy": FLIGHT.occupancy(),
        "ring_capacity": FLIGHT.capacity,
        "ring_recorded_total": FLIGHT.total_recorded(),
        "ring_dropped": FLIGHT.dropped(),
        "decode_tok_s_recorder_on": tok_s_on,
        "decode_tok_s_recorder_off": tok_s_off,
        "recorder_overhead_frac": round(overhead, 4),
        "recorder_overhead_within_1pct": overhead <= 0.01,
    }


def _costmodel_extra() -> dict:
    """Device-observability acceptance block (extra.costmodel): MFU and
    bytes/decode-token from the warmup-captured cost model, HBM-ledger
    attribution + drift, and the accounting overhead — the same wave
    measured with the cost model + ledger on then off (contract: tok/s
    delta <= 1%). Runs on its OWN tiny engine, like _tracing_extra, so
    it is independent of the serving engine's lifecycle."""
    from tools.profile_chaos import _build_engine

    eng, tk = _build_engine()
    try:
        # the capture pass: every dispatch variant's XLA cost row lands
        # in the table here (accounting is a dict lookup afterwards)
        eng.warmup()
        cm, ledger = eng._costmodel, eng._ledger
        tok_s_on = tok_s_off = 0.0
        for _ in range(3):
            # alternate accounting-on/off waves, best-of per arm — the
            # same interleaving rationale as the recorder overhead block
            eng._costmodel, eng._ledger = cm, ledger
            on, _, _ = _bench_config(eng, tk, 4, 32, runs=1)
            eng._costmodel, eng._ledger = None, None
            off, _, _ = _bench_config(eng, tk, 4, 32, runs=1)
            tok_s_on = max(tok_s_on, on)
            tok_s_off = max(tok_s_off, off)
        eng._costmodel, eng._ledger = cm, ledger

        # bytes per decode token: decode-kind byte delta across one
        # accounted config run (2 warmup + 1 measured wave of 4x32)
        def _decode_bytes():
            if cm is None:
                return 0.0
            return sum(v[1] for k, v in cm._totals.items()
                       if k.startswith("decode"))

        b0 = _decode_bytes()
        _bench_config(eng, tk, 4, 32, runs=1)
        tokens = 3 * 4 * 32
        bytes_per_tok = (_decode_bytes() - b0) / tokens
        stats = eng.cost_stats()
        drift_ratio = None
        if ledger is not None:
            drift_ratio = ledger.reconcile().get("drift_ratio")
        hbm = eng.hbm_stats()
    finally:
        eng.close()
    overhead = max(0.0, 1.0 - tok_s_on / max(tok_s_off, 1e-9))
    return {
        "mfu_ewma": stats["mfu_ewma"] if stats else None,
        "mfu_samples": stats["mfu_samples"] if stats else 0,
        "variants_captured": stats["variants_captured"] if stats else 0,
        "decode_bytes_per_token": round(bytes_per_tok, 1),
        "ledger_attributed_bytes": (hbm or {}).get("attributed"),
        # None on CPU (no memory_stats); the contract is <=5% on device
        "ledger_drift_ratio": drift_ratio,
        "ledger_within_5pct": (None if drift_ratio is None
                               else abs(drift_ratio) <= 0.05),
        "decode_tok_s_costmodel_on": tok_s_on,
        "decode_tok_s_costmodel_off": tok_s_off,
        "costmodel_overhead_frac": round(overhead, 4),
        "costmodel_overhead_within_1pct": overhead <= 0.01,
    }


def _cost_sched_extra() -> dict:
    """Cost-model-driven-scheduling acceptance block (extra.cost_sched):
    tools/profile_roofline.py's --mixed long-prompt flood at CPU smoke
    size — ITL p99 + max inter-token gap with ms-budget scheduling
    (LOCALAI_COST_SCHED=on + explicit LOCALAI_ITL_BUDGET_MS) vs the
    token-budget baseline, plus the predicted-vs-measured device-time
    geomean after EWMA warmup. Builds its own engines (one per leg),
    so it is independent of the serving engine's lifecycle."""
    from tools.profile_roofline import run_mixed

    return run_mixed(smoke=True)


def _lint_extra():
    """graftlint trajectory per release: rule count, findings, baseline
    size, interprocedural call-graph size, and graftsan (runtime
    sanitizer) micro-costs — armed vs disarmed per lock round-trip and
    per guarded attribute rebind. New findings here mean tier-1
    (tests/test_lint.py) is already red; the bench records the numbers
    so the baseline's shrink-over-releases is visible in BENCH."""
    import threading

    from tools.lint import ALL_RULES, lint_repo
    from tools.lint import sanitizer as san
    from tools.lint.core import callgraph_edges, load_context

    findings, res = lint_repo()
    edges = callgraph_edges(load_context())

    def _time_ns(fn, n=2000):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n * 1e9

    from localai_tfp_tpu.telemetry.registry import Counter

    san.reset()
    san.arm(include=lambda f: True)
    lock = threading.Lock()  # wrapped: feeds the lock-order graph
    child = Counter("bench_graftsan_probe_total",
                    "graftsan bench probe").labels()

    def _locked():
        with lock:
            pass

    def _guarded_inc():
        with child._lock:
            child.value += 1.0

    armed_lock_ns = _time_ns(_locked)
    armed_set_ns = _time_ns(_guarded_inc)
    graph = san.stats()
    san.disarm()
    raw = threading.Lock()

    def _raw_locked():
        with raw:
            pass

    disarmed_lock_ns = _time_ns(_raw_locked)
    disarmed_set_ns = _time_ns(_guarded_inc)
    san.reset()

    return {
        "rules": len(ALL_RULES),
        "findings": len(findings),
        "new": len(res.new),
        "grandfathered": len(res.grandfathered),
        "stale_baseline": len(res.stale),
        "clean": res.ok,
        "callgraph_edges": edges,
        "san": {
            "lock_sites": graph["sites"],
            "lock_edges": graph["edges"],
            "guarded_classes": graph["guarded_classes"],
            "cycles": graph["cycles"],
            "violations": graph["violations"],
            "lock_ns_armed": round(armed_lock_ns, 1),
            "lock_ns_disarmed": round(disarmed_lock_ns, 1),
            "guarded_set_ns_armed": round(armed_set_ns, 1),
            "guarded_set_ns_disarmed": round(disarmed_set_ns, 1),
        },
    }


def _bench_http(state, model, n_req, n_tok, runs=2, extra=None):
    """Endpoint-level benchmark: boot the REAL aiohttp server (routes,
    middleware, SSE writer) over the given Application (whose loader
    already serves ``model``) and drive ``n_req`` concurrent streaming
    /v1/chat/completions clients through localhost TCP. Returns (decode
    tok/s, ttft p50 ms, ttft p95 ms, steady p50 ms) as a stock OpenAI
    client would observe them (BASELINE.md: the north star is measured
    "via stock /v1/chat/completions").

    Pass the bench's ``extra`` dict so the live-engine ordering guard
    can verify every _LIVE_ENGINE_EXTRAS block was measured BEFORE this
    call — teardown closes the serving engine, so anything measured
    after it reads a dead engine."""
    if extra is not None:
        missing = [k for k in _LIVE_ENGINE_EXTRAS if k not in extra]
        if missing:
            raise RuntimeError(
                f"bench ordering violated: extra[{missing!r}] must be "
                "measured before _bench_http — its teardown "
                "(runner.cleanup()) fires the app cleanup that closes "
                "the serving engine, so live-engine extras measured "
                "after this point would silently read a dead engine")
    import asyncio
    import json as _json

    from aiohttp import ClientSession, ClientTimeout, TCPConnector, web

    from localai_tfp_tpu.server.app import build_app

    app = build_app(state)
    out = {}

    # LOCALAI_BENCH_TRACE=1: per-run TTFT + engine dispatch timeline to
    # stderr — the in-context profiler for when the stock numbers and
    # tools/profile_http.py disagree (they construct subtly different
    # engines: this one has the engine leg's warm KV prefixes)
    import os

    trace = os.environ.get("LOCALAI_BENCH_TRACE", "") not in ("", "0")
    eng_t = state.model_loader.get(model).backend.engine if trace else None
    tlog: list = []
    if trace:
        _orig_run = eng_t._run

        def _traced(kind, payload):
            t = time.perf_counter()
            sh = (list(payload["toks"].shape)
                  if kind == "mixed" else payload.get("k"))
            tlog.append((kind, sh, t))
            return _orig_run(kind, payload)

        eng_t._run = _traced
        _orig_pf = eng_t._complete_mixed
        _orig_dk = eng_t._complete_decodek

        def _tpf(fl):
            t = time.perf_counter()
            r = _orig_pf(fl)
            tlog.append(("harvest_pf",
                         round((time.perf_counter() - t) * 1e3, 1), t))
            return r

        def _tdk(fl):
            t = time.perf_counter()
            r = _orig_dk(fl)
            tlog.append(("harvest_dk",
                         round((time.perf_counter() - t) * 1e3, 1), t))
            return r

        eng_t._complete_mixed = _tpf
        eng_t._complete_decodek = _tdk

    def _trace_dump(label, t0, tts):
        if not trace:
            return
        import sys as _sys

        tt = sorted(t for t in tts if t is not None)
        line = {
            "run": label,
            "ttft_p50": round(tt[len(tt) // 2], 1) if tt else None,
            "ttft_p95": (round(tt[int(len(tt) * 0.95)], 1)
                         if tt else None),
            "dispatches": [
                (k, sh, round((at - t0) * 1e3, 1))
                for k, sh, at in tlog if at >= t0][:24],
        }
        print(f"TRACE {json.dumps(line)}", file=_sys.stderr, flush=True)
        tlog.clear()

    async def drive():
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}/v1/chat/completions"
        async with ClientSession(
            connector=TCPConnector(limit=0),
            # generous: a warmup wave may sit behind a cold jit of a
            # prefill variant (minutes at 8B through the AOT path); the
            # persistent compile cache makes later runs immune
            timeout=ClientTimeout(total=3600),
        ) as sess:

            async def one(i, t0, ttfts):
                body = {
                    "model": model,
                    # the chat template adds a handful of tokens
                    # ("user: ", "\nassistant:", BOS); 10 reps keeps the
                    # templated prompt inside the SAME 128-token prefill
                    # bucket as the engine leg, so the legs share
                    # compiled variants
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
                    # lean SSE client: the bench client shares ONE host
                    # CPU with the server it measures, and a full
                    # json.loads of every chunk across 64 concurrent
                    # streams showed up IN the measured TTFT (the
                    # server's first-token write sat behind client
                    # parse callbacks on the loop). Parse only the two
                    # chunks that matter: first content (byte sniff)
                    # and the finaljson with usage. A real client runs
                    # on its own machine.
                    async for line in r.content:
                        if not line.startswith(b"data: "):
                            continue
                        if line.strip() == b"data: [DONE]":
                            break
                        if (ttfts[i] is None
                                and b'"content": "' in line
                                and b'"content": ""' not in line):
                            ttfts[i] = (time.perf_counter() - t0) * 1e3
                        if b'"usage"' in line:
                            d = _json.loads(line[6:])
                            u = d.get("usage") or {}
                            total = u.get("completion_tokens", total)
                return total

            best, tt_all = 0.0, []
            for run in range(runs + 2):  # 2 warmups: HTTP arrival
                # raggedness admits in VARYING group sizes, so the first
                # wave does not compile every (group, window) variant the
                # measured waves will hit — one extra wave covers them
                ttfts = [None] * n_req
                t0 = time.perf_counter()
                totals = await asyncio.gather(
                    *[one(i, t0, ttfts) for i in range(n_req)])
                wall = time.perf_counter() - t0
                _trace_dump(f"wave{run}", t0, ttfts)
                if run < 2:
                    continue
                best = max(best, sum(totals) / wall)
                got = [t for t in ttfts if t is not None]
                if not got:
                    # the TTFT byte-sniff above is coupled to the
                    # server's json.dumps separators — if that drifts,
                    # fail the bench loudly instead of reporting None
                    raise RuntimeError(
                        "no stream produced a first-content TTFT — "
                        "SSE sniff out of sync with the server format?")
                tt_all.extend(got)

            # steady-state TTFT: one new request arriving while the
            # engine is BUSY serving a near-full wave — the classic
            # serving-TTFT methodology (arrival at service rate), vs the
            # cold 64-deep burst above where p50 necessarily includes
            # half the wave's own admission
            steady: list[float] = []

            async def stagger():
                for j in range(8):
                    await asyncio.sleep(0.35)
                    tt = [None]
                    t1 = time.perf_counter()
                    await one(0, t1, tt)
                    _trace_dump(f"steady{j}", t1, tt)
                    if tt[0] is not None:
                        steady.append(tt[0])

            bg_tt = [None] * (n_req - 1)
            t0 = time.perf_counter()
            await asyncio.gather(
                *[one(i, t0, bg_tt) for i in range(n_req - 1)],
                stagger())
        await runner.cleanup()
        tt_all.sort()
        steady.sort()
        out["tok_s"] = round(best, 2)
        out["p50"] = round(tt_all[len(tt_all) // 2], 1) if tt_all else 0.0
        out["p95"] = (round(tt_all[int(len(tt_all) * 0.95)], 1)
                      if tt_all else 0.0)
        out["p50_steady"] = (round(steady[len(steady) // 2], 1)
                             if steady else 0.0)

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(drive())
    finally:
        loop.close()
        if trace:
            eng_t._run = _orig_run
            eng_t._complete_mixed = _orig_pf
            eng_t._complete_decodek = _orig_dk
    return out["tok_s"], out["p50"], out["p95"], out["p50_steady"]


def _build_bpe_tokenizer(dirpath: str, vocab_size: int = 128256) -> None:
    """A REAL byte-level BPE tokenizer covering every id in the model
    vocab, built programmatically (zero egress): 256 byte symbols plus
    ~128k generated merges. Encoding runs the genuine greedy BPE merge
    loop over the rank table and any sampled id decodes to visible
    text — so client-side TTFT includes real tokenize/detokenize work
    (VERDICT r4 weak #4: the synthetic ASCII tokenizer excluded it)."""
    import json
    import os

    from tokenizers import Tokenizer, decoders, pre_tokenizers
    from tokenizers.models import BPE

    alphabet = sorted(pre_tokenizers.ByteLevel.alphabet())
    vocab = {tok: i for i, tok in enumerate(alphabet)}
    # merges only over symbols that DECODE to printable ASCII (the
    # GPT-2 byte map sends 0x21-0x7E to themselves and space to 'Ġ'),
    # so any merged token is valid standalone UTF-8: a random sampled
    # id must stream as visible text IMMEDIATELY, not sit in the
    # incremental UTF-8 decoder awaiting continuation bytes. Random
    # ids over the full byte alphabet were withheld often enough to
    # slide measured first-content from the prefill harvest to the
    # NEXT decode harvest (~+230 ms of pure tokenizer artifact on
    # steady TTFT; same failure the 1B leg's WideByteTok docstring
    # records). The 256 raw-byte symbols stay in the vocab for
    # encoding coverage — they are 0.2% of sampled ids.
    printable = [c for c in alphabet
                 if (len(c) == 1 and 0x21 <= ord(c) <= 0x7E)] + ["Ġ"]
    merges = []
    target = vocab_size - 2  # two specials appended below
    lvl = list(printable)
    while len(vocab) < target:
        nxt = []
        for a in lvl:
            if len(vocab) >= target:
                break
            for b in printable:
                if len(vocab) >= target:
                    break
                m = a + b
                if m in vocab:
                    continue
                vocab[m] = len(vocab)
                merges.append((a, b))
                nxt.append(m)
        lvl = nxt
    tk = Tokenizer(BPE(vocab=vocab, merges=merges))
    tk.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tk.decoder = decoders.ByteLevel()
    tk.add_special_tokens(["<|begin_of_text|>", "<|end_of_text|>"])
    os.makedirs(dirpath, exist_ok=True)
    tk.save(os.path.join(dirpath, "tokenizer.json"))
    with open(os.path.join(dirpath, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "bos_token": "<|begin_of_text|>",
                   "eos_token": "<|end_of_text|>"}, f)


def _write_hf_checkpoint(dirpath: str, spec) -> None:
    """Write a REAL-format Llama HF checkpoint (config.json +
    model.safetensors, torch [out, in] layout, bf16) with synthetic
    weights, so the 8B leg flows through the actual loader: safetensors
    read -> llama key mapping -> int8 quantization -> engine + warmup
    (VERDICT r4 weak #4: nothing previously proved the 8B bench config
    is reachable from a disk checkpoint)."""
    import json
    import math
    import os

    import ml_dtypes
    import numpy as np

    rng = np.random.default_rng(0)
    D, F, V, L = spec.d_model, spec.d_ff, spec.vocab_size, spec.n_layers
    q_dim, kv_dim = spec.q_dim, spec.kv_dim

    def w(out_d, in_d):
        q = rng.integers(-127, 128, (out_d, in_d), np.int8)
        scale = np.float32(1.0 / (127.0 * math.sqrt(in_d)))
        return (q.astype(np.float32) * scale).astype(ml_dtypes.bfloat16)

    t = {
        "model.embed_tokens.weight": w(V, D),
        "model.norm.weight": np.ones((D,), ml_dtypes.bfloat16),
        "lm_head.weight": w(V, D),
    }
    for i in range(L):
        lp = f"model.layers.{i}."
        t[lp + "self_attn.q_proj.weight"] = w(q_dim, D)
        t[lp + "self_attn.k_proj.weight"] = w(kv_dim, D)
        t[lp + "self_attn.v_proj.weight"] = w(kv_dim, D)
        t[lp + "self_attn.o_proj.weight"] = w(D, q_dim)
        t[lp + "mlp.gate_proj.weight"] = w(F, D)
        t[lp + "mlp.up_proj.weight"] = w(F, D)
        t[lp + "mlp.down_proj.weight"] = w(D, F)
        t[lp + "input_layernorm.weight"] = np.ones((D,),
                                                   ml_dtypes.bfloat16)
        t[lp + "post_attention_layernorm.weight"] = np.ones(
            (D,), ml_dtypes.bfloat16)
    from safetensors.numpy import save_file

    os.makedirs(dirpath, exist_ok=True)
    save_file(t, os.path.join(dirpath, "model.safetensors"))
    with open(os.path.join(dirpath, "config.json"), "w") as f:
        json.dump({
            "architectures": ["LlamaForCausalLM"],
            "model_type": "llama",
            "hidden_size": D, "intermediate_size": F,
            "num_attention_heads": spec.n_heads,
            "num_key_value_heads": spec.n_kv_heads,
            "num_hidden_layers": L, "vocab_size": V,
            "head_dim": spec.d_head,
            "rope_theta": spec.rope_theta,
            "max_position_embeddings": spec.max_position,
            "rms_norm_eps": 1e-5, "torch_dtype": "bfloat16",
            "bos_token_id": V - 2, "eos_token_id": V - 1,
        }, f)
    _build_bpe_tokenizer(dirpath, V)


def _fast_int8_params(spec):
    """Random int8 weight-only params, generated with numpy (jax.random
    threefry on host CPU takes ~20 min at 8B scale; numpy does it in
    seconds). The bench's own 8B leg now loads REAL-format disk
    checkpoints (_write_hf_checkpoint) — this helper remains for the
    engine microbenches (tools/profile_r5.py, tools/microbench_step.py),
    which want params without the disk round trip."""
    import math

    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from localai_tfp_tpu.models.quant import QTensor

    rng = np.random.default_rng(0)
    L, D, F, V = (spec.n_layers, spec.d_model, spec.d_ff,
                  spec.vocab_size)

    def qt(*shape):
        q = rng.integers(-127, 128, shape, np.int8)
        scale = np.full(shape[:-2] + (shape[-1],),
                        1.0 / (127.0 * math.sqrt(shape[-2])), np.float32)
        return QTensor(q=jnp.asarray(q), scale=jnp.asarray(scale))

    def dense(*shape, scale=0.02):
        a = (rng.standard_normal(shape, np.float32) * scale)
        return jnp.asarray(a.astype(ml_dtypes.bfloat16))

    def qembed(v, d):  # per-row-scale int8 table (quant.quantize_embed)
        q = rng.integers(-127, 128, (v, d), np.int8)
        scale = np.full((v,), 0.02 / 127.0, np.float32)
        return QTensor(q=jnp.asarray(q), scale=jnp.asarray(scale))

    ones = lambda *s: jnp.ones(s, jnp.bfloat16)  # noqa: E731
    return {
        # int8 embed/lm_head (quant.quantize_params embeddings=True):
        # ~2 GB of HBM back vs bf16 — the room that buys batch 64
        "embed": qembed(V, D),
        "lm_head": qt(D, V),
        "wq": qt(L, D, spec.q_dim),
        "wk": qt(L, D, spec.kv_dim),
        "wv": qt(L, D, spec.kv_dim),
        "wo": qt(L, spec.q_dim, D),
        "w_gate": qt(L, D, F),
        "w_up": qt(L, D, F),
        "w_down": qt(L, F, D),
        "ln1_w": ones(L, D),
        "ln2_w": ones(L, D),
        "final_norm_w": ones(D),
    }


def main() -> None:
    import jax
    import jax.numpy as jnp

    # persistent compile cache (JAX_COMPILATION_CACHE_DIR places it):
    # cached, the serving executables load in seconds, so repeat bench
    # runs measure serving, not the compiler
    from localai_tfp_tpu.utils import compile_cache

    compile_cache.configure()

    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
    from localai_tfp_tpu.models.llm_spec import LLMSpec
    from localai_tfp_tpu.models.transformer import init_params

    class WideByteTok(ByteTokenizer):
        """ByteTokenizer whose decode maps ANY id to a PRINTABLE ASCII
        char. Random-weight models over a 128k vocab virtually never
        sample ids < 256, so with the plain ByteTokenizer no text would
        ever stream through the endpoint and client-side TTFT could not
        be measured. Printable ASCII (not id % 256 raw bytes) matters
        for honesty the other way: random high bytes look like UTF-8
        lead bytes, the stream decoder withholds them awaiting
        continuations, and half the streams' first visible content
        slips to the NEXT k-step scan burst — measured +1.3s of
        client TTFT that says nothing about the serving engine. A real
        tokenizer emits visible text on virtually every token."""

        def decode(self, ids):
            return "".join(
                chr(32 + (i % 95)) for i in ids
                if i not in (self.bos_id, *self.eos_ids)
            )

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a CPU run proves correctness and counts, never speed: every
        # number below is a device metric, so no chip means no benchmark
        raise SystemExit(
            f"bench.py measures the chip, and JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}). Run it through the "
            "chip tool; CPU verification lives in tests/ and "
            "chip_smoke.py refuses likewise.")
    tok = WideByteTok()
    extra: dict = {}

    # telemetry registry delta across the whole bench run: the counter/
    # histogram movement (requests by reason, tokens, TTFT/queue-wait
    # counts) lands in extra.telemetry so a regression in serving
    # signals is visible next to the throughput headline
    from localai_tfp_tpu.telemetry.registry import REGISTRY

    tel_snap = REGISTRY.snapshot()

    # --- 1B-class config (driver-tracked geometry since round 1;
    # kept in extra for cross-round continuity) ---
    spec = LLMSpec(
        vocab_size=32000, d_model=2048, n_layers=16, n_heads=32,
        n_kv_heads=8, d_head=64, d_ff=8192, max_position=4096,
    )
    n_slots, max_seq, gen_tokens = 64, 2048, 512
    extra["n_slots_1b"] = n_slots
    params = init_params(jax.random.PRNGKey(0), spec)
    # paged KV pool at HALF the dense worst case: every bench slot
    # peaks near prompt(~130) + 512 generated ~= 650 tokens (3 of 8
    # logical 256-token pages), so a pool of n_slots*max_pages/2
    # data pages seats the same 64 slots in the HBM a dense cache
    # would spend on 32 — the >=2x slot_capacity_multiple
    # extra.paged_kv reports, with zero admission failures
    kv_pages = n_slots * (max_seq // 256) // 2 + 1
    eng = LLMEngine(
        spec, params, tok, n_slots=n_slots, max_seq=max_seq,
        decode_steps=64, cache_dtype=jnp.bfloat16, autostart=False,
        kv_pages=kv_pages,
    )
    eng.start()
    eng.warmup()
    tok_s_1b, p50, p95 = _bench_config(eng, tok, n_slots, gen_tokens)
    extra["decode_tok_s_1b"] = tok_s_1b
    extra["ttft_p50_ms_1b"] = p50  # under a 64-deep burst
    extra["ttft_p95_ms_1b"] = p95
    # interactive TTFT: one request against the warm engine (the
    # BASELINE <200 ms target's classic reading)
    singles = []
    for _ in range(5):
        _, _, tt, errs = _run_wave(eng, tok, 1, 8, "benchmark " * 12)
        if errs:
            raise RuntimeError(
                f"single-request wave errored: {errs[0][:200]}")
        if tt:
            singles.append(tt[0])
    if not singles:
        raise RuntimeError("single-request TTFT produced no samples")
    singles.sort()
    extra["ttft_ms_1b_single"] = round(singles[len(singles) // 2], 1)
    extra["prefix_cache_1b"] = _prefix_cache_extra(eng)
    # the driver-tracked paged-KV capacity block: THIS leg runs the
    # half-worst-case pool, so slot_capacity_multiple shows the 2x
    # residency the paged arena buys at fixed HBM
    extra["paged_kv"] = _paged_kv_extra(eng)
    eng.close()
    del params, eng
    # release the 1B leg's HBM (params + KV cache + jit executables
    # holding donated buffers) before the 8B weights arrive
    import gc

    gc.collect()
    jax.clear_caches()

    # --- 8B leg (Llama-3.1-8B geometry) = THE HEADLINE, measured
    # through the stock /v1/chat/completions endpoint against a
    # REAL-format disk checkpoint: safetensors written in the HF
    # llama layout, loaded through the actual model loader (key
    # mapping -> int8_full quantization -> engine + warmup), with a
    # real byte-level BPE tokenizer — so TTFT includes genuine
    # tokenize/template/detokenize work and the whole path a user's
    # model YAML takes is the path measured ---
    import os
    import shutil
    import tempfile
    import time as _time

    from localai_tfp_tpu.config.app_config import ApplicationConfig
    from localai_tfp_tpu.server.state import Application

    spec8 = LLMSpec(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_head=128, d_ff=14336, max_position=4096,
        rope_theta=500000.0,
    )
    tmp = tempfile.mkdtemp(prefix="bench8b-")
    try:
        models = os.path.join(tmp, "models")
        os.makedirs(models, exist_ok=True)
        # the checkpoint is deterministic (seed 0): cache the ~16 GB
        # write across runs (4-10 min of pure disk IO per run
        # otherwise); the LOAD path is still exercised every run.
        # The key hashes the spec plus a writer-version literal —
        # BUMP "writer-v2" when _write_hf_checkpoint or
        # _build_bpe_tokenizer changes what they emit, or the stale
        # cache gets benched. Stale keys are swept so edits don't
        # strand 16 GB orphans.
        import glob
        import hashlib

        key = hashlib.sha256(
            (repr(spec8) + "|writer-v2").encode()).hexdigest()[:16]
        cache_root = os.environ.get(
            "XDG_CACHE_HOME", os.path.expanduser("~/.cache"))
        cache_ckpt = os.path.join(cache_root,
                                  f"localai_bench_ckpt_{key}")
        for stale in glob.glob(
                os.path.join(cache_root, "localai_bench_ckpt_*")):
            if stale != cache_ckpt:
                shutil.rmtree(stale, ignore_errors=True)
        marker = os.path.join(cache_ckpt, ".complete")
        t0 = _time.perf_counter()
        if not os.path.exists(marker):
            shutil.rmtree(cache_ckpt, ignore_errors=True)
            _write_hf_checkpoint(cache_ckpt, spec8)
            with open(marker, "w") as f:
                f.write("ok")
        extra["checkpoint_write_s"] = round(
            _time.perf_counter() - t0, 1)  # ~0 when cached
        os.symlink(cache_ckpt, os.path.join(models, "ckpt"))
        with open(os.path.join(models, "bench8b.yaml"), "w") as f:
            f.write(
                "name: bench8b\n"
                "backend: jax-llm\n"
                "parameters:\n  model: ckpt\n"
                "context_size: 1024\n"
                "max_batch_slots: 64\n"
                "quantization: int8_full\n"
                "kv_cache_dtype: int8\n"
                "decode_steps: 16\n"
                # open-capacity scans stay under ~70 ms of device
                # work so a steady-state arrival's prefill rides the
                # dispatch floor instead of queueing behind two full
                # scans (BASELINE.md: p50 TTFT < 200 ms)
                "latency_target_ms: 70\n"
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
        # configs + backend registry normally initialize in the
        # server's startup hook; the bench drives the loader directly
        from localai_tfp_tpu.engine.loader import (
            register_default_backends)

        register_default_backends()
        state.config_loader.load_configs_from_path()
        t0 = _time.perf_counter()
        backend = state.model_loader.load(
            state.config_loader.get("bench8b"))
        extra["checkpoint_load_s"] = round(
            _time.perf_counter() - t0, 1)  # incl. int8 quantize +
        # engine warmup (the jit-variant precompile)
        # which path the load ACTUALLY took, from the worker itself
        # (cold ~11 min: disk+stream-quantize+warmup; artifact
        # ~90 s: int8 read+transfer+warmup) — so the number above
        # is interpretable
        extra["checkpoint_load_mode"] = getattr(
            backend, "load_mode", "unknown")
        # per-phase wall-time breakdown (models/load_timing.py):
        # read/dequant/transfer/compile/warmup + other must
        # reconcile against checkpoint_load_s, so a regression in
        # any one phase is attributable instead of vanishing into
        # the total (the r5 167-missing-seconds problem)
        extra["checkpoint_load_breakdown"] = getattr(
            backend, "load_breakdown", {})
        eng8, tok8 = backend.engine, backend.tokenizer
        # 512-token streams: admission raggedness amortizes over the
        # stream length, so throughput reflects serving, not edges
        tok_s8, p50_8, p95_8 = _bench_config(eng8, tok8, 64, 512,
                                             runs=2)
        extra["decode_tok_s_8b_engine"] = tok_s8
        extra["ttft_p50_ms_8b_engine"] = p50_8
        extra["ttft_p95_ms_8b_engine"] = p95_8
        # live-engine measurements: _bench_http's guard enforces
        # that every _LIVE_ENGINE_EXTRAS block precedes it (its
        # teardown closes the serving engine via app cleanup)
        extra["mixed_itl"] = _mixed_itl_extra(eng8, tok8)
        # 8B pool is default-sized (worst case — the YAML config
        # sets no kv_pages), so this block tracks occupancy and
        # sharing; the capacity multiple lives in extra.paged_kv
        extra["paged_kv_8b"] = _paged_kv_extra(eng8)
        # ragged unification acceptance block: mode + variant count
        # + the throughput/ITL numbers measured above on this
        # engine (warmup_variants is 0 when the persistent-cache
        # marker skipped the pass)
        extra["ragged_attn"] = _ragged_attn_extra(
            eng8, extra["mixed_itl"], tok_s8)
        # tiered KV acceptance: decode overhead on THIS live
        # engine, capacity multiple on a dedicated pair
        extra["kv_tiering"] = _kv_tiering_extra(eng8, tok8)
        # disaggregated-serving acceptance: ITL contrast +
        # zero-re-prefill on a dedicated pair
        extra["disagg"] = _disagg_extra()
        tok_s, p50_h, p95_h, p50_steady = _bench_http(
            state, "bench8b", 64, 512, runs=2, extra=extra)
        extra["ttft_p50_ms_8b_http"] = p50_h
        extra["ttft_p95_ms_8b_http"] = p95_h
        extra["ttft_p50_ms_8b_http_steady"] = p50_steady
        extra["http_vs_engine"] = round(tok_s / max(tok_s8, 1e-9), 4)
        extra["tokenizer"] = "byte-bpe-128256 (real merge table)"
        extra["prefix_cache"] = _prefix_cache_extra(eng8)
        backend.shutdown()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    gc.collect()
    jax.clear_caches()
    # compiled-kernel parity on the real chip (VERDICT r3 next #5)
    from localai_tfp_tpu.ops.kernel_check import run_kernel_checks

    extra["kernel_check"] = run_kernel_checks()
    # pod-scale paged serving: builds its own meshed engine pair (or a
    # forced-host-device child on single-device smokes), so it is not
    # subject to the _LIVE_ENGINE_EXTRAS ordering guard
    extra["meshed_paged"] = _meshed_paged_extra()
    extra["weight_paging"] = _weight_paging_extra()
    extra["chaos"] = _chaos_extra()
    extra["fleet"] = _fleet_extra()
    extra["fleet_routing"] = _fleet_routing_extra()
    extra["tracing"] = _tracing_extra()
    extra["costmodel"] = _costmodel_extra()
    extra["cost_sched"] = _cost_sched_extra()
    extra["lint"] = _lint_extra()
    extra["telemetry"] = REGISTRY.delta(tel_snap)
    print(json.dumps({
        "metric": "decode_throughput",
        "value": tok_s,
        "unit": "tok/s/chip",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "vs_baseline": round(tok_s / BASELINE_TOK_S, 4),
        "extra": extra,
    }))


if __name__ == "__main__":
    main()
