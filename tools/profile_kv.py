"""Paged KV pool profiler: occupancy, sharing, HBM-per-live-token.

The paged pool's whole point is that HBM follows LIVE tokens instead of
worst-case context and that shared prefixes cost refcount bumps instead
of row copies. This tool measures both claims under the two traffic
shapes that stress them:

  python tools/profile_kv.py --shared-prefix [--small] \
      [--requests N] [--prefix-tokens P]

drives a burst of N requests sharing a P-token prefix, then N fully
distinct requests, straight through the engine scheduler. Reports, per
burst: page-allocation outcomes (fresh / zero-copy shared / COW),
kvcopy dispatches (whole-page shares must need ZERO for the aligned
prefix body), peak pool occupancy, share ratio (refs vs distinct
pages), and HBM bytes per live token.

  python tools/profile_kv.py --mixed [--small] \
      [--streams N] [--bursts K] [--burst-size B]

sustains N decode streams while injecting K admission bursts of B
requests, sampling the pool every 50 ms. Reports peak/mean occupancy
and HBM-per-live-token across the run — the series that shows the
arena tracking expected context while traffic churns.

  python tools/profile_kv.py --returning-users [--small] [--users N]

measures the tiered KV memory claim (engine/kv_tier.py): N distinct
sessions (N > n_slots) are served through slot churn, then every user
RETURNS. With LOCALAI_KV_TIER=off a returning session re-prefills
unless it still sits in a slot; with the tier on, demoted sessions are
prefetched back from host RAM. Reports resident-session capacity
(off vs on, and the multiple), prefetch hit rate, re-prefill tokens
avoided, and re-prefill tokens paid on hits (must be ZERO — a hit
promotes the full covered prefix by reference).

  python tools/profile_kv.py --gather [--small] \
      [--plane DTYPE:L,PAGES,...:B[,B...]]...

times the device half of a spill ALONE (engine/kv_tier.py
``_gather_pages``; no engine, no model): for each pool plane and page
count it runs the shipped program beside the plain ``arr[:, tbl]`` it
has to equal bit for bit, and reports the device time a call (the
profiler's module events — chip only; "not measured" on a CPU) against
the bytes the call has to move (``b`` pages read, ``b`` written) over
the chip's HBM peak. The default planes are the two benchmark cells'
(Mistral: int8 K/V + float32 scale planes; Trinity: bfloat16 K/V). Run
it on the chip for a new pool shape before trusting a spill's cost. (At
ONE page both forms lower to the same program, which the compile cache
loads under the shipped form's name: the reference then reads "not
measured" and the shipped form counts both forms' events.)

``--small`` runs the tiny CPU config (smoke) with a 16-token page so
page-granular sharing is visible at toy prompt lengths.
"""

from __future__ import annotations

import argparse
import json
import os
import queue as _queue
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class _CopySpy:
    """Count kvcopy dispatches at the engine._run layer — ground truth
    for the zero-copy claim (telemetry is cross-checked against it)."""

    def __init__(self, eng):
        self.eng = eng
        self.copies = 0
        self._orig = eng._run
        eng._run = self._run

    def reset(self):
        self.copies = 0

    def _run(self, kind, payload):
        if kind == "kvcopy":
            self.copies += 1
        return self._orig(kind, payload)


def _pool_block(eng) -> dict:
    from bench import _paged_kv_extra

    return _paged_kv_extra(eng)


def _drain_all(qs, timeout=300):
    pending = list(qs)
    while pending:
        nxt = []
        for q in pending:
            done = False
            while True:
                try:
                    ev = q.get_nowait()
                except _queue.Empty:
                    break
                if ev.done:
                    if ev.error:
                        raise RuntimeError(ev.error)
                    done = True
                    break
            if not done:
                nxt.append(q)
        pending = nxt
        if pending:
            time.sleep(0.002)


def _build(small: bool):
    if small:
        # 16-token pages: page-run sharing becomes visible at toy
        # prompt lengths (the default 256-token page needs a 256-token
        # aligned prefix before the first zero-copy share)
        os.environ.setdefault("LOCALAI_KV_PAGE", "16")
    from tools.profile_ttft import build_engine

    return build_engine(small)


def shared_prefix_shape(small: bool, n_req: int,
                        prefix_tokens: int) -> dict:
    from localai_tfp_tpu.engine.engine import GenRequest
    from localai_tfp_tpu.engine.prefix_index import PrefixIndex

    eng, tok, _, _ = _build(small)
    if small:
        n_req = min(n_req, eng.n_slots)
        prefix_tokens = min(prefix_tokens, eng.max_seq // 2)
    n_tok = 8 if small else 32
    spy = _CopySpy(eng)
    out: dict = {"paged": getattr(eng, "_paged", False),
                 "page_tokens": getattr(eng, "_page", None)}
    shared = "S" * prefix_tokens
    shapes = {
        "shared": [shared + f" req {i:03d}" for i in range(n_req)],
        "distinct": [f"{i:03d} " + os.urandom(8).hex() + " distinct"
                     for i in range(n_req)],
    }
    try:
        # warm pass compiles every dispatch variant the measured waves
        # hit, so wave timing reflects the allocator, not the jit
        _drain_all(eng.submit_many([
            GenRequest(prompt_ids=tok.encode(c), max_tokens=n_tok,
                       temperature=0.0, ignore_eos=True)
            for c in shapes["shared"]]))
        for name, contents in shapes.items():
            # cold start per shape: drop residents so occupancy and
            # sharing are attributable to THIS wave
            for s in eng.slots:
                s.cache_tokens = []
                s.n_past = 0
                if eng._paged:
                    eng._pool.drop(s.idx)
            eng._prefix_index = PrefixIndex()
            spy.reset()
            alloc0 = (dict(eng._pool.allocs) if eng._paged else {})
            # donor first (its KV must be resident before sharers), then
            # the sharer wave
            _drain_all(eng.submit_many([GenRequest(
                prompt_ids=tok.encode(contents[0]), max_tokens=n_tok,
                temperature=0.0, ignore_eos=True)]))
            _drain_all(eng.submit_many([
                GenRequest(prompt_ids=tok.encode(c), max_tokens=n_tok,
                           temperature=0.0, ignore_eos=True)
                for c in contents[1:]]))
            blk = _pool_block(eng)
            if eng._paged:
                blk["alloc"] = {k: v - alloc0.get(k, 0)
                                for k, v in eng._pool.allocs.items()}
            blk["kv_copies"] = spy.copies
            out[name] = blk
        if out["paged"]:
            sh = out["shared"]
            sh["share_ratio"] = round(
                sh["page_refs"] / max(sh["pages_in_use"], 1), 3)
    finally:
        eng.close()
    return out


def mixed_shape(small: bool, n_streams: int, n_bursts: int,
                burst_size: int) -> dict:
    from localai_tfp_tpu.engine.engine import GenRequest

    eng, tok, _, _ = _build(small)
    n_streams = min(n_streams, max(1, eng.n_slots // 2))
    burst_size = min(burst_size, max(1, eng.n_slots - n_streams))
    n_tok = 48 if small else 128
    bp = "burst " * max(1, min(eng.max_seq // 2, 256) // 6)
    out: dict = {"paged": getattr(eng, "_paged", False),
                 "page_tokens": getattr(eng, "_page", None),
                 "streams": n_streams, "bursts": n_bursts,
                 "burst_size": burst_size}
    samples: list[tuple[int, float]] = []  # (pages_in_use, hbm/tok)
    stop = threading.Event()

    def sampler():
        while not stop.wait(0.05):
            if not eng._paged:
                continue
            st = eng._pool.stats()
            live = sum(len(s.cache_tokens) for s in eng.slots)
            c = eng.cache
            tb = 2 * c.k.dtype.itemsize * c.k.shape[0] * c.k.shape[-1]
            if c.quantized:
                tb += 2 * 4 * c.k.shape[0]
            samples.append((st.in_use,
                            st.in_use * eng._page * tb / max(live, 1)))

    try:
        # warm compile pass
        _drain_all(eng.submit_many([GenRequest(
            prompt_ids=tok.encode(bp + "w"), max_tokens=4,
            temperature=0.0, ignore_eos=True)]))
        t = threading.Thread(target=sampler, daemon=True)
        t.start()
        qs = eng.submit_many([
            GenRequest(prompt_ids=tok.encode(f"stream {i:02d}"),
                       max_tokens=n_tok, temperature=0.0,
                       ignore_eos=True)
            for i in range(n_streams)])
        burst_qs = []
        for j in range(n_bursts):
            time.sleep(0.1)
            burst_qs += eng.submit_many([
                GenRequest(prompt_ids=tok.encode(bp + f"{j}-{b}"),
                           max_tokens=8, temperature=0.0,
                           ignore_eos=True)
                for b in range(burst_size)])
        _drain_all(qs + burst_qs)
        stop.set()
        t.join(timeout=2)
        blk = _pool_block(eng)
        if samples:
            occ = [s[0] for s in samples]
            hbm = [s[1] for s in samples]
            blk["pages_in_use_peak"] = max(occ)
            blk["pages_in_use_mean"] = round(sum(occ) / len(occ), 1)
            blk["hbm_bytes_per_live_token_peak"] = round(max(hbm), 1)
            blk["hbm_bytes_per_live_token_mean"] = round(
                sum(hbm) / len(hbm), 1)
        out["pool"] = blk
    finally:
        stop.set()
        eng.close()
    return out


def _resident_sessions(eng, ids) -> int:
    """Sessions whose full prompt KV is still reachable without a
    re-prefill: resident in a slot, or promotable from the tier."""

    def covered(pid) -> bool:
        need = len(pid) - 1  # the relogit token always reprocesses
        if any(_common(s.cache_tokens, pid) >= need for s in eng.slots):
            return True
        tier = getattr(eng, "_tier", None)
        if tier is not None:
            _, n = tier._lookup(pid)
            return n >= need
        return False

    return sum(1 for pid in ids if covered(pid))


def _common(a, b) -> int:
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


def returning_users_shape(small: bool, n_users: int) -> dict:
    """Churn n_users distinct sessions through the slots, then have
    every user return — tier off vs on, same traffic."""
    from localai_tfp_tpu.engine.engine import GenRequest

    out: dict = {"users": n_users}
    saved = os.environ.get("LOCALAI_KV_TIER")
    try:
        for mode in ("off", "on"):
            os.environ["LOCALAI_KV_TIER"] = mode
            eng, tok, _, _ = _build(small)
            tier = getattr(eng, "_tier", None)
            ids = [tok.encode(f"user {i:03d} " + "ctx " * 12
                              + f"tail {i}")
                   for i in range(n_users)]
            total_prompt = sum(len(i) for i in ids)

            def serve(round_ids):
                for lo in range(0, len(round_ids), eng.n_slots):
                    _drain_all(eng.submit_many([
                        GenRequest(prompt_ids=pid, max_tokens=4,
                                   temperature=0.0, ignore_eos=True)
                        for pid in round_ids[lo:lo + eng.n_slots]]))
                if tier is not None:
                    tier.settle()

            try:
                serve(ids)  # round 1: every session served once
                blk: dict = {
                    "resident_sessions": _resident_sessions(eng, ids),
                }
                reused0 = eng.metrics.prefix_reused_tokens
                t0 = (dict(tier.counters) if tier is not None else {})
                wall = time.perf_counter()
                serve(ids)  # round 2: every user returns
                wall = time.perf_counter() - wall
                reused = eng.metrics.prefix_reused_tokens - reused0
                blk["return_wall_s"] = round(wall, 3)
                blk["reprefill_tokens"] = total_prompt - reused
                blk["reused_tokens"] = reused
                if tier is not None:
                    tc = {k: tier.counters[k] - t0.get(k, 0)
                          for k in tier.counters}
                    ret = tc["prefetch_hit"] + tc["prefetch_late"] \
                        + tc["prefetch_miss"]
                    blk["prefetch_hits"] = tc["prefetch_hit"]
                    blk["prefetch_hit_rate"] = round(
                        tc["prefetch_hit"] / max(ret, 1), 3)
                    blk["tier_reused_tokens"] = tc["reused_tokens"]
                    # a hit promotes the full covered prompt (less the
                    # relogit token): re-prefill paid on hits must be 0
                    blk["reprefill_tokens_on_hits"] = (
                        tc["prefetch_hit"] * (len(ids[0]) - 1)
                        - min(tc["reused_tokens"],
                              tc["prefetch_hit"] * (len(ids[0]) - 1)))
                    blk["tier"] = {k: v for k, v in
                                   tier.stats().items() if v}
                    tier.leak_check()
                if eng._paged:
                    eng._pool.leak_check()
                out[mode] = blk
            finally:
                eng.close()
    finally:
        if saved is None:
            os.environ.pop("LOCALAI_KV_TIER", None)
        else:
            os.environ["LOCALAI_KV_TIER"] = saved
    out["capacity_multiple"] = round(
        out["on"]["resident_sessions"]
        / max(out["off"]["resident_sessions"], 1), 2)
    return out


# the benchmark cells' pool planes and the page counts their spills pad
# to (PERF.md section 5): Mistral int8 K/V + scale planes, Trinity bf16
_CELL_PLANES = ("int8:32,257,256,1024:2,4", "float32:32,257,256:4",
                "bfloat16:8,257,256,512:16")
_SMALL_PLANES = ("int8:2,33,16,128:1,4", "float32:2,33,16:4",
                 "bfloat16:2,33,16,64:16")


def _take_pages(arr, tbl):
    """What a spill's gather has to equal: plain indexing (the form the
    tier shipped until PR 42, which copied the whole plane on a v5e)."""
    return arr[:, tbl]


def _module_times_us(trace_dir: str, name: str) -> list:
    """Device time of every run of the program ``jit_<name>`` in the
    one capture under ``trace_dir`` — the benchmark's own reduction
    (benchmark/lib/trace.py), so ``breakdown`` reads the same events."""
    import glob

    from benchmark.lib import trace as T

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    tr = T.dump(path)
    if not T.chip_planes(tr):  # a CPU capture has no device plane
        return []
    return [e[2] / 1e3 for e in T.module_events(tr, (f"jit_{name}",))]


def parse_plane(spec: str):
    """``DTYPE:L,PAGES,...:B[,B...]`` -> (dtype name, shape, page
    counts)."""
    dt, shape, bs = spec.split(":")
    return (dt, tuple(int(x) for x in shape.split(",")),
            tuple(int(x) for x in bs.split(",")))


def gather_alone(planes, calls: int = 10) -> dict:
    """Each plane's gather alone: shipped form against the reference
    form — outputs bit-equal, device microseconds a call, and the
    share of the HBM roof (bytes moved / peak / time)."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from localai_tfp_tpu.engine.kv_pool import TRASH_PAGE
    from localai_tfp_tpu.engine.kv_tier import _gather_pages
    from localai_tfp_tpu.telemetry.costmodel import peak_rates

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    hbm = peak_rates(dev.device_kind)[1] if on_chip else None
    forms = {"_gather_pages": _gather_pages,
             "_take_pages": jax.jit(_take_pages)}
    out: dict = {"device": {"platform": dev.platform,
                            "kind": dev.device_kind},
                 "hbm_bytes_per_s": hbm, "calls": calls, "points": []}
    for spec in planes:
        dt, shape, bs = parse_plane(spec)
        dtype = jnp.dtype(dt)
        # one layer of random pages, shifted by the layer's number (a
        # 2 GB plane drawn whole would need 4 x that in temporaries)
        key = jax.random.PRNGKey(len(out["points"]))
        if dtype == jnp.int8:
            base = jax.random.randint(key, shape[1:], -128, 128, jnp.int8)
        else:
            base = jax.random.normal(key, shape[1:],
                                     jnp.float32).astype(dtype)
        layer = jnp.arange(shape[0]).astype(dtype).reshape(
            (-1,) + (1,) * (len(shape) - 1))
        arr = jax.block_until_ready(jax.jit(jnp.add)(base[None], layer))
        del base
        rng = np.random.default_rng(0)
        for b in bs:
            # a spill's table: distinct page ids, the tail padded with
            # the trash page (3 real ids of 4, as _pow2 pads), and one
            # id twice where there is room (the gather does not care)
            ids = rng.choice(np.arange(1, shape[1]), size=b,
                             replace=False).astype(np.int32)
            if b >= 4:
                ids[-1] = TRASH_PAGE
                ids[1] = ids[0]
            tbl = jnp.asarray(ids)
            moved = 2 * b * int(np.prod(shape)) // shape[1] \
                * dtype.itemsize
            point = {"plane": f"{dt}{list(shape)}", "pages": b,
                     "bytes_moved": moved,
                     "roof_us": (round(moved / hbm * 1e6, 2)
                                 if hbm else None)}
            outs = {}
            for name, fn in forms.items():
                outs[name] = jax.block_until_ready(fn(arr, tbl))  # warm
            ref = np.asarray(outs["_take_pages"]).view(np.uint8)
            point["bit_equal"] = all(
                np.array_equal(np.asarray(o).view(np.uint8), ref)
                for o in outs.values())
            del outs, ref
            with tempfile.TemporaryDirectory() as tmp:
                with jax.profiler.trace(tmp):
                    for fn in forms.values():
                        for _ in range(calls):
                            jax.block_until_ready(fn(arr, tbl))
                for name in forms:
                    us = _module_times_us(tmp, name)
                    if on_chip and us:
                        med = float(np.median(us))
                        point[name] = {
                            "us_call": round(med, 2), "events": len(us),
                            "roof_share": round(moved / hbm / med * 1e6,
                                                4)}
                    else:
                        point[name] = {"us_call": "not measured"}
            out["points"].append(point)
        del arr
    stats = dev.memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["ok"] = all(p["bit_equal"] for p in out["points"])
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--small", action="store_true",
                    help="tiny CPU config (smoke)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="shared-prefix burst vs distinct burst")
    ap.add_argument("--mixed", action="store_true",
                    help="sustained streams + admission bursts")
    ap.add_argument("--returning-users", action="store_true",
                    help="session churn + return: KV tiering on vs off")
    ap.add_argument("--gather", action="store_true",
                    help="time the tier's spill gather alone")
    ap.add_argument("--plane", action="append", default=None,
                    metavar="DTYPE:SHAPE:PAGES",
                    help="a pool plane for --gather, e.g. "
                    "int8:32,257,256,1024:2,4 (repeatable; default: "
                    "the benchmark cells' planes)")
    ap.add_argument("--users", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prefix-tokens", type=int, default=96)
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--bursts", type=int, default=3)
    ap.add_argument("--burst-size", type=int, default=4)
    args = ap.parse_args()
    if args.gather:
        # no engine, no model: the gather's program alone
        rep = gather_alone(args.plane or (
            _SMALL_PLANES if args.small else _CELL_PLANES))
        print(json.dumps(rep, indent=1), flush=True)
        sys.exit(0 if rep["ok"] else 1)
    if not (args.shared_prefix or args.mixed or args.returning_users):
        ap.error("pick a traffic shape: --shared-prefix, --mixed, "
                 "--returning-users or --gather")
    report: dict = {}
    if args.shared_prefix:
        report["shared_prefix"] = shared_prefix_shape(
            args.small, args.requests, args.prefix_tokens)
    if args.mixed:
        report["mixed"] = mixed_shape(args.small, args.streams,
                                      args.bursts, args.burst_size)
    if args.returning_users:
        report["returning_users"] = returning_users_shape(
            args.small, args.users)
    # ragged paged attention: jit-cache variant counts + warmup wall
    # time, on vs off — the compile-variant collapse next to the pool
    # numbers it rides on
    from bench import ragged_variant_report

    report["ragged_attn"] = ragged_variant_report()
    print(json.dumps(report, indent=1), flush=True)


if __name__ == "__main__":
    main()
