"""Fleet profile: the telemetry digest plane measured end to end.

Boots N REAL member servers (subprocesses, tiny CPU checkpoint) behind
an in-process federated balancer, drives mixed streaming traffic
through the balancer, and prints one JSON report with the acceptance
numbers the fleet-telemetry PR tracks:

  percentile cross-check:
    client_ttft_p95_s       — p95 of client-measured time-to-first-
                              content-chunk across every request
    fleet_ttft_p95_bounds_s — the bucket holding p95 in the balancer's
                              merged digest histogram (/fleet/metrics)
    ttft_within_one_bucket  — |client bucket - digest bucket| <= 1:
                              exact bucket merges put the fleet p95
                              within one histogram bucket of what
                              clients actually saw (the contract that
                              forbids averaging per-node percentiles)

  digest plane health:
    digest_bytes_max        — largest /telemetry/digest body observed
                              (contract: <= LOCALAI_DIGEST_MAX_BYTES)
    digest_age_max_s        — staleness across nodes right after the
                              traffic wave (probe-refreshed, so this
                              tracks the probe interval, not the 20 s
                              heartbeat)
    load_skew               — max(requests_served) / mean — least-used
                              routing should keep this near 1.0

  SLO burn-rate monitor:
    slo_flip_latency_s      — kill one member; seconds until the
                              availability objective on /fleet/slo
                              leaves "ok" (fast/slow windows shrunk via
                              env so the flip is observable in a smoke)
    slo_flip_within_2_probes— latency <= 2 probe intervals (+ sched
                              slack): the first failed probe marks the
                              node not-serving, the second confirms
    metrics_served_during_kill — /fleet/metrics kept answering 200
                              while the fleet was degraded

Run:  python tools/profile_fleet.py [--members N] [--requests N]
                                    [--probe-s S] [--json]

CPU smoke (what CI can afford):  python tools/profile_fleet.py --smoke
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import re
import socket
import sys
import tempfile
import time
from bisect import bisect_left

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# SLO windows shrunk so a burn-rate flip is observable inside a smoke
# run; generous TTFT threshold so first-request compiles cannot push
# the latency objective into warning and muddy the availability check
_SMOKE_ENV = {
    "LOCALAI_SLO_FAST_WINDOW_S": "1",
    "LOCALAI_SLO_SLOW_WINDOW_S": "5",
    "LOCALAI_SLO_TTFT_P95_MS": "30000",
    "LOCALAI_SLO_ITL_P99_MS": "30000",
}

_TINY_YAML = """
name: tiny
backend: jax-llm
parameters:
  model: tiny-ckpt
  temperature: 0.0
  max_tokens: 16
context_size: 128
max_batch_slots: 2
dtype: float32
template:
  completion: "{{.Input}}"
  chat_message: "{{.RoleName}}: {{.Content}}"
  chat: "{{.Input}}\\nassistant:"
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _make_models(models_dir: str, *, hidden: int = 64,
                 inter: int = 128, heads: int = 4, kv_heads: int = 2,
                 ctx: int = 128) -> None:
    """Tiny torch Llama checkpoint + config: real jax-llm members that
    boot (and first-request compile) in seconds on CPU. The routing
    leg widens it (hidden/ctx up, still 2 layers so XLA compile stays
    seconds): prefill compute must be MEASURABLE there, because the
    locality win a hit buys IS the skipped prefill — on the 64-wide
    model a full prefill and a tail prefill differ by ~2 ms, under
    per-request noise."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    LlamaForCausalLM(LlamaConfig(
        vocab_size=300, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=2, num_attention_heads=heads,
        num_key_value_heads=kv_heads,
        max_position_embeddings=max(256, 2 * ctx),
    )).save_pretrained(os.path.join(models_dir, "tiny-ckpt"),
                       safe_serialization=True)
    with open(os.path.join(models_dir, "tiny.yaml"), "w") as f:
        f.write(_TINY_YAML.replace("context_size: 128",
                                   f"context_size: {ctx}"))


def _spawn_member(models_dir: str, cwd: str, port: int, *,
                  balancer_url: str, token: str, name: str):
    """One REAL member: announces itself (digest riding the heartbeat)
    and serves the balancer's /healthz + /telemetry/digest probes."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["LOCALAI_WARMUP"] = "0"  # skip warmup decode: fast boot
    env["LOCALAI_NODE_NAME"] = name
    env.pop("LOCALAI_FAULTS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    return subprocess.Popen(
        [sys.executable, "-m", "localai_tfp_tpu.cli", "run",
         "--models-path", models_dir, "--address", "127.0.0.1",
         "--port", str(port),
         "--p2p-token", token,
         "--federated-server", balancer_url,
         "--advertise-address", f"http://127.0.0.1:{port}"],
        cwd=cwd, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT)


async def _wait_ready(session, base: str, timeout_s: float = 180.0) -> None:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        try:
            async with session.get(base + "/readyz") as r:
                if r.status == 200:
                    return
        except Exception:
            pass
        await asyncio.sleep(0.3)
    raise TimeoutError(f"member {base} never became ready")


async def _chat_ttft(client, prompt: str = "", max_tokens: int = 8,
                     messages=None) -> float:
    """One streaming chat completion through the balancer; returns the
    client-measured time to the first GENERATED event — the first chunk
    after the role preamble (which is written before generation
    starts). A tiny random checkpoint can emit tokens whose bytes decode
    to empty text, so the finish chunk is an accepted (late) fallback —
    at smoke token counts it lands in the same log bucket."""
    t0 = time.perf_counter()
    resp = await client.request(
        "POST", "/v1/chat/completions",
        json={"model": "tiny", "stream": True, "max_tokens": max_tokens,
              "messages": messages
              or [{"role": "user", "content": prompt}]})
    assert resp.status == 200, f"proxy status {resp.status}"
    ttft = None
    async for raw in resp.content:
        line = raw.decode("utf-8", "replace").strip()
        if not line.startswith("data: ") or line == "data: [DONE]":
            continue
        try:
            ev = json.loads(line[len("data: "):])
        except ValueError:
            continue
        choice = (ev.get("choices") or [{}])[0]
        delta = choice.get("delta") or {}
        finish = choice.get("finish_reason")
        if finish == "error":
            raise RuntimeError(f"stream errored: {ev}")
        if ttft is None and "role" not in delta and (
                delta.get("content") or finish is not None):
            ttft = time.perf_counter() - t0
    resp.release()
    if ttft is None:
        raise RuntimeError("stream produced no generated event")
    return ttft


def _prom_hist(text: str, family: str) -> list[tuple[float, float]]:
    """[(le, cumulative_count)] rows of one un-labelled fleet histogram
    from a Prometheus 0.0.4 page, in exposition order."""
    rows = []
    for m in re.finditer(
            rf'^{family}_bucket\{{le="([^"]+)"\}}\s+(\S+)$', text, re.M):
        le = m.group(1)
        rows.append((float("inf") if le == "+Inf" else float(le),
                     float(m.group(2))))
    return rows


def _cum_p95_index(rows: list[tuple[float, float]], q: float) -> int:
    """Bucket index holding the q-quantile of a cumulative histogram."""
    total = rows[-1][1] if rows else 0.0
    if total <= 0:
        return 0
    rank = max(1.0, math.ceil(q * total))
    for i, (_le, cum) in enumerate(rows):
        if cum >= rank:
            return i
    return len(rows) - 1


async def fleet_leg(n_members: int = 3, probe_s: float = 0.5,
                    n_requests: int = 18) -> dict:
    import aiohttp
    from aiohttp.test_utils import TestClient, TestServer

    from localai_tfp_tpu.parallel.federated import (
        FederatedServer, generate_token,
    )
    from localai_tfp_tpu.telemetry import digest as dg

    saved = {k: os.environ.get(k) for k in _SMOKE_ENV}
    os.environ.update(_SMOKE_ENV)
    out: dict = {"members": n_members, "probe_s": probe_s,
                 "requests": n_requests}
    members: list = []
    try:
        with tempfile.TemporaryDirectory() as tmp:
            models = os.path.join(tmp, "models")
            os.makedirs(models)
            _make_models(models)

            tok = generate_token()
            fed = FederatedServer(tok, probe_s=probe_s)
            client = TestClient(TestServer(fed.build_app()))
            await client.start_server()
            balancer_url = (f"http://127.0.0.1:"
                            f"{client.server.port}")
            session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=10))
            try:
                ports = []
                for i in range(n_members):
                    port = _free_port()
                    cwd = os.path.join(tmp, f"member{i}")
                    os.makedirs(cwd)
                    members.append(_spawn_member(
                        models, cwd, port, balancer_url=balancer_url,
                        token=tok, name=f"member-{i}"))
                    ports.append(port)
                t_boot = time.monotonic()
                await asyncio.gather(*[
                    _wait_ready(session, f"http://127.0.0.1:{p}")
                    for p in ports])
                out["member_boot_s"] = round(
                    time.monotonic() - t_boot, 1)

                # the startup announce registers each member (digest
                # attached); wait until the registry sees the full fleet
                t0 = time.monotonic()
                while time.monotonic() - t0 < 60:
                    r = await client.get("/federation/nodes")
                    nodes = await r.json()
                    if len(nodes) == n_members:
                        break
                    await asyncio.sleep(0.2)
                assert len(nodes) == n_members, \
                    f"only {len(nodes)}/{n_members} members registered"
                out["announce_digest_nodes"] = sum(
                    1 for n in nodes
                    if (n.get("digest") or {}).get("src") == "announce")

                # ---- mixed traffic wave through the balancer ----
                sem = asyncio.Semaphore(3)
                prompts = ["hi", "tell me a story about a boat",
                           "x " * 20, "why"]

                async def one(i: int) -> float:
                    async with sem:
                        return await _chat_ttft(
                            client, prompts[i % len(prompts)],
                            max_tokens=8 + 8 * (i % 2))

                ttfts = await asyncio.gather(
                    *[one(i) for i in range(n_requests)])
                ttfts = sorted(ttfts)

                # let the next probe round pick up final digests
                await asyncio.sleep(2 * probe_s + 0.2)

                # ---- digest plane health ----
                sizes = []
                for p in ports:
                    async with session.get(
                            f"http://127.0.0.1:{p}/telemetry/digest"
                    ) as r:
                        raw = await r.read()
                    dg.decode(raw)  # must round-trip the wire format
                    sizes.append(len(raw))
                out["digest_bytes_max"] = max(sizes)
                out["digest_within_cap"] = max(sizes) <= dg._max_bytes()

                r = await client.get("/federation/nodes")
                nodes = await r.json()
                out["nodes_cache_control"] = r.headers.get(
                    "Cache-Control")
                ages = [(n.get("digest") or {}).get("age_s")
                        for n in nodes]
                out["digest_age_max_s"] = round(
                    max(a for a in ages if a is not None), 3)
                out["digest_stale_nodes"] = sum(
                    1 for n in nodes
                    if (n.get("digest") or {}).get("stale", True))
                served = [n["requests_served"] for n in nodes]
                mean = sum(served) / max(1, len(served))
                out["requests_served"] = served
                out["load_skew"] = round(max(served) / mean, 3) \
                    if mean else None

                # ---- percentile cross-check: merged digests vs what
                # clients measured ----
                r = await client.get("/fleet/metrics")
                prom = (await r.read()).decode()
                rows = _prom_hist(prom, "fleet_ttft_seconds")
                total = rows[-1][1] if rows else 0
                out["fleet_ttft_count"] = int(total)
                i_fleet = _cum_p95_index(rows, 0.95)
                client_p95 = ttfts[
                    min(len(ttfts) - 1, int(math.ceil(0.95 * len(ttfts))) - 1)]
                bounds = dg.HIST_BOUNDS["ttft"]
                i_client = bisect_left(bounds, client_p95)
                out["client_ttft_p50_s"] = round(
                    ttfts[len(ttfts) // 2], 4)
                out["client_ttft_p95_s"] = round(client_p95, 4)
                out["fleet_ttft_p95_bounds_s"] = [
                    0.0 if i_fleet == 0 else bounds[i_fleet - 1],
                    rows[i_fleet][0] if rows else 0.0]
                out["ttft_within_one_bucket"] = abs(
                    i_fleet - i_client) <= 1
                itl_rows = _prom_hist(prom, "fleet_itl_seconds")
                if itl_rows and itl_rows[-1][1] > 0:
                    i50 = _cum_p95_index(itl_rows, 0.50)
                    i95 = _cum_p95_index(itl_rows, 0.95)
                    out["fleet_itl_p50_le_s"] = itl_rows[i50][0]
                    out["fleet_itl_p95_le_s"] = itl_rows[i95][0]

                # ---- SLO flip: kill one member ----
                r = await client.get("/fleet/slo")
                slo = await r.json()
                out["slo_cache_control"] = r.headers.get("Cache-Control")
                out["slo_state_before_kill"] = \
                    slo["objectives"]["availability"]["state"]
                members[-1].kill()
                t_kill = time.monotonic()
                flip = None
                metrics_ok = True
                while time.monotonic() - t_kill < 15.0:
                    r = await client.get("/fleet/metrics")
                    metrics_ok &= r.status == 200
                    await r.release()
                    r = await client.get("/fleet/slo")
                    slo = await r.json()
                    if slo["objectives"]["availability"]["state"] != "ok":
                        flip = time.monotonic() - t_kill
                        break
                    await asyncio.sleep(0.05)
                out["slo_state_after_kill"] = \
                    slo["objectives"]["availability"]["state"]
                out["slo_flip_latency_s"] = (round(flip, 3)
                                             if flip is not None else None)
                out["slo_flip_within_2_probes"] = (
                    flip is not None and flip <= 2 * probe_s + 0.5)
                out["metrics_served_during_kill"] = metrics_ok
                out["nodes_serving_after_kill"] = slo["nodes"]["serving"]
            finally:
                await session.close()
                await client.close()
    finally:
        for m in members:
            m.terminate()
        for m in members:
            try:
                m.wait(timeout=10)
            except Exception:
                m.kill()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


# --------------------------------------------------------------------
# --routing: prefix-locality routing vs blind least-used, same fleet
# --------------------------------------------------------------------

# knobs for the routing leg: fast prefix-summary refresh so a seed
# request's KV residency reaches the gossiped digest within one probe
_ROUTING_ENV = dict(_SMOKE_ENV, LOCALAI_PREFIX_SUMMARY_S="0.2")


def _group_messages(tag: str, i: int) -> list:
    """Shared-prefix workload: every request in a group opens with the
    same long system message (one fingerprint boundary == one reusable
    KV prefix) and diverges at the user turn. The tag leads the
    preamble so DIFFERENT groups diverge at the first content token —
    a shared opening would make every group's token prefix overlap."""
    # ~300 chars (~310 tokens at this tokenizer's ~1 token/char): the
    # routing leg's widened model has a 384-token context, and the
    # shared prefix must dominate the tail — the hit-vs-miss TTFT gap
    # IS the prefill the hit skips
    preamble = f"{tag} desk. " + "Cite the runbook. " * 16
    tails = ["status?", "next?", "oncall?", "doc?", "retry?", "eta?"]
    return [{"role": "system", "content": preamble},
            {"role": "user", "content": tails[i % len(tails)]}]


async def routing_leg(n_members: int = 3, probe_s: float = 0.5,
                      groups: int = 4, repeats: int = 6) -> dict:
    """A/B inside one run: phase A drives grouped shared-prefix traffic
    with blind ``least-used`` routing, phase B drives fresh groups with
    ``prefix`` (cost-scored) routing. Reports the cross-replica prefix
    hit rate and the repeat-request TTFT p50 of each phase — locality
    must land repeats on the member already holding the group's KV.

    ``groups`` deliberately does NOT equal ``n_members``: least-used
    rotation is deterministic, so with groups == members the blind
    phase's group->member assignment is CONSTANT across rounds and can
    accidentally align every group with its seeded KV holder — a blind
    baseline that routes like a perfect locality router. A group count
    coprime to the member count rotates each group across members, so
    blind hits the holder at the expected ~1/members rate."""
    import aiohttp
    from aiohttp.test_utils import TestClient, TestServer

    from localai_tfp_tpu.parallel.federated import (
        FederatedServer, generate_token,
    )

    saved = {k: os.environ.get(k) for k in _ROUTING_ENV}
    os.environ.update(_ROUTING_ENV)
    out: dict = {"members": n_members, "probe_s": probe_s,
                 "groups": groups, "repeats": repeats}
    members: list = []
    try:
        with tempfile.TemporaryDirectory() as tmp:
            models = os.path.join(tmp, "models")
            os.makedirs(models)
            # wider model (see _make_models): a skipped 300-token
            # prefill must be worth 10s of ms for the locality TTFT
            # comparison to clear per-request noise
            _make_models(models, hidden=256, inter=512, heads=8,
                         kv_heads=4, ctx=384)
            tok = generate_token()
            fed = FederatedServer(tok, strategy="least-used",
                                  probe_s=probe_s)
            client = TestClient(TestServer(fed.build_app()))
            await client.start_server()
            balancer_url = f"http://127.0.0.1:{client.server.port}"
            session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=10))
            try:
                ports = []
                for i in range(n_members):
                    port = _free_port()
                    cwd = os.path.join(tmp, f"member{i}")
                    os.makedirs(cwd)
                    members.append(_spawn_member(
                        models, cwd, port, balancer_url=balancer_url,
                        token=tok, name=f"member-{i}"))
                    ports.append(port)
                await asyncio.gather(*[
                    _wait_ready(session, f"http://127.0.0.1:{p}")
                    for p in ports])
                t0 = time.monotonic()
                while time.monotonic() - t0 < 60:
                    nodes = await (await client.get(
                        "/federation/nodes")).json()
                    if len(nodes) == n_members:
                        break
                    await asyncio.sleep(0.2)

                # compile warm-up so neither phase pays first-request
                # compiles. Round 1 (one request per member,
                # least-used rotation) compiles the full-prefill
                # variant; round 2 repeats each warm GROUP — the same
                # rotation lands repeat i on the member already
                # holding w{i}'s prefix, compiling the prefix-copy +
                # tail-prefill variant the measured prefix phase's
                # HITS dispatch (unwarmed, the first hit per member
                # pays a multi-second XLA compile inside the phase)
                for r in (0, 1):
                    for i in range(n_members):
                        await _chat_ttft(
                            client, max_tokens=4,
                            messages=_group_messages(f"w{i}", r))

                from localai_tfp_tpu.utils import fingerprint as fp

                def _gossiped() -> set:
                    have = set()
                    for n in fed.registry.nodes():
                        for h, _t in ((n.digest or {}).get("prefixes")
                                      or []):
                            have.add(h)
                    return have

                async def phase(strategy: str, tagset: str) -> dict:
                    fed.strategy = strategy
                    # settle: the engine's eviction value is LRU x
                    # length with SECOND-granular recency, so seeding
                    # immediately after the previous phase's traffic
                    # makes a just-touched leftover residue look more
                    # valuable than a sibling seed placed seconds ago
                    # — the last seed then evicts the first instead of
                    # the leftover. A few seconds of decay makes every
                    # leftover the unambiguous victim.
                    await asyncio.sleep(3.0)
                    # seed each group's prefix into some member's KV
                    want = set()
                    for g in range(groups):
                        msgs = _group_messages(f"{tagset}{g}", 0)
                        # the shared (system-message) boundary hash —
                        # what every repeat in the group will match
                        h = fp.chain_from_body(
                            {"model": "tiny", "messages": msgs})[0][0]
                        want.add(h)
                        await _chat_ttft(client, max_tokens=4,
                                         messages=msgs)
                    # wait for the probe loop to gossip every seeded
                    # prefix (both phases, so traffic stays symmetric)
                    t0 = time.monotonic()
                    while time.monotonic() - t0 < 15.0 \
                            and not want <= _gossiped():
                        await asyncio.sleep(0.2)
                    before = dict(fed.route_stats)
                    ttfts = []
                    for r in range(1, repeats + 1):
                        for g in range(groups):
                            ttfts.append(await _chat_ttft(
                                client, max_tokens=4,
                                messages=_group_messages(
                                    f"{tagset}{g}", r)))
                        if r < repeats:
                            # think-time >= one probe round between
                            # rounds (both phases, symmetric): any
                            # residency change a round caused reaches
                            # the gossiped digests before the next
                            # round routes on them — back-to-back
                            # rounds outrun the probe loop and a group
                            # that lost residency would miss every
                            # remaining repeat instead of recovering
                            await asyncio.sleep(probe_s + 0.3)
                    delta = {k: fed.route_stats[k] - before[k]
                             for k in before}
                    ttfts.sort()
                    n = len(ttfts)
                    return {
                        "strategy": strategy,
                        "route_stats": delta,
                        "repeat_requests": n,
                        "ttft_p50_s": round(ttfts[n // 2], 4),
                        "ttft_p95_s": round(
                            ttfts[min(n - 1,
                                      math.ceil(0.95 * n) - 1)], 4),
                    }

                blind = await phase("least-used", "a")
                prefix = await phase("prefix", "b")
                out["blind"] = blind
                out["prefix"] = prefix
                routed = sum(prefix["route_stats"].values())
                hits = prefix["route_stats"]["hit"]
                out["prefix_hit_rate"] = round(
                    hits / max(1, routed), 3)
                out["prefix_hit_rate_gt_half"] = \
                    hits / max(1, routed) > 0.5
                out["locality_ttft_gain_s"] = round(
                    blind["ttft_p50_s"] - prefix["ttft_p50_s"], 4)
                out["locality_beats_blind"] = \
                    prefix["ttft_p50_s"] < blind["ttft_p50_s"]
                # blind phase must stay locality-blind end to end
                out["blind_phase_scored"] = \
                    blind["route_stats"]["hit"] \
                    + blind["route_stats"]["stale"]
            finally:
                await session.close()
                await client.close()
    finally:
        for m in members:
            m.terminate()
        for m in members:
            try:
                m.wait(timeout=10)
            except Exception:
                m.kill()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


# --------------------------------------------------------------------
# --autoscale: burst -> warmup-reuse replica boot -> drain -> kill
# --------------------------------------------------------------------

_AUTOSCALE_ENV = dict(
    _SMOKE_ENV,
    LOCALAI_SCALE_UP_QW_MS="80",
    LOCALAI_SCALE_HYSTERESIS="1",
    LOCALAI_SCALE_COOLDOWN_S="4",
    LOCALAI_SCALE_MIN="1",
    LOCALAI_SCALE_MAX="2",
    # generous floors so an idle tiny-model fleet qualifies for
    # scale-down the moment the burst drains
    LOCALAI_SCALE_DOWN_OCC="0.9",
    LOCALAI_SCALE_DOWN_MFU="0.9",
    LOCALAI_SCALE_DRAIN_TIMEOUT_S="30",
)


def _make_subprocess_driver(models_dir: str, tmp: str, token: str):
    """A real ScaleDriver: scale-up boots another member subprocess
    (same warmup-reuse fast-boot env as _spawn_member), scale-down
    terminates the victim's process. Records timings + the victim's
    in-flight count at kill time for the drain-before-kill check."""
    from localai_tfp_tpu.parallel.autoscale import ScaleDriver

    class SubprocessScaleDriver(ScaleDriver):
        mutates = True

        def __init__(self):
            self.balancer_url = None  # set once the app is listening
            self.procs: dict = {}  # advertise url -> Popen
            self.up_times: list = []
            self.down_times: list = []
            self.down_inflight: list = []
            self._n = 0

        def adopt(self, url: str, proc) -> None:
            self.procs[url] = proc

        def scale_up(self, count: int) -> None:
            for _ in range(count):
                self._n += 1
                port = _free_port()
                cwd = os.path.join(tmp, f"scale{self._n}")
                os.makedirs(cwd, exist_ok=True)
                proc = _spawn_member(
                    models_dir, cwd, port,
                    balancer_url=self.balancer_url, token=token,
                    name=f"scale-{self._n}")
                self.procs[f"http://127.0.0.1:{port}"] = proc
                self.up_times.append(time.monotonic())

        def scale_down(self, node) -> None:
            self.down_times.append(time.monotonic())
            self.down_inflight.append(node.in_flight)
            proc = self.procs.pop(node.address, None)
            if proc is not None:
                proc.terminate()

    return SubprocessScaleDriver()


async def autoscale_leg(probe_s: float = 2.0,
                        burst: int = 10) -> dict:
    """One member + the subprocess ScaleDriver: a queue burst must boot
    a second replica within ~2 probe intervals of the signal landing,
    and the post-burst idle fleet must drain (victim out of rotation,
    zero in-flight at kill) before the process dies."""
    import aiohttp
    from aiohttp.test_utils import TestClient, TestServer

    from localai_tfp_tpu.parallel.federated import (
        FederatedServer, generate_token,
    )

    saved = {k: os.environ.get(k) for k in _AUTOSCALE_ENV}
    os.environ.update(_AUTOSCALE_ENV)
    out: dict = {"probe_s": probe_s, "burst": burst}
    driver = None
    try:
        with tempfile.TemporaryDirectory() as tmp:
            models = os.path.join(tmp, "models")
            os.makedirs(models)
            _make_models(models)
            tok = generate_token()
            driver = _make_subprocess_driver(models, tmp, tok)
            fed = FederatedServer(tok, probe_s=probe_s,
                                  scale_driver=driver)
            client = TestClient(TestServer(fed.build_app()))
            await client.start_server()
            driver.balancer_url = \
                f"http://127.0.0.1:{client.server.port}"
            session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=10))
            try:
                port = _free_port()
                cwd = os.path.join(tmp, "member0")
                os.makedirs(cwd)
                base = _spawn_member(
                    models, cwd, port,
                    balancer_url=driver.balancer_url, token=tok,
                    name="base-0")
                driver.adopt(f"http://127.0.0.1:{port}", base)
                await _wait_ready(session, f"http://127.0.0.1:{port}")
                t0 = time.monotonic()
                while time.monotonic() - t0 < 60:
                    nodes = await (await client.get(
                        "/federation/nodes")).json()
                    if len(nodes) == 1:
                        break
                    await asyncio.sleep(0.2)
                # warm BOTH slots concurrently: members boot with
                # LOCALAI_WARMUP=0, so the batch=2 decode shape
                # compiles on first use — unwarmed, that multi-second
                # compile pins both slots through the burst's first
                # wave and the reaction clock measures XLA, not the
                # autoscaler. Disarm the scale-up threshold while
                # warming (knobs read env live; the balancer is
                # in-process): if the warms arrive staggered, the
                # second's admission waits out the first's compile and
                # that wait would trip the threshold pre-burst. Two
                # probe rounds of idle after the warms fold their
                # queue-wait samples into the windowed diff's baseline
                # before re-arming.
                os.environ["LOCALAI_SCALE_UP_QW_MS"] = "0"
                await asyncio.gather(*[
                    _chat_ttft(client, f"warm {i}", max_tokens=4)
                    for i in range(2)])
                await asyncio.sleep(2 * probe_s + 0.3)
                os.environ["LOCALAI_SCALE_UP_QW_MS"] = \
                    _AUTOSCALE_ENV["LOCALAI_SCALE_UP_QW_MS"]

                # ---- burst: overflow the 2 decode slots ----
                # short decodes so slots RELEASE quickly: queue-wait
                # samples are recorded at admission, so the scale-up
                # signal can only appear in a digest once the first
                # burst requests have been admitted off the queue
                t_burst = time.monotonic()
                await asyncio.gather(*[
                    _chat_ttft(client, f"burst {i}", max_tokens=4)
                    for i in range(burst)])
                while (not driver.up_times
                       and time.monotonic() - t_burst < 30):
                    await asyncio.sleep(0.1)
                assert driver.up_times, \
                    "burst never triggered a scale-up"
                reaction = driver.up_times[0] - t_burst
                out["boot_reaction_s"] = round(reaction, 3)
                out["reaction_within_2_probes"] = \
                    reaction <= 2 * probe_s + 0.5
                out["replicas_desired_peak"] = fed.autoscaler.desired

                # the booted replica must register and serve
                t0 = time.monotonic()
                nodes = []
                while time.monotonic() - t0 < 180:
                    nodes = await (await client.get(
                        "/federation/nodes")).json()
                    if len(nodes) == 2:
                        break
                    await asyncio.sleep(0.3)
                out["replicas_after_boot"] = len(nodes)
                out["boot_to_serving_s"] = round(
                    time.monotonic() - driver.up_times[0], 1)

                # ---- idle: drain-before-kill scale-down ----
                saw_draining = False
                t0 = time.monotonic()
                while time.monotonic() - t0 < 90:
                    nodes = await (await client.get(
                        "/federation/nodes")).json()
                    saw_draining |= any(
                        n.get("draining") for n in nodes)
                    if len(nodes) == 1 and driver.down_times:
                        break
                    await asyncio.sleep(0.2)
                out["replicas_after_drain"] = len(nodes)
                out["victim_seen_draining"] = saw_draining
                out["victim_in_flight_at_kill"] = \
                    driver.down_inflight
                out["scale_down_after_drain"] = bool(
                    driver.down_times) and all(
                    n == 0 for n in driver.down_inflight)
                out["scale_events"] = {
                    f"{d}/{o}": n for (d, o), n in sorted(
                        fed.autoscaler.snapshot()["events"].items())}
                page = await (await client.get(
                    "/fleet/metrics")).text()
                out["desired_gauge_exported"] = \
                    "fleet_replicas_desired_count" in page
            finally:
                await session.close()
                await client.close()
    finally:
        if driver is not None:
            for proc in driver.procs.values():
                proc.terminate()
            for proc in driver.procs.values():
                try:
                    proc.wait(timeout=10)
                except Exception:
                    proc.kill()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--members", type=int, default=3)
    ap.add_argument("--requests", type=int, default=18)
    ap.add_argument("--probe-s", type=float, default=0.5)
    ap.add_argument("--routing", action="store_true",
                    help="run the prefix-locality routing A/B leg "
                         "instead of the digest-plane leg")
    ap.add_argument("--autoscale", action="store_true",
                    help="run the elastic-autoscaling leg instead of "
                         "the digest-plane leg")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CPU smoke settings (3 members, "
                         "12 requests; routing: 3 groups x 4 repeats)")
    ap.add_argument("--json", action="store_true",
                    help="compact one-line JSON output")
    args = ap.parse_args()
    if args.smoke:
        args.members, args.requests = 3, 12

    if args.routing or args.autoscale:
        report = {}
        if args.routing:
            report["routing"] = asyncio.run(routing_leg(
                n_members=args.members, probe_s=args.probe_s,
                repeats=3 if args.smoke else 6))
        if args.autoscale:
            report["autoscale"] = asyncio.run(autoscale_leg())
    else:
        report = asyncio.run(fleet_leg(
            n_members=args.members, probe_s=args.probe_s,
            n_requests=args.requests))
    # member servers are children forced onto the CPU (_spawn_member:
    # a chip has one owner); nothing below is a device number
    report["members_platform"] = "cpu"
    print(json.dumps(report) if args.json
          else json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
