"""Chaos profile: serving-survival numbers under injected faults.

Drives the robustness work end to end on a small CPU config and prints
one JSON report with the acceptance numbers the robustness PR tracks:

  engine leg (in-process LLMEngine):
    shed_rate              — fraction of a 4x-overcommit flood refused
                             at admission (bounded queue)
    retry_after_s          — backoff hint stamped on shed terminals
    deadline_queued/decode — both deadline stages observed terminally
    device_fault           — InjectedFault storm at engine.device_step:
                             terminal completeness + survived followup
    terminal_completeness  — EVERY submitted stream ended in exactly
                             one terminal event (the core contract)

  disagg leg (prefill + decode engines under the migration relay):
    migrate_fault / handoff_fault / device_fault storms against the
    disagg.migrate and disagg.handoff injection points and the shared
    device-step funnel: every request must still end in exactly one
    terminal (served, graceful re-prefill fallback, or error), a calm
    followup must be served, and both KV pools PLUS the host
    interchange must come out leak-clean

  gallery leg (one paged engine, engine/weight_pager.py):
    faults on the weights.demote D2H page-out (the model must stay hot
    and keep serving) and on the weights.fetch H2D layer stream (the
    promotion must fall back to one cold blocking load and the request
    still serve, with exactly one terminal event). Pager accounting
    must come out leak-clean after both storms.

  federation leg (balancer + 2 member instances over localhost HTTP):
    failover_latency_s     — kill a member; time until the breaker
                             opens via the active /healthz probe
                             (contract: < 2 s, vs STALE_S=60 passive)
    rerouted_ok            — connect-failure retry served the request
                             from the surviving node

  tracing leg (in-process balancer + ONE REAL server subprocess):
    an injected federated.upstream fault forces a reroute while a
    client-minted traceparent rides the request; the report joins the
    balancer's proxy trace (fault delivery + retry + terminal as span
    events) with the member process's /debug/traces?id= entry — one
    trace id spanning both processes.

Run:  python tools/profile_chaos.py [--flood N] [--probe-s S]

CPU smoke (tiny model, fast settings — what CI can afford):

  python tools/profile_chaos.py --smoke
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _build_engine(n_slots=4, max_seq=128):
    from localai_tfp_tpu.engine.engine import LLMEngine
    from localai_tfp_tpu.engine.tokenizer import ByteTokenizer
    from localai_tfp_tpu.models.llm_spec import tiny_spec
    from localai_tfp_tpu.models.transformer import init_params

    tk = ByteTokenizer()
    spec = tiny_spec(vocab_size=tk.vocab_size, max_position=512)
    params = init_params(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    eng = LLMEngine(spec, params, tk, n_slots=n_slots, max_seq=max_seq,
                    prefill_buckets=(8, 32, 128), cache_dtype=jnp.float32)
    return eng, tk


def _drain(q, timeout=120):
    """(n_terminal_events, final). n_terminal MUST come out 1."""
    n_term, final = 0, None
    while final is None:
        ev = q.get(timeout=timeout)
        if ev.done:
            n_term, final = n_term + 1, ev
    # anything after the terminal breaks the exactly-once contract
    time.sleep(0.02)
    try:
        while True:
            if q.get_nowait().done:
                n_term += 1
    except Exception:
        pass
    return n_term, final


def engine_leg(flood: int) -> dict:
    from localai_tfp_tpu.engine.engine import GenRequest
    from localai_tfp_tpu.utils import faultinject as fi

    eng, tk = _build_engine()
    out: dict = {}
    complete = True
    try:
        # warm the jit paths so timings below measure policy, not compile
        eng.generate(GenRequest(prompt_ids=tk.encode("warm"), max_tokens=4,
                                ignore_eos=True))

        # ---- bounded-admission flood: 4x overcommit ----
        eng.max_queue = max(1, flood // 4)
        reqs = [GenRequest(prompt_ids=tk.encode(f"flood {i}"), max_tokens=4,
                           ignore_eos=True) for i in range(flood)]
        t0 = time.perf_counter()
        qs = eng.submit_many(reqs)
        finals = []
        for q in qs:
            n, ev = _drain(q)
            complete &= n == 1
            finals.append(ev)
        shed = [f for f in finals if f.finish_reason == "shed"]
        out["flood_requests"] = flood
        out["max_queue"] = eng.max_queue
        out["shed_rate"] = round(len(shed) / flood, 3)
        out["retry_after_s"] = (round(shed[0].retry_after_s, 2)
                                if shed else None)
        out["flood_wall_s"] = round(time.perf_counter() - t0, 3)
        eng.max_queue = 0

        # ---- deadlines: queued + mid-decode stage ----
        n, ev = _drain(eng.submit(GenRequest(
            prompt_ids=tk.encode("late"), max_tokens=4, ignore_eos=True,
            timeout_s=1e-6)))
        complete &= n == 1
        out["deadline_queued"] = ev.finish_reason == "deadline_exceeded"
        fi.arm("engine.device_step:delay@80")
        n, ev = _drain(eng.submit(GenRequest(
            prompt_ids=tk.encode("slow"), max_tokens=120, ignore_eos=True,
            timeout_s=0.5)))
        fi.disarm()
        complete &= n == 1
        out["deadline_decode"] = (ev.finish_reason == "deadline_exceeded"
                                  and 0 < ev.completion_tokens < 120)

        # ---- device-step fault storm, then a clean followup ----
        fi.arm("engine.device_step:rate@0.3@11")
        reasons: list[str] = []
        for i in range(8):
            n, ev = _drain(eng.submit(GenRequest(
                prompt_ids=tk.encode(f"storm {i}"), max_tokens=6,
                ignore_eos=True)))
            complete &= n == 1
            reasons.append(ev.finish_reason)
        injected = fi.counts()["engine.device_step"][1]
        fi.disarm()
        ev = eng.generate(GenRequest(prompt_ids=tk.encode("calm"),
                                     max_tokens=4, ignore_eos=True))
        out["device_fault"] = {
            "injected": injected,
            "errored": reasons.count("error"),
            "served": reasons.count("length"),
            "survived_followup": ev.finish_reason == "length",
        }
        out["terminal_completeness"] = complete
        if eng._pool is not None:
            eng._pool.leak_check()
            out["kv_pool_leak_check"] = "clean"
    finally:
        eng.close()
    return out


def disagg_leg(flood: int) -> dict:
    """Chaos on the disaggregated relay: migration-capture faults,
    handoff faults, and a device-step storm across BOTH engines — every
    request must still end in exactly one terminal (served, fallback
    re-prefill, or error), and both pools plus the host interchange
    must come out leak-clean."""
    import jax.numpy as jnp

    from localai_tfp_tpu.engine.engine import GenRequest
    from localai_tfp_tpu.engine.kv_migrate import (DisaggRouter,
                                                   build_prefill_engine)
    from localai_tfp_tpu.utils import faultinject as fi

    saved = {k: os.environ.get(k) for k in
             ("LOCALAI_DISAGG_MIN_PROMPT", "LOCALAI_KV_PAGE")}
    os.environ["LOCALAI_DISAGG_MIN_PROMPT"] = "32"
    # 16-token pages: the default 256-token page sizes the pool at
    # exactly one page per slot, so staging an adoption would always
    # hit pool exhaustion and the leg would only ever measure fallbacks
    os.environ.setdefault("LOCALAI_KV_PAGE", "16")
    eng, tk = _build_engine(max_seq=256)
    prefill = build_prefill_engine(eng.spec, eng.params, tk, decode=eng,
                                   cache_dtype=jnp.float32)
    router = DisaggRouter(prefill, eng)
    router.start()
    out: dict = {}
    long = "disagg chaos probe " + "x " * 24

    def storm(tag: str) -> list:
        reqs = [GenRequest(prompt_ids=tk.encode(f"{tag} {i:02d} " + long),
                           max_tokens=4, ignore_eos=True)
                for i in range(flood)]
        reasons = []
        for q in router.submit_many(reqs):
            n, ev = _drain(q)
            nonlocal_complete[0] &= n == 1
            reasons.append(ev.finish_reason)
        return reasons

    nonlocal_complete = [True]
    try:
        # warm the relay (compiles + a clean adoption)
        ev = router.generate(GenRequest(prompt_ids=tk.encode("w " + long),
                                        max_tokens=4, ignore_eos=True))
        assert ev.finish_reason == "length", ev.error

        legs = {
            "migrate_fault": "disagg.migrate:rate@0.5@3",
            "handoff_fault": "disagg.handoff:rate@0.5@5",
            "device_fault": "engine.device_step:rate@0.2@13",
        }
        for name, spec in legs.items():
            fb0 = eng._migrator.counters["adoptions"]
            fi.arm(spec)
            reasons = storm(name)
            injected = {p: c[1] for p, c in fi.counts().items()}
            fi.disarm()
            out[name] = {
                "injected": injected,
                "reasons": {r: reasons.count(r) for r in set(reasons)},
                "served_or_errored": all(
                    r in ("length", "error", "stop") for r in reasons),
                "adoptions": eng._migrator.counters["adoptions"] - fb0,
            }
        # a clean followup proves both engines survived the storms
        ev = router.generate(GenRequest(prompt_ids=tk.encode("calm " + long),
                                        max_tokens=4, ignore_eos=True))
        out["survived_followup"] = ev.finish_reason == "length"
        out["terminal_completeness"] = nonlocal_complete[0]
        out["fallbacks"] = router.prefill._migrator.counters[
            "capture_faults"]
        time.sleep(0.3)
        eng._pool.leak_check()
        prefill._pool.leak_check()
        assert router.bus.live_blocks() == 0, "interchange leak"
        out["kv_pool_leak_check"] = "clean"
        out["interchange_leak_check"] = "clean"
    finally:
        fi.disarm()
        router.close()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


def gallery_leg() -> dict:
    """Chaos on the weight pager: a demote fault must leave the model
    hot and serving; a fetch fault mid-promotion must fall back to one
    cold blocking load with the request still served — exactly one
    terminal event either way, and the pager leak-clean after both."""
    from localai_tfp_tpu.engine.engine import GenRequest
    from localai_tfp_tpu.utils import faultinject as fi

    saved = os.environ.get("LOCALAI_WEIGHT_PAGING")
    os.environ["LOCALAI_WEIGHT_PAGING"] = "on"
    eng, tk = _build_engine()
    out: dict = {}

    def demote_now(timeout=30.0):
        t0 = time.monotonic()
        while not eng._pager.request_demote():
            if time.monotonic() - t0 > timeout:
                raise TimeoutError("engine never went quiet")
            time.sleep(0.01)
        assert eng._pager.settle(timeout)

    try:
        pager = eng._pager
        ev = eng.generate(GenRequest(prompt_ids=tk.encode("warm"),
                                     max_tokens=4, ignore_eos=True))
        assert ev.finish_reason == "length", ev.error

        # ---- fault on the D2H page-out: abandon, stay hot, serve ----
        fi.arm("weights.demote:fail@1")
        demote_now()
        fi.disarm()
        n, ev = _drain(eng.submit(GenRequest(
            prompt_ids=tk.encode("after demote fault"), max_tokens=4,
            ignore_eos=True)))
        out["demote_fault"] = {
            "stayed_hot": pager.state == "hot"
            and eng.params is not None,
            "faulted_demotes": pager.counters["faulted_demotes"],
            "served": ev.finish_reason == "length" and n == 1,
        }

        # ---- fault on the H2D layer stream: cold fallback, serve ----
        demote_now()
        assert pager.state == "warm" and eng.params is None
        fi.arm("weights.fetch:fail@1")
        n, ev = _drain(eng.submit(GenRequest(
            prompt_ids=tk.encode("after fetch fault"), max_tokens=4,
            ignore_eos=True)))
        fi.disarm()
        out["fetch_fault"] = {
            "cold_fallbacks": pager.counters["cold_fallbacks"],
            "promoted_hot": pager.state == "hot",
            "served": ev.finish_reason == "length",
            "one_terminal": n == 1,
        }
        pager.leak_check()
        out["pager_leak_check"] = "clean"
        out["stats"] = pager.stats()
    finally:
        fi.disarm()
        eng.close()
        if saved is None:
            os.environ.pop("LOCALAI_WEIGHT_PAGING", None)
        else:
            os.environ["LOCALAI_WEIGHT_PAGING"] = saved
    return out


def _spawn_member(models_dir: str, cwd: str, port: int):
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("LOCALAI_FAULTS", None)  # faults stay balancer-side here
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    return subprocess.Popen(
        [sys.executable, "-m", "localai_tfp_tpu.cli", "run",
         "--models-path", models_dir, "--address", "127.0.0.1",
         "--port", str(port)],
        cwd=cwd, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT)


async def tracing_leg() -> dict:
    """One trace id across two processes: an in-process balancer (with
    an injected upstream fault forcing a failover) proxying to a REAL
    server subprocess, joined by ``/debug/traces?id=``."""
    import socket
    import tempfile
    import urllib.request

    from aiohttp.test_utils import TestClient, TestServer

    from localai_tfp_tpu.parallel.federated import (
        FederatedServer, generate_token,
    )
    from localai_tfp_tpu.telemetry.tracing import (
        TRACER, make_traceparent, mint_trace_id,
    )
    from localai_tfp_tpu.utils import faultinject as fi

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    out: dict = {}
    member = None
    with tempfile.TemporaryDirectory() as tmp:
        models = os.path.join(tmp, "models")
        cwd = os.path.join(tmp, "member")
        os.makedirs(models)
        os.makedirs(cwd)
        # zero-checkpoint config: the tts backend serves /v1/models
        # with no model files, so the member boots in seconds
        with open(os.path.join(models, "voice.yaml"), "w") as f:
            f.write("name: voice\nbackend: jax-tts\n")
        member = _spawn_member(models, cwd, port)
        try:
            base = f"http://127.0.0.1:{port}"
            t0 = time.time()
            while time.time() - t0 < 120:
                try:
                    urllib.request.urlopen(base + "/readyz", timeout=2)
                    break
                except Exception:
                    time.sleep(0.3)
            else:
                raise TimeoutError("member server never became ready")

            tok = generate_token()
            fed = FederatedServer(tok, probe_s=0.0)
            client = TestClient(TestServer(fed.build_app()))
            await client.start_server()
            try:
                # the SAME member registered under two node ids: the
                # injected first-attempt fault reroutes to "the other
                # node" and still lands — a failover that needs only
                # one real process
                for nid in ("m1", "m2"):
                    r = await client.post("/federation/register", json={
                        "token": tok, "id": nid, "name": nid,
                        "address": base})
                    assert r.status == 200

                fi.arm("federated.upstream:fail@1")
                tid = mint_trace_id()
                r = await client.get(
                    "/v1/models",
                    headers={"traceparent": make_traceparent(tid)})
                out["proxied_status"] = r.status
                out["echoed_traceparent"] = tid in r.headers.get(
                    "traceparent", "")
                fi.disarm()

                balancer = TRACER.lookup(tid)
                names = [n["name"] for tr in balancer
                         for n in tr.get("span_events", [])]
                points = [n.get("point") for tr in balancer
                          for n in tr.get("span_events", [])]
                with urllib.request.urlopen(
                        f"{base}/debug/traces?id={tid}",
                        timeout=10) as resp:
                    remote = json.loads(resp.read()).get("traces", [])
                out["trace_id"] = tid
                out["balancer_entries"] = len(balancer)
                out["fault_on_trace"] = "federated.upstream" in points
                out["failover_on_trace"] = "retry" in names
                out["member_entries"] = len(remote)
                out["member_joined_by_trace_id"] = all(
                    tr.get("trace_id") == tid for tr in remote) and bool(
                    remote)
                out["one_trace_id_both_processes"] = (
                    out["fault_on_trace"] and out["failover_on_trace"]
                    and out["member_joined_by_trace_id"])
            finally:
                fi.disarm()
                await client.close()
        finally:
            if member is not None:
                member.terminate()
                try:
                    member.wait(timeout=10)
                except Exception:
                    member.kill()
    return out


async def federation_leg(probe_s: float) -> dict:
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from localai_tfp_tpu.parallel.federated import (
        FederatedServer, generate_token,
    )

    async def handler(request):
        return web.json_response({"ok": True})

    def member():
        app = web.Application()
        app.router.add_route("*", "/{tail:.*}", handler)
        return TestServer(app)

    doomed, healthy = member(), member()
    await doomed.start_server()
    await healthy.start_server()
    tok = generate_token()
    fed = FederatedServer(tok, probe_s=probe_s)
    client = TestClient(TestServer(fed.build_app()))
    await client.start_server()
    out: dict = {"probe_s": probe_s}
    try:
        for nid, m in (("a-doomed", doomed), ("b-healthy", healthy)):
            r = await client.post("/federation/register", json={
                "token": tok, "id": nid, "name": nid,
                "address": f"http://127.0.0.1:{m.port}"})
            assert r.status == 200

        # kill a member: how long until the breaker routes around it?
        t0 = time.monotonic()
        await doomed.close()
        node = fed.registry._nodes["a-doomed"]
        while (fed.registry.state(node) != "open"
               and time.monotonic() - t0 < 10.0):
            await asyncio.sleep(0.02)
        opened = fed.registry.state(node) == "open"
        out["failover_latency_s"] = (round(time.monotonic() - t0, 2)
                                     if opened else None)
        out["failover_under_2s"] = opened and out["failover_latency_s"] < 2

        # connect-failure retry: the request lands on the survivor even
        # if the balancer tries the corpse first
        r = await client.post("/v1/models", data=b"x")
        out["rerouted_ok"] = (r.status == 200
                              and fed.registry._nodes[
                                  "b-healthy"].requests_served >= 1)
    finally:
        await client.close()
        await healthy.close()
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--flood", type=int, default=32,
                    help="flood size for the bounded-admission leg")
    ap.add_argument("--probe-s", type=float, default=0.1,
                    help="active /healthz probe interval for the "
                         "failover-latency leg")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CPU smoke settings (flood=12)")
    args = ap.parse_args()
    if args.smoke:
        args.flood = 12

    report = {
        "engine": engine_leg(args.flood),
        "disagg": disagg_leg(max(4, args.flood // 4)),
        "gallery": gallery_leg(),
        "federation": asyncio.run(federation_leg(args.probe_s)),
        "tracing": asyncio.run(tracing_leg()),
        # member servers are children forced onto the CPU
        # (_spawn_member: a chip has one owner)
        "members_platform": "cpu",
    }
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
