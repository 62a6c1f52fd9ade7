"""LocalAI-native endpoints.

Ref: core/http/routes/localai.go — /tts, /vad, /rerank (jina), stores,
/metrics, backend monitor/shutdown, /system, /version, health
(routes/health.go), ElevenLabs adapters (routes/elevenlabs.go).
Gallery REST lands with the gallery service.
"""

from __future__ import annotations

import asyncio
import json
import time

from aiohttp import web

from ..config.model_config import Usecase
from ..version import __version__
from ..workers.base import PredictOptions
from . import schema
from .common import WORKER_POOL, run_blocking
from .state import Application


def register(app: web.Application) -> None:
    r = app.router
    r.add_get("/healthz", health)
    r.add_get("/readyz", health)
    r.add_get("/version", version)
    r.add_get("/metrics", metrics)
    r.add_get("/telemetry/digest", telemetry_digest)
    r.add_get("/debug/traces", debug_traces)
    r.add_get("/debug/timeline", debug_timeline)
    r.add_get("/debug/profile", debug_profile)
    r.add_get("/system", system)
    r.add_get("/backend/monitor", backend_monitor)
    r.add_post("/backend/shutdown", backend_shutdown)
    r.add_post("/tts", tts)
    for p in ("/vad", "/v1/vad"):
        r.add_post(p, vad)
    r.add_post("/v1/rerank", rerank)  # Jina-compatible (routes/jina.go)
    # ElevenLabs-compatible (routes/elevenlabs.go:19-28)
    r.add_post("/v1/text-to-speech/{voice_id}", tts_elevenlabs)
    r.add_post("/v1/sound-generation", sound_generation)
    for p in ("/stores/set", "/stores/delete", "/stores/get", "/stores/find"):
        r.add_post(p, stores_dispatch)
    # p2p/federation introspection (ref: routes/localai.go:79-82)
    r.add_get("/api/p2p", p2p_nodes)
    r.add_get("/api/p2p/token", p2p_token)
    r.add_post("/federation/register", federation_register)
    # gallery management (ref: routes/localai.go:27-38)
    r.add_post("/models/apply", models_apply)
    r.add_post("/models/delete/{name}", models_delete)
    r.add_get("/models/available", models_available)
    r.add_get("/models/galleries", models_galleries)
    r.add_post("/models/galleries", galleries_add)
    r.add_delete("/models/galleries", galleries_remove)
    r.add_get("/models/jobs/{uuid}", models_job)
    r.add_get("/models/jobs/{uuid}/stream", models_job_stream)
    r.add_get("/models/jobs", models_jobs)


def _state(request: web.Request) -> Application:
    return request.app["state"]


async def _body(request: web.Request) -> dict:
    try:
        data = await request.json()
    except Exception:
        raise web.HTTPBadRequest(reason="invalid JSON body")
    if not isinstance(data, dict):
        raise web.HTTPBadRequest(reason="body must be a JSON object")
    return data


async def health(request: web.Request) -> web.Response:
    return web.json_response({"status": "ok"})


async def version(request: web.Request) -> web.Response:
    return web.json_response({"version": __version__})


async def metrics(request: web.Request) -> web.Response:
    st = _state(request)
    if st.config.disable_metrics:
        raise web.HTTPNotFound()
    from ..telemetry.registry import CONTENT_TYPE, OPENMETRICS_CONTENT_TYPE

    # content negotiation: OpenMetrics (exemplars, # EOF) only when the
    # scraper asks for it; the default stays the 0.0.4 text format
    # byte-identical to what it always rendered
    om = "application/openmetrics-text" in request.headers.get(
        "Accept", "")
    return web.Response(
        body=st.metrics.render(openmetrics=om).encode("utf-8"),
        headers={"Content-Type": (OPENMETRICS_CONTENT_TYPE if om
                                  else CONTENT_TYPE)})


def _digest_caller_trusted(request: web.Request) -> bool:
    """The digest endpoint is auth-exempt so the balancer probe always
    reaches it, but the prefix top-k is derived from user PROMPT
    content — it only ships to callers that prove themselves: a valid
    API key, or the shared federation token (what the balancer's probe
    sends). With no API keys configured the whole server is open and
    the distinction is moot."""
    st = _state(request)
    keys = st.config.api_keys
    if not keys:
        return True
    auth = request.headers.get("Authorization", "")
    token = (auth[7:] if auth.startswith("Bearer ")
             else request.headers.get("x-api-key", ""))
    if token in keys:
        return True
    from ..parallel.federated import tokens_match

    return tokens_match(request.headers.get("X-Federation-Token", ""),
                        st.config.p2p_token)


async def telemetry_digest(request: web.Request) -> web.Response:
    """This node's mergeable telemetry digest (telemetry/digest.py) —
    what the federation balancer's probe loop fetches and the
    heartbeat attaches. Bounded JSON (LOCALAI_DIGEST_MAX_BYTES);
    collection reads host-held registry/scheduler values only, run off
    the event loop because it briefly takes each engine's lock.
    Anonymous callers get the digest minus the prompt-derived prefix
    top-k (see _digest_caller_trusted)."""
    st = _state(request)
    from ..telemetry import digest as dg

    payload = await run_blocking(dg.collect, st.model_loader)
    if not _digest_caller_trusted(request):
        payload = dict(payload, prefixes=[])
    return web.json_response(payload,
                             headers={"Cache-Control": "no-store"})


async def debug_traces(request: web.Request) -> web.Response:
    """Request-lifecycle timelines (telemetry/tracing.py): newest-first
    JSON, ``?model=`` filter, ``?limit=`` cap (default 50), ``?id=``
    point lookup by trace id / request id / correlation id / full
    traceparent header value. Pretty-printer: tools/trace_report.py."""
    from ..telemetry.tracing import TRACER

    try:
        limit = int(request.query.get("limit") or 50)
    except ValueError:
        raise web.HTTPBadRequest(reason="'limit' must be an integer")
    ident = request.query.get("id")
    # live debug state: a cached poll response shows a stale engine
    hdrs = {"Cache-Control": "no-store"}
    if ident:
        return web.json_response({
            "traces": TRACER.lookup(ident, limit=limit),
        }, headers=hdrs)
    return web.json_response({
        "traces": TRACER.traces(model=request.query.get("model") or None,
                                limit=limit),
    }, headers=hdrs)


async def debug_timeline(request: web.Request) -> web.Response:
    """The scheduler/device flight recorder as Chrome-trace JSON
    (telemetry/flightrec.py) — save the body and open it in Perfetto
    (https://ui.perfetto.dev) or chrome://tracing; offline renderer:
    tools/trace_viewer.py. ``?limit=`` bounds the serialized event
    count (newest last — the ring is bounded, but a monitoring poll
    should not re-serialize all 8k events every few seconds)."""
    from ..telemetry.flightrec import FLIGHT

    trace = FLIGHT.export_chrome_trace()
    limit_q = request.query.get("limit")
    if limit_q:
        try:
            limit = max(0, int(limit_q))
        except ValueError:
            raise web.HTTPBadRequest(reason="'limit' must be an integer")
        ev = trace.get("traceEvents", [])
        if len(ev) > limit:
            trace = {**trace, "traceEvents": ev[-limit:] if limit else []}
    return web.json_response(trace,
                             headers={"Cache-Control": "no-store"})


# the single-capture gate for /debug/profile: jax.profiler supports one
# active trace per process, so concurrent captures get 409, not a crash
_PROFILE_LOCK = None  # created lazily (threading.Lock is importable at
# module scope, but keeping the gate with its handler reads clearer)


async def debug_profile(request: web.Request) -> web.Response:
    """On-demand, duration-bounded ``jax.profiler`` capture. Gated by
    LOCALAI_PROFILER (off by default: a capture costs real device/host
    overhead and writes to disk). ``?duration=`` seconds (clamped to
    LOCALAI_PROFILER_MAX_S), ``?download=1`` streams the capture dir
    back as a zip; otherwise the response names the path under
    ``state_dir`` for tensorboard/xprof."""
    import io
    import os
    import threading
    import zipfile

    from ..config import knobs

    global _PROFILE_LOCK
    if not knobs.flag("LOCALAI_PROFILER"):
        raise web.HTTPForbidden(
            reason="profiler disabled (set LOCALAI_PROFILER=on)")
    try:
        duration = float(request.query.get("duration") or 2.0)
    except ValueError:
        raise web.HTTPBadRequest(reason="'duration' must be a number")
    max_s = max(0.1, knobs.float_("LOCALAI_PROFILER_MAX_S"))
    duration = min(max(0.1, duration), max_s)
    if _PROFILE_LOCK is None:
        _PROFILE_LOCK = threading.Lock()
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise web.HTTPConflict(reason="a profile capture is already "
                                      "running")
    st = _state(request)
    logdir = os.path.join(st.config.state_dir, "profiles",
                          time.strftime("%Y%m%d-%H%M%S"))
    from ..telemetry import flightrec

    try:
        import jax

        os.makedirs(logdir, exist_ok=True)
        # both ends run on the worker pool: stop_trace writes the
        # capture, which takes seconds at serving scale, and on the
        # event loop it held every SSE stream for as long
        await run_blocking(jax.profiler.start_trace, logdir)
        # the scheduler's spans are TraceAnnotations exactly while the
        # profiler listens (flightrec.PhaseClock / LoadWatch)
        flightrec.set_capturing(True)
        t_start = time.perf_counter()
        try:
            await asyncio.sleep(duration)
        finally:
            t_stop = time.perf_counter()
            flightrec.set_capturing(False)
            await run_blocking(jax.profiler.stop_trace)
    except Exception as e:
        raise web.HTTPInternalServerError(
            reason=f"profiler capture failed: {e!r}")
    finally:
        _PROFILE_LOCK.release()
    if request.query.get("download"):
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
            for root, _dirs, files in os.walk(logdir):
                for fname in files:
                    full = os.path.join(root, fname)
                    zf.write(full, os.path.relpath(full, logdir))
        return web.Response(
            body=buf.getvalue(),
            headers={
                "Content-Type": "application/zip",
                "Content-Disposition": 'attachment; filename="%s.zip"'
                % os.path.basename(logdir),
                "Cache-Control": "no-store",
            })
    # perf_counter at both ends, against /debug/timeline's origin: a
    # timeline (microseconds since timeline_t0) lays beside the capture
    return web.json_response(
        {"path": logdir, "duration_s": duration,
         "perf_counter_start": t_start, "perf_counter_stop": t_stop,
         "timeline_t0": flightrec.origin()},
        headers={"Cache-Control": "no-store"})


async def system(request: web.Request) -> web.Response:
    """ref: endpoints/localai/system.go — loaded models + capabilities."""
    import jax

    from ..utils.sysinfo import device_memory

    st = _state(request)
    try:
        devs = [str(d) for d in jax.devices()]
    except RuntimeError:
        devs = []
    return web.json_response({
        "backends": sorted(
            set(__import__("localai_tfp_tpu.engine.loader",
                           fromlist=["registry"]).registry.known())
        ),
        "loaded_models": st.model_loader.loaded_names(),
        "devices": devs,
        # per-device HBM stats + model-fit surface (ref: pkg/xsysinfo
        # GPU/VRAM enumeration behind /system)
        "device_memory": device_memory(),
        "uptime_s": time.time() - st.started_at,
    })


async def backend_monitor(request: web.Request) -> web.Response:
    """ref: core/services/backend_monitor.go + endpoints /backend/monitor:
    per-model status + process memory/CPU (gopsutil equivalent via
    /proc; workers are in-process here, so process stats are the backend
    stats)."""
    import asyncio as _asyncio
    import os
    import resource

    st = _state(request)
    body = await _body(request) if request.can_read_body else {}
    name = body.get("model") or request.query.get("model")
    if not name:
        raise web.HTTPBadRequest(reason="model required")
    lm = st.model_loader.get(name)
    if lm is None:
        raise web.HTTPNotFound(reason=f"model '{name}' not loaded")
    status = lm.backend.status()
    rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    def cpu_times() -> float:
        r = resource.getrusage(resource.RUSAGE_SELF)
        return r.ru_utime + r.ru_stime

    t0, c0 = _asyncio.get_running_loop().time(), cpu_times()
    await _asyncio.sleep(0.1)
    dt = _asyncio.get_running_loop().time() - t0
    cpu_percent = 100.0 * (cpu_times() - c0) / max(dt, 1e-6)
    rss_now = 0
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    rss_now = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    return web.json_response({
        "memory_info": {"rss": rss_now or rss_kb * 1024,
                        "peak_rss": rss_kb * 1024,
                        **status.memory},
        "cpu_percent": round(cpu_percent, 2),
        "pid": os.getpid(),
        "status": status.state,
        "backend": lm.backend_type,
        "busy": lm.busy_since is not None,
        # cold-start observability (models/load_timing.py): where the
        # load's wall time went — read/dequant/transfer/compile/warmup
        "load_s": round(lm.load_s, 2),
        "load_breakdown": getattr(lm.backend, "load_breakdown",
                                  None) or None,
        # live serving-state snapshot (engine-backed models): queue
        # depth, slot occupancy, KV utilization, token counters
        "engine": (lm.backend.engine_stats()
                   if hasattr(lm.backend, "engine_stats") else None),
    })


async def backend_shutdown(request: web.Request) -> web.Response:
    st = _state(request)
    body = await _body(request)
    name = body.get("model")
    if not name:
        raise web.HTTPBadRequest(reason="model required")
    ok = st.model_loader.shutdown_model(name)
    if not ok:
        raise web.HTTPNotFound(reason=f"model '{name}' not loaded")
    return web.json_response({"success": True})


# ---------------------------------------------------------------- media


async def _tts_impl(request: web.Request, text: str, model_name,
                    voice: str, language: str = "") -> web.Response:
    st = _state(request)
    cfg = st.config_loader.resolve(model_name, Usecase.TTS)
    if cfg is None:
        raise web.HTTPNotFound(reason="no TTS model available")
    backend = await run_blocking(st.model_loader.load, cfg)
    import os
    import uuid as _uuid

    dst = os.path.join(st.config.generated_content_dir,
                       f"tts-{_uuid.uuid4().hex}.wav")
    res = await run_blocking(
        lambda: backend.tts(text=text, voice=voice or cfg.tts.voice,
                            dst=dst, language=language))
    if not res.success:
        raise web.HTTPInternalServerError(reason=res.message)
    return web.FileResponse(dst)


async def tts(request: web.Request) -> web.Response:
    """ref: routes/localai.go:41 POST /tts."""
    body = await _body(request)
    schema.TTSRequest.validate(body)
    return await _tts_impl(
        request, body.get("input", ""), body.get("model"),
        body.get("voice", ""), body.get("language", ""),
    )


async def tts_elevenlabs(request: web.Request) -> web.Response:
    """ref: elevenlabs/tts.go — voice id in path, model in body."""
    body = await _body(request)
    # same typed-400 contract as /tts: a non-string "text" must be a
    # schema error, not a 500 from deep inside the worker
    schema.TTSRequest.validate(body)
    return await _tts_impl(
        request, body.get("text", ""), body.get("model_id"),
        request.match_info["voice_id"],
    )


async def sound_generation(request: web.Request) -> web.Response:
    body = await _body(request)
    req = schema.SoundGenerationRequest.validate(body)
    st = _state(request)
    cfg = st.config_loader.resolve(body.get("model_id"),
                                   Usecase.SOUND_GENERATION)
    if cfg is None:
        raise web.HTTPNotFound(reason="no sound-generation model available")
    backend = await run_blocking(st.model_loader.load, cfg)
    import os
    import uuid as _uuid

    dst = os.path.join(st.config.generated_content_dir,
                       f"sound-{_uuid.uuid4().hex}.wav")
    res = await run_blocking(lambda: backend.sound_generation(
            text=req.text, dst=dst,
            duration=req.duration,
            temperature=1.0 if req.temperature is None
            else req.temperature,
            # explicit temperature 0 means deterministic, not "unset"
            do_sample=body.get("do_sample",
                               req.temperature is None
                               or req.temperature > 0),
        ))
    if not res.success:
        raise web.HTTPInternalServerError(reason=res.message)
    return web.FileResponse(dst)


async def vad(request: web.Request) -> web.Response:
    """ref: routes/localai.go:46-52; endpoints/localai/vad.go."""
    body = await _body(request)
    st = _state(request)
    cfg = st.config_loader.resolve(body.get("model"), Usecase.VAD)
    if cfg is None:
        raise web.HTTPNotFound(reason="no VAD model available")
    backend = await run_blocking(st.model_loader.load, cfg)
    res = await run_blocking(backend.vad, body.get("audio") or [])
    return web.json_response({
        "segments": [{"start": s.start, "end": s.end} for s in res.segments]
    })


async def rerank(request: web.Request) -> web.Response:
    """ref: jina/rerank.go — Jina-compatible POST /v1/rerank."""
    body = await _body(request)
    schema.RerankRequest.validate(body)
    st = _state(request)
    cfg = st.config_loader.resolve(body.get("model"), Usecase.RERANK)
    if cfg is None:
        raise web.HTTPNotFound(reason="no rerank model available")
    backend = await run_blocking(st.model_loader.load, cfg)
    docs = body.get("documents") or []
    res = await run_blocking(backend.rerank, body.get("query", ""),
                             docs, int(body.get("top_n") or len(docs)))
    return web.json_response({
        "model": cfg.name,
        "usage": res.usage,
        "results": [
            {"index": d.index, "relevance_score": d.relevance_score,
             "document": {"text": d.text}}
            for d in res.results
        ],
    })


# ------------------------------------------------------------ federation


async def p2p_nodes(request: web.Request) -> web.Response:
    """ref: endpoints/localai/p2p.go ShowP2PNodes — swarm members."""
    st = _state(request)
    nodes = []
    if st.registry is not None:
        nodes = [
            {"id": n.id, "name": n.name, "address": n.address,
             "online": n.online(), "requests_served": n.requests_served}
            for n in st.registry.nodes()
        ]
    return web.json_response({
        "enabled": st.registry is not None,
        "nodes": nodes,
    })


async def p2p_token(request: web.Request) -> web.Response:
    """ref: endpoints/localai/p2p.go ShowP2PToken."""
    return web.json_response({"token": _state(request).config.p2p_token})


async def federation_register(request: web.Request) -> web.Response:
    """Accept worker announcements when this instance carries a token —
    every instance can act as a registry (the gossip-ledger analogue)."""
    st = _state(request)
    if st.registry is None:
        raise web.HTTPNotFound(reason="federation not enabled")
    body = await _body(request)
    ok = st.registry.announce(
        body.get("token", ""), body.get("id", ""), body.get("name", ""),
        body.get("address", ""), digest=body.get("digest"))
    if not ok:
        raise web.HTTPUnauthorized(reason="bad federation token")
    from ..parallel.federated import HEARTBEAT_S

    return web.json_response({"ok": True, "heartbeat_s": HEARTBEAT_S})


# --------------------------------------------------------------- gallery


async def models_apply(request: web.Request) -> web.Response:
    """ref: endpoints/localai/gallery.go ApplyModelGalleryEndpoint —
    body: {id: "gallery@model"} or {url: config-url}, optional overrides;
    returns {uuid, status} with the job-status poll URL."""
    from ..gallery.service import GalleryOp

    st = _state(request)
    body = await _body(request)
    op = GalleryOp(
        gallery_model_name=body.get("id") or body.get("name") or "",
        config_url=body.get("url") or body.get("config_url") or "",
        overrides=body.get("overrides") or {},
    )
    if not op.gallery_model_name and not op.config_url:
        raise web.HTTPBadRequest(reason="'id' or 'url' required")
    job = st.gallery.submit(op, config_loader=st.config_loader)
    return web.json_response(
        {"uuid": job, "status": f"/models/jobs/{job}"})


async def models_delete(request: web.Request) -> web.Response:
    from ..gallery.service import GalleryOp

    st = _state(request)
    name = request.match_info["name"]
    st.model_loader.shutdown_model(name)
    job = st.gallery.submit(
        GalleryOp(gallery_model_name=name, delete=True),
        config_loader=st.config_loader,
    )
    return web.json_response(
        {"uuid": job, "status": f"/models/jobs/{job}"})


async def models_available(request: web.Request) -> web.Response:
    st = _state(request)
    models = await run_blocking(st.gallery.available_models)
    return web.json_response([
        {
            "name": m.name, "description": m.description,
            "license": m.license, "urls": m.urls, "tags": m.tags,
            "gallery": {"name": m.gallery_name}, "installed": m.installed,
        }
        for m in models
    ])


async def models_galleries(request: web.Request) -> web.Response:
    return web.json_response(_state(request).gallery.galleries)


async def galleries_add(request: web.Request) -> web.Response:
    st = _state(request)
    body = await _body(request)
    if not body.get("url"):
        raise web.HTTPBadRequest(reason="'url' required")
    st.gallery.galleries.append(
        {"name": body.get("name", ""), "url": body["url"]})
    st.gallery.invalidate_index()
    return web.json_response(st.gallery.galleries)


async def galleries_remove(request: web.Request) -> web.Response:
    st = _state(request)
    body = await _body(request)
    st.gallery.galleries = [
        g for g in st.gallery.galleries
        if g.get("name") != body.get("name") and g.get("url") != body.get("url")
    ]
    st.gallery.invalidate_index()
    return web.json_response(st.gallery.galleries)


async def models_job(request: web.Request) -> web.Response:
    st = _state(request)
    status = st.gallery.status(request.match_info["uuid"])
    if status is None:
        raise web.HTTPNotFound(reason="no such job")
    return web.json_response({
        "deletion": status.deletion, "file_name": status.file_name,
        "error": status.error or None, "processed": status.processed,
        "message": status.message, "progress": status.progress,
        "gallery_model_name": status.gallery_model_name,
    })


async def models_job_stream(request: web.Request) -> web.StreamResponse:
    """SSE job progress (ref: the reference's browse UI streams install
    progress over SSE — routes/ui.go job progress)."""
    st = _state(request)
    jid = request.match_info["uuid"]
    if st.gallery.status(jid) is None:
        raise web.HTTPNotFound(reason="no such job")
    resp = web.StreamResponse()
    resp.headers["Content-Type"] = "text/event-stream"
    resp.headers["Cache-Control"] = "no-cache"
    await resp.prepare(request)
    try:
        while True:
            s = st.gallery.status(jid)
            payload = {
                "processed": s.processed, "progress": s.progress,
                "error": s.error or None, "message": s.message,
            }
            await resp.write(
                b"data: " + json.dumps(payload).encode() + b"\n\n")
            if s.processed:
                break
            await asyncio.sleep(0.5)
        await resp.write_eof()
    except (ConnectionResetError, ConnectionError):
        pass  # client went away mid-install: a routine event, not an error
    return resp


async def models_jobs(request: web.Request) -> web.Response:
    st = _state(request)
    return web.json_response({
        jid: {"processed": s.processed, "progress": s.progress,
              "error": s.error or None, "message": s.message}
        for jid, s in st.gallery.all_status().items()
    })


# ---------------------------------------------------------------- stores


async def stores_dispatch(request: web.Request) -> web.Response:
    """ref: routes/localai.go:55-58 + endpoints/localai/stores.go — proxies
    to the local-store backend."""
    st = _state(request)
    body = await _body(request)
    cfg = st.config_loader.resolve(body.get("store") or "default-store",
                                   Usecase.ANY)
    if cfg is None:
        from ..config.model_config import ModelConfig

        cfg = ModelConfig.from_dict(
            {"name": body.get("store") or "default-store",
             "backend": "local-store"}
        )
        st.config_loader.register(cfg)
    backend = await run_blocking(st.model_loader.load, cfg)
    op = request.path.rsplit("/", 1)[-1]
    if op == "set":
        backend.stores_set(body.get("keys") or [], body.get("values") or [])
        return web.json_response({})
    if op == "delete":
        backend.stores_delete(body.get("keys") or [])
        return web.json_response({})
    if op == "get":
        keys, values = backend.stores_get(body.get("keys") or [])
        return web.json_response({"keys": keys, "values": values})
    keys, values, sims = backend.stores_find(
        body.get("key") or [], int(body.get("topk") or 10)
    )
    return web.json_response(
        {"keys": keys, "values": values, "similarities": sims}
    )
