"""Cross-process trace propagation (telemetry/tracing.py §distributed).

One trace id must join every hop of a federated, multi-host serving
path: the HTTP edge adopts/mints W3C ``traceparent``, the balancer
forwards it to the member it picks, the multihost leader stamps it on
dispatch-record envelopes so follower replays emit joined entries, and
armed faultinject deliveries land as span events on the traces in
scope. The reference exposes /debug + Prometheus with no cross-process
joining at all (SURVEY.md §2.5); these tests pin the join behavior
in-process so the distributed paths can't silently regress.
"""

import asyncio
import time

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from localai_tfp_tpu.telemetry.flightrec import FLIGHT
from localai_tfp_tpu.telemetry.tracing import (
    TRACER, make_traceparent, mint_trace_id, new_span_id,
    parse_traceparent,
)


# ------------------------------------------------- traceparent helpers


def test_traceparent_roundtrip():
    tid = mint_trace_id()
    span = new_span_id()
    parsed = parse_traceparent(make_traceparent(tid, span))
    assert parsed == (tid, span)


def test_traceparent_rejects_malformed():
    assert parse_traceparent("") is None
    assert parse_traceparent("garbage") is None
    # wrong lengths
    assert parse_traceparent("00-abc-def-01") is None
    # non-hex
    assert parse_traceparent(
        "00-" + "z" * 32 + "-" + "a" * 16 + "-01") is None
    # all-zero ids are invalid per W3C trace context
    assert parse_traceparent(
        "00-" + "0" * 32 + "-" + "a" * 16 + "-01") is None
    assert parse_traceparent(
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01") is None
    # a valid header parses case-insensitively
    tid = "AB" * 16
    assert parse_traceparent(f"00-{tid}-{'cd' * 8}-01") == \
        (tid.lower(), "cd" * 8)


# ------------------------------------------ HTTP edge adoption + lookup


@pytest.fixture(scope="module")
def app_client(tmp_path_factory):
    from localai_tfp_tpu.config.app_config import ApplicationConfig
    from localai_tfp_tpu.server.app import build_app
    from localai_tfp_tpu.server.state import Application

    root = tmp_path_factory.mktemp("tracing-srv")
    (root / "models").mkdir()
    loop = asyncio.new_event_loop()
    cfg = ApplicationConfig(
        models_path=str(root / "models"),
        generated_content_dir=str(root / "generated"),
        upload_dir=str(root / "uploads"),
        config_dir=str(root / "configuration"),
    )
    state = Application(cfg)
    app = build_app(state)
    tc = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(tc.start_server())

    def get(path, **kw):
        async def go():
            r = await tc.request("GET", path, **kw)
            body = await r.json()
            return r.status, r.headers, body

        return loop.run_until_complete(go())

    yield get
    loop.run_until_complete(tc.close())
    loop.close()


def test_edge_adopts_traceparent_and_joins_by_id(app_client):
    """An external traceparent on ANY endpoint opens an edge entry under
    the caller's trace id, so the hop is joinable via /debug/traces?id=
    — the middleware half of the cross-process join."""
    tid = mint_trace_id()
    pspan = new_span_id()
    status, headers, _ = app_client(
        "/v1/models", headers={"traceparent": make_traceparent(tid, pspan)})
    assert status == 200
    # the response echoes the ADOPTED trace id (fresh span for this hop)
    echoed = parse_traceparent(headers.get("traceparent", ""))
    assert echoed is not None and echoed[0] == tid

    status, _, body = app_client(f"/debug/traces?id={tid}")
    assert status == 200
    rows = body["traces"]
    assert rows, "edge hop left no joinable trace entry"
    edge = rows[0]
    assert edge["trace_id"] == tid
    assert edge["parent_span"] == pspan
    assert edge["request_id"].startswith("edge:")
    notes = {n["name"]: n for n in edge["span_events"]}
    assert notes["http"]["path"] == "/v1/models"


def test_edge_without_header_mints_fresh_id(app_client):
    status, headers, _ = app_client("/v1/models")
    assert status == 200
    echoed = parse_traceparent(headers.get("traceparent", ""))
    assert echoed is not None  # minted at this edge


def test_debug_timeline_is_chrome_trace_json(app_client):
    """/debug/timeline must serve the Chrome-trace schema Perfetto
    loads: a traceEvents list of dicts with ph/name/ts, thread-name
    metadata, and the ring bookkeeping under otherData."""
    FLIGHT.span("step:test", "device", time.perf_counter(), 0.001,
                {"rows": 1})
    FLIGHT.sample("queue_depth", "scheduler", 3)
    status, _, doc = app_client("/debug/timeline")
    assert status == 200
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    phases = {ev["ph"] for ev in events}
    assert "M" in phases  # process/thread metadata for track naming
    for ev in events:
        assert "name" in ev and "ph" in ev
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], (int, float))
    names = {ev["name"] for ev in events}
    assert "step:test" in names and "queue_depth" in names
    other = doc["otherData"]
    assert other["ring_capacity"] >= 64
    assert other["recorded_total"] >= 2


# --------------------------------------- federated balancer forwarding


def test_federated_proxy_forwards_traceparent():
    """The balancer hop: an inbound traceparent is forwarded to the
    member it picks (same trace id, FRESH span id), and the balancer's
    own proxy entry joins the trace with the caller's span as parent."""
    from localai_tfp_tpu.parallel.federated import (
        FederatedServer, generate_token,
    )

    loop = asyncio.new_event_loop()

    async def go():
        seen = {}

        async def handler(request):
            seen["traceparent"] = request.headers.get("traceparent", "")
            return web.json_response({"ok": True})

        mapp = web.Application()
        mapp.router.add_route("*", "/{tail:.*}", handler)
        member = TestServer(mapp)
        await member.start_server()

        tok = generate_token()
        fed = FederatedServer(tok)
        client = TestClient(TestServer(fed.build_app()))
        await client.start_server()
        r = await client.post("/federation/register", json={
            "token": tok, "id": "m1", "name": "m1",
            "address": f"http://127.0.0.1:{member.port}",
        })
        assert r.status == 200

        tid = mint_trace_id()
        pspan = new_span_id()
        r = await client.post(
            "/v1/models", data=b"{}",
            headers={"traceparent": make_traceparent(tid, pspan)})
        assert r.status == 200

        upstream = parse_traceparent(seen["traceparent"])
        assert upstream is not None, "member never saw a traceparent"
        assert upstream[0] == tid  # same trace id crossed the hop
        assert upstream[1] != pspan  # fresh span id for this hop

        await client.close()
        await member.close()
        return tid, pspan

    tid, pspan = loop.run_until_complete(go())
    loop.close()

    rows = TRACER.lookup(tid)
    proxy = [t for t in rows if t["request_id"].startswith("proxy:")]
    assert proxy, "balancer recorded no proxy entry for the trace"
    tr = proxy[0]
    assert tr["trace_id"] == tid and tr["parent_span"] == pspan
    assert tr["status"] == "proxied"
    notes = {n["name"] for n in tr["span_events"]}
    # pick decision, upstream sub-span and terminal outcome all join
    assert {"pick", "upstream", "terminal"} <= notes
    term = [n for n in tr["span_events"] if n["name"] == "terminal"]
    assert term[0]["outcome"] == "proxied"


# --------------------------------------- multihost follower replay join


def test_replayer_joins_leader_trace_ids():
    """The Replayer unit contract (no engines, no jit — the full
    leader/follower engine path asserts the same join in
    tests/test_multihost.py): each leader trace id on a record envelope
    opens ONE ``replay:<tid16>`` entry joined by that id, annotated
    with the kinds replayed, closed when the id leaves the live set."""
    from localai_tfp_tpu.parallel.multihost import Replayer

    calls = []

    class FakeEngine:
        def _dev_exec(self, kind, payload):
            calls.append(kind)

    tid_a, tid_b = mint_trace_id(), mint_trace_id()
    rp = Replayer()
    eng = FakeEngine()
    rp.exec(eng, "mixed", {}, trace=(tid_a,))
    rp.exec(eng, "decodek", {}, trace=(tid_a, tid_b))
    rp.exec(eng, "decodek", {}, trace=(tid_b,))  # a's entry closes here
    assert calls == ["mixed", "decodek", "decodek"]

    rows_a = TRACER.lookup(tid_a)
    assert rows_a and rows_a[0]["request_id"] == "replay:" + tid_a[:16]
    assert rows_a[0]["trace_id"] == tid_a
    assert rows_a[0]["model"] == "follower"
    assert rows_a[0]["status"] == "replayed"  # closed on departure
    kinds = [n["kind"] for n in rows_a[0]["span_events"]
             if n["name"] == "replay"]
    assert kinds == ["mixed", "decodek"]

    rows_b = TRACER.lookup(tid_b)
    assert rows_b and rows_b[0]["status"] == "active"  # still live
    assert rows_b[0]["trace_id"] == tid_b
