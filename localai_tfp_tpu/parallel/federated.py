"""Federated serving: node registry + HTTP request load balancer.

TPU-native replacement of the reference's libp2p/edgevpn federation
(core/p2p/federated.go:20-118 SelectLeastUsedServer/RandomServer,
federated_server.go:17-130 proxy loop; worker announce p2p.go:319-365 —
gossip ledger with LastSeen, offline nodes skipped). Re-design rationale
(SURVEY.md §2.5): inside a pod ICI/DCN collectives replace tensor
transport, so what remains for federation is a *control plane* + an HTTP
request router across independent LocalAI instances. That needs no DHT:
a shared-token registry with heartbeats and an HTTP reverse proxy give
the same operator surface (token join, /api/p2p introspection,
least-used/random balancing).

Failure handling (the part the reference delegates to edgevpn's
LastSeen gossip): routing decisions cannot wait out the STALE_S=60
heartbeat window, so the proxy layers three faster signals on top —

- a per-node circuit breaker: LOCALAI_FED_BREAKER_FAILS consecutive
  proxy/probe failures open the breaker for an exponentially growing
  backoff (LOCALAI_FED_BREAKER_BASE_S doubling up to
  LOCALAI_FED_BREAKER_CAP_S); after it elapses the node is half-open
  and the active prober re-admits it on the first healthy answer;
- connect-failure retry: an upstream that cannot be reached (or dies
  before the response is prepared — no bytes streamed yet) is marked
  failed and the request is re-proxied to the next eligible node;
- active /healthz probing every LOCALAI_FED_PROBE_S seconds (0
  disables) layered on the passive heartbeat, so a killed node is
  marked down in seconds, not at the staleness horizon.

An upstream that dies MID-stream cannot be retried (bytes are gone);
the client instead gets a clean terminal frame (an SSE ``data:
{"error": ...}`` event on event streams) and the node is marked down
for subsequent requests.

Token UX kept from the reference: one opaque base64 string carries
network id + shared secret (ref: p2p.go:33-66 GenerateToken).
"""

from __future__ import annotations

import asyncio
import base64
import hmac
import json
import logging
import os
import random
import secrets
import time
from dataclasses import dataclass, field
from typing import Optional

from aiohttp import ClientError, ClientSession, ClientTimeout, web

from ..config import knobs
from ..telemetry import digest as dg
from ..telemetry import fleet as fleetmod
from ..telemetry import metrics as tm
from ..telemetry.flightrec import FLIGHT
from ..telemetry.tracing import (
    TRACER, fault_scope, make_traceparent, mint_trace_id, new_span_id,
    parse_traceparent,
)
from ..utils import faultinject, fingerprint

log = logging.getLogger(__name__)

HEARTBEAT_S = 20.0  # ref: announce every 20s (p2p.go:350-362)
STALE_S = 60.0  # ref: FailureThreshold on LastSeen


def generate_token(network_id: str = "") -> str:
    """Opaque join token: base64 JSON {network_id, secret}."""
    payload = {
        "network_id": network_id or secrets.token_hex(8),
        "secret": secrets.token_hex(16),
    }
    return base64.urlsafe_b64encode(
        json.dumps(payload).encode()).decode()


def parse_token(token: str) -> dict:
    try:
        return json.loads(base64.urlsafe_b64decode(token.encode()))
    except Exception:
        raise ValueError("invalid federation token")


def tokens_match(a: str, b: str) -> bool:
    """Constant-time federation-token equivalence by shared SECRET
    (two encodings of the same payload still match). Members use this
    to recognize the balancer's X-Federation-Token on otherwise
    auth-exempt telemetry fetches."""
    if not a or not b:
        return False
    try:
        pa, pb = parse_token(a), parse_token(b)
    except ValueError:
        return False
    return hmac.compare_digest(pa.get("secret", ""),
                               pb.get("secret", ""))


@dataclass
class Node:
    """ref: p2p.NodeData (name, id, address, last seen) + the
    circuit-breaker record the registry drives."""

    id: str
    name: str
    address: str  # http(s)://host:port of the member instance
    last_seen: float = field(default_factory=time.monotonic)
    in_flight: int = 0
    requests_served: int = 0  # SUCCESSFUL proxies only
    # breaker record: consecutive failures, the open-until horizon and
    # the backoff that produced it (doubles per re-trip), last error
    consec_failures: int = 0
    open_until: float = 0.0
    backoff_s: float = 0.0
    last_error: str = ""
    # telemetry digest plane: last GOOD digest (a bad one never
    # replaces it), when it landed, and which path delivered it
    digest: Optional[dict] = None
    digest_at: float = 0.0
    digest_src: str = ""
    # autoscaler drain marker: a draining node takes no NEW traffic
    # (route() skips it) while its in-flight work finishes, then the
    # ScaleDriver kills it — drain-before-kill, never mid-request
    draining: bool = False

    def online(self, now: Optional[float] = None) -> bool:
        return (now or time.monotonic()) - self.last_seen < STALE_S

    def digest_age(self, now: Optional[float] = None) -> Optional[float]:
        if self.digest is None:
            return None
        return max(0.0, (now or time.monotonic()) - self.digest_at)

    def digest_stale(self, now: Optional[float] = None) -> bool:
        age = self.digest_age(now)
        return (age is None
                or age > knobs.float_("LOCALAI_DIGEST_STALE_S"))


class NodeRegistry:
    """Token-guarded membership table (the gossip-ledger equivalent)
    plus the per-node circuit breakers."""

    def __init__(self, token: str, *,
                 rng: Optional[random.Random] = None) -> None:
        self.token_payload = parse_token(token)
        self._nodes: dict[str, Node] = {}
        self.breaker_fails = max(
            1, knobs.int_("LOCALAI_FED_BREAKER_FAILS"))
        self.breaker_base_s = knobs.float_("LOCALAI_FED_BREAKER_BASE_S")
        self.breaker_cap_s = knobs.float_("LOCALAI_FED_BREAKER_CAP_S")
        # injectable RNG: the "random" strategy is seedable in tests
        # (the module doubles as the default shared Random instance)
        self.rng = rng if rng is not None else random

    def _authorized(self, token: str) -> bool:
        try:
            other = parse_token(token)
        except ValueError:
            return False
        return hmac.compare_digest(
            other.get("secret", ""), self.token_payload.get("secret", ""))

    def announce(self, token: str, node_id: str, name: str,
                 address: str, digest=None) -> bool:
        if not self._authorized(token):
            return False
        now = time.monotonic()
        n = self._nodes.get(node_id)
        if n is None:
            n = Node(id=node_id, name=name, address=address,
                     last_seen=now)
            self._nodes[node_id] = n
        else:
            # every successful announce is a full refresh: name and
            # address may both have changed across a node restart, and
            # last_seen must advance on the FIRST announce too (the
            # old code split these between the dataclass default and
            # the re-registration branch)
            n.name = name
            n.address = address
            n.last_seen = now
        if digest is not None:
            self.store_digest(n, digest, src="announce")
        self.update_state_gauge()
        return True

    def store_digest(self, n: Node, obj, src: str = "probe") -> bool:
        """Validate and attach a digest to ``n``. A malformed /
        oversized / wrong-version digest is COUNTED and dropped — the
        last good digest (with its age) keeps serving /fleet/* and
        routing (satellite-1 hardening)."""
        try:
            d = (dg.decode(obj) if isinstance(obj, (bytes, bytearray))
                 else dg.validate(obj))
        except dg.DigestError as e:
            tm.FEDERATION_DIGEST_ERRORS.labels(reason=e.reason).inc()
            return False
        except Exception:
            # validate()/decode() contract says DigestError-only, but a
            # digest arrives off the wire: an escape here would kill the
            # probe task (announce path: 500 /federation/register), so
            # contain it the same way and keep the last good digest
            log.exception("unexpected digest validation failure")
            tm.FEDERATION_DIGEST_ERRORS.labels(reason="malformed").inc()
            return False
        n.digest, n.digest_at, n.digest_src = d, time.monotonic(), src
        return True

    def nodes(self, online_only: bool = False) -> list[Node]:
        now = time.monotonic()
        out = sorted(self._nodes.values(), key=lambda n: n.id)
        return [n for n in out if n.online(now)] if online_only else out

    def remove(self, node_id: str) -> None:
        """Drop a node (autoscaler scale-down after drain + kill; a
        re-announce from a still-alive member simply re-registers)."""
        self._nodes.pop(node_id, None)
        self.update_state_gauge()

    # ---- circuit breaker ----

    def state(self, n: Node, now: Optional[float] = None) -> str:
        """closed (healthy) | open (tripped, backoff running) |
        half_open (backoff elapsed; one healthy answer re-closes)."""
        if n.consec_failures < self.breaker_fails:
            return "closed"
        if (now or time.monotonic()) < n.open_until:
            return "open"
        return "half_open"

    def record_failure(self, n: Node, error: str = "") -> None:
        n.consec_failures += 1
        n.last_error = error
        if n.consec_failures >= self.breaker_fails:
            # trip (or re-trip from half-open): exponential backoff
            n.backoff_s = min(self.breaker_cap_s,
                              n.backoff_s * 2 if n.backoff_s
                              else self.breaker_base_s)
            n.open_until = time.monotonic() + n.backoff_s
        self.update_state_gauge()

    def record_success(self, n: Node) -> None:
        n.consec_failures = 0
        n.backoff_s = 0.0
        n.open_until = 0.0
        n.last_error = ""
        self.update_state_gauge()

    def update_state_gauge(self) -> None:
        now = time.monotonic()
        counts = {"closed": 0, "open": 0, "half_open": 0}
        for n in self._nodes.values():
            counts[self.state(n, now)] += 1
        for st, c in counts.items():
            tm.FEDERATION_NODE_STATE.labels(state=st).set(c)

    # ---- selection (ref: federated.go SelectLeastUsedServer :78,
    #      RandomServer :39) ----

    def pick(self, strategy: str = "least-used",
             exclude: frozenset = frozenset()) -> Optional[Node]:
        """Route-eligible node, or None. Open-breaker nodes are never
        picked; half-open nodes only when no closed node remains (the
        active prober is the designated half-open probe — proxy traffic
        prefers known-good nodes). `exclude` carries the ids already
        tried by the current request's retry loop."""
        node, _ = self.route(strategy, exclude=exclude)
        return node

    def route(self, strategy: str = "least-used",
              exclude: frozenset = frozenset(),
              chain: tuple = ()) -> tuple[Optional[Node], dict]:
        """``pick`` plus prefix locality: with ``strategy="prefix"``
        and a request fingerprint ``chain`` (utils/fingerprint.py),
        eligible nodes are scored ::

            score = alpha * matched_prefix_tokens * disc
                  - beta  * predicted_drain_s     * disc
                  - gamma * queue_pressure

        where ``matched_prefix_tokens`` is the largest gossiped prefix
        entry whose hash appears in the chain, ``disc`` linearly
        discounts every digest-derived term by age (0 at
        LOCALAI_DIGEST_STALE_S — a fully stale digest decays to
        load-only routing on the balancer's own in_flight counts), and
        ``queue_pressure`` is balancer-live in_flight plus the
        discounted digest queue/busy fraction. Ties break
        deterministically on (in_flight, requests_served, id).

        Breaker/exclude semantics are identical to ``pick``; with
        ``least-used`` (or no chain, or no digests stored) the choice
        is byte-identical to the legacy pick. Returns ``(node, info)``
        with ``info = {"result": hit|miss|stale|off,
        "matched_tokens": int}``.
        """
        now = time.monotonic()
        online = [n for n in self.nodes(online_only=True)
                  if n.id not in exclude and not n.draining]
        closed = [n for n in online if self.state(n, now) == "closed"]
        pool = closed or [n for n in online
                          if self.state(n, now) == "half_open"]
        info = {"result": "off", "matched_tokens": 0}
        if not pool:
            return None, info
        if strategy == "random":
            return self.rng.choice(pool), info
        scored = (strategy == "prefix" and bool(chain)
                  and any(n.digest is not None for n in pool))
        if not scored:
            if strategy == "prefix" and chain:
                # locality was requested but nothing has gossiped yet
                info["result"] = "miss"
            return min(pool, key=lambda n: (n.in_flight,
                                            n.requests_served)), info
        alpha = knobs.float_("LOCALAI_ROUTE_ALPHA")
        beta = knobs.float_("LOCALAI_ROUTE_BETA")
        gamma = knobs.float_("LOCALAI_ROUTE_GAMMA")
        stale_s = max(1e-9, knobs.float_("LOCALAI_DIGEST_STALE_S"))
        hashes = fingerprint.chain_hashes(chain)
        fresh_match = stale_match = False
        best = None
        best_key = None
        best_hit = (0, 0.0)  # (matched, disc) of the current best
        for n in pool:
            matched = 0
            disc = 0.0
            drain = 0.0
            pressure = float(n.in_flight)
            d = n.digest
            if d is not None:
                age = n.digest_age(now) or 0.0
                disc = max(0.0, 1.0 - age / stale_s)
                for h, toks in d.get("prefixes", ()):
                    if h in hashes and int(toks) > matched:
                        matched = int(toks)
                drain = float(d.get("drain_s") or 0.0)
                occ = d.get("occ", {})
                n_slots = max(1, int(occ.get("n_slots", 0) or 0))
                pressure += disc * (
                    int(occ.get("queue_depth", 0) or 0)
                    + int(occ.get("slots_busy", 0) or 0)) / n_slots
            if matched:
                if disc > 0.0:
                    fresh_match = True
                else:
                    stale_match = True
            score = (alpha * matched * disc - beta * drain * disc
                     - gamma * pressure)
            key = (-score, n.in_flight, n.requests_served, n.id)
            if best_key is None or key < best_key:
                best, best_key, best_hit = n, key, (matched, disc)
        if best_hit[0] > 0 and best_hit[1] > 0.0:
            info["result"] = "hit"
            info["matched_tokens"] = best_hit[0]
        elif stale_match and not fresh_match:
            info["result"] = "stale"
        else:
            info["result"] = "miss"
        return best, info


class FederatedServer:
    """HTTP front door balancing whole requests across member instances
    (ref: federated_server.go proxy loop — whole-connection forwarding,
    least-used default), with connect-failure retry and per-node
    circuit breaking (see module docstring)."""

    HOP_HEADERS = {"connection", "keep-alive", "transfer-encoding",
                   "upgrade", "proxy-authorization", "te", "trailer"}

    def __init__(self, token: str, *, strategy: Optional[str] = None,
                 probe_s: Optional[float] = None,
                 scale_driver=None) -> None:
        self.registry = NodeRegistry(token)
        self.token = token
        self.strategy = (strategy if strategy is not None
                         else knobs.str_("LOCALAI_FED_STRATEGY"))
        self.probe_s = (knobs.float_("LOCALAI_FED_PROBE_S")
                        if probe_s is None else probe_s)
        self.slo = fleetmod.SLOMonitor()
        # SLO-driven elastic autoscaling: runs beside the probe task;
        # the default LogScaleDriver only logs intent, a real driver
        # (tools/profile_fleet.py boots warmup-reuse members) acts
        from .autoscale import Autoscaler

        self.autoscaler = Autoscaler(self, driver=scale_driver)
        # in-process routing tally (per result class), mirrored into
        # federation_route_locality_total — profile_fleet reads this
        # to compute cross-replica prefix hit rates without scraping
        self.route_stats = {"hit": 0, "miss": 0, "stale": 0, "off": 0}

    def build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_post("/federation/register", self.handle_register)
        app.router.add_get("/federation/nodes", self.handle_nodes)
        # fleet telemetry plane — MUST register before the catch-all
        # proxy route or these would be forwarded to a member
        app.router.add_get("/fleet/metrics", self.handle_fleet_metrics)
        app.router.add_get("/fleet/slo", self.handle_fleet_slo)
        app.router.add_route("*", "/{tail:.*}", self.handle_proxy)
        app.cleanup_ctx.append(self._client_ctx)
        return app

    async def _client_ctx(self, app):
        self._client = ClientSession(timeout=ClientTimeout(total=600))
        loop = asyncio.get_event_loop()
        self._probe_task = (loop.create_task(self._probe_loop())
                            if self.probe_s > 0 else None)
        # default cadence rides the probe loop (step right after the
        # digests refresh); an explicit LOCALAI_SCALE_TICK_S runs free
        self._scale_task = (loop.create_task(self.autoscaler.run())
                            if self.autoscaler.enabled
                            and not self.autoscaler.rides_probe
                            else None)
        yield
        for task in (self._probe_task, self._scale_task):
            if task is not None:
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
        await self._client.close()

    async def _probe_loop(self) -> None:
        """Active health probing layered on the passive heartbeat: GET
        each member's /healthz every probe_s seconds. Success counts as
        liveness (refreshes last_seen AND closes a half-open breaker);
        failure feeds the breaker, so a killed node is routed around in
        seconds instead of the STALE_S heartbeat horizon."""
        while True:
            await asyncio.sleep(self.probe_s)
            for node in self.registry.nodes():
                healthy = False
                try:
                    async with self._client.get(
                        node.address.rstrip("/") + "/healthz",
                        timeout=ClientTimeout(total=2),
                    ) as resp:
                        if resp.status < 500:
                            node.last_seen = time.monotonic()
                            self.registry.record_success(node)
                            healthy = True
                        else:
                            self.registry.record_failure(
                                node, f"healthz HTTP {resp.status}")
                except (ClientError, asyncio.TimeoutError, OSError) as e:
                    self.registry.record_failure(
                        node, f"healthz probe: {e!r}")
                if healthy:
                    await self._refresh_digest(node)
            self._slo_tick()
            if self.autoscaler.enabled and self.autoscaler.rides_probe:
                try:
                    await self.autoscaler.step()
                except Exception:
                    # same containment as Autoscaler.run(): a decision
                    # bug must not kill the probe loop
                    log.exception("autoscaler step failed")

    async def _refresh_digest(self, node: Node) -> None:
        """Probe-path digest refresh. Failures here feed
        federation_digest_errors_total, never the circuit breaker —
        /healthz alone governs liveness, so a node with a broken
        telemetry endpoint keeps serving traffic (satellite-1)."""
        cap = dg._max_bytes()
        try:
            if faultinject.ACTIVE:
                # chaos surface: digest fetch/decode hardening
                faultinject.fire("federated.digest")
            async with self._client.get(
                node.address.rstrip("/") + "/telemetry/digest",
                # the federation token unlocks the prefix top-k (the
                # member omits prompt-derived fields on anonymous GETs)
                headers={"X-Federation-Token": self.token},
                timeout=ClientTimeout(total=2),
            ) as resp:
                if resp.status != 200:
                    tm.FEDERATION_DIGEST_ERRORS.labels(
                        reason="fetch").inc()
                    return
                # bounded read: one extra byte proves oversize without
                # ever buffering an unbounded body
                raw = await resp.content.read(cap + 1)
            self.registry.store_digest(node, raw, src="probe")
        except (ClientError, asyncio.TimeoutError, OSError,
                faultinject.InjectedFault):
            tm.FEDERATION_DIGEST_ERRORS.labels(reason="fetch").inc()

    # ------------------------------------------------- fleet telemetry

    def _merged_digest(self) -> dict:
        return dg.merge_all(n.digest for n in self.registry.nodes())

    def _offline_frac(self, now: Optional[float] = None) -> float:
        """Fraction of registered nodes NOT serving — the availability
        error rate. A node counts as serving when it is inside the
        liveness horizon with no outstanding probe/proxy failure, so a
        kill shows up at the FIRST failed probe, not after the breaker
        trips."""
        nodes = self.registry.nodes()
        if not nodes:
            return 0.0
        now = now or time.monotonic()
        serving = sum(1 for n in nodes
                      if n.online(now) and n.consec_failures == 0)
        return 1.0 - serving / len(nodes)

    def _slo_tick(self) -> None:
        self.slo.record(self._merged_digest(), self._offline_frac())

    def _node_views(self, limit: int) -> list[dict]:
        now = time.monotonic()
        views = []
        for n in self.registry.nodes()[:limit]:
            views.append({
                "node": n.name or n.id, "digest": n.digest,
                "age_s": n.digest_age(now), "stale": n.digest_stale(now),
                "in_flight": n.in_flight,
                "serving": n.online(now)
                and self.registry.state(n, now) != "open"})
        return views

    @staticmethod
    def _limit(request: web.Request, default: int = 64,
               cap: int = 512) -> int:
        try:
            limit = int(request.query.get("limit") or default)
        except ValueError:
            raise web.HTTPBadRequest(reason="'limit' must be an integer")
        return max(1, min(limit, cap))

    async def handle_fleet_metrics(self, request: web.Request
                                   ) -> web.Response:
        from ..telemetry.registry import CONTENT_TYPE

        limit = self._limit(request)
        self.slo.maybe_record(
            lambda: (self._merged_digest(), self._offline_frac()))
        text = fleetmod.render_fleet(
            self._node_views(limit), self._merged_digest(),
            self.slo.evaluate(), scale=self.autoscaler.snapshot())
        return web.Response(body=text.encode("utf-8"), headers={
            "Content-Type": CONTENT_TYPE, "Cache-Control": "no-store"})

    async def handle_fleet_slo(self, request: web.Request
                               ) -> web.Response:
        self.slo.maybe_record(
            lambda: (self._merged_digest(), self._offline_frac()))
        out = self.slo.evaluate()
        now = time.monotonic()
        nodes = self.registry.nodes()
        out["nodes"] = {
            "total": len(nodes),
            "serving": sum(1 for n in nodes
                           if n.online(now) and n.consec_failures == 0)}
        return web.json_response(
            out, headers={"Cache-Control": "no-store"})

    async def handle_register(self, request: web.Request) -> web.Response:
        body = await request.json()
        ok = self.registry.announce(
            body.get("token", ""), body.get("id", ""),
            body.get("name", ""), body.get("address", ""),
            digest=body.get("digest"))
        if not ok:
            raise web.HTTPUnauthorized(reason="bad federation token")
        return web.json_response({"ok": True,
                                  "heartbeat_s": HEARTBEAT_S})

    @staticmethod
    def _digest_summary(n: Node, now: float) -> Optional[dict]:
        """Compact per-node digest view for /federation/nodes (the full
        digest stays on /fleet/metrics; this is the operator listing)."""
        d = n.digest
        if d is None:
            return None
        return {
            "age_s": round(n.digest_age(now) or 0.0, 3),
            "stale": n.digest_stale(now), "src": n.digest_src,
            "queue_depth": d["occ"].get("queue_depth", 0),
            "slots_busy": d["occ"].get("slots_busy", 0),
            "n_slots": d["occ"].get("n_slots", 0),
            "mfu": dg.mfu_mean(d),
            "drain_s": d.get("drain_s"),
            "models": d.get("models", []),
            "kv_pages": d.get("kv_pages", {}),
            "prefixes": len(d.get("prefixes", [])),
        }

    async def handle_nodes(self, request: web.Request) -> web.Response:
        now = time.monotonic()
        # the operator listing defaults to the cap, not the 64 the
        # per-node gauge endpoints use: consumers that never pass
        # ?limit must see the whole fleet, and X-Total-Count makes an
        # explicit-limit truncation detectable
        limit = self._limit(request, default=512)
        nodes = self.registry.nodes()
        return web.json_response([
            {"id": n.id, "name": n.name, "address": n.address,
             "online": n.online(now), "in_flight": n.in_flight,
             "requests_served": n.requests_served,
             "state": self.registry.state(n, now),
             "draining": n.draining,
             "consec_failures": n.consec_failures,
             "breaker_open_for_s": round(max(0.0, n.open_until - now), 3),
             "last_error": n.last_error,
             "digest": self._digest_summary(n, now)}
            for n in nodes[:limit]
        ], headers={"Cache-Control": "no-store",
                    "X-Total-Count": str(len(nodes))})

    async def handle_proxy(self, request: web.Request) -> web.StreamResponse:
        # the body is buffered up front so a connect-failure retry can
        # replay it against the next node
        data = await request.read()
        # distributed trace: join the caller's traceparent (or mint one
        # at this edge) so the balancer hop and every member it touches
        # share ONE trace id; the proxy's own entry records routing —
        # node picks, breaker states, retries — as span events
        parsed = parse_traceparent(request.headers.get("traceparent", ""))
        tid, pspan = parsed if parsed else (mint_trace_id(), "")
        rid = "proxy:" + new_span_id()
        TRACER.start(
            rid, model="federated",
            correlation_id=request.headers.get("X-Correlation-ID", ""),
            events=[("receive", time.perf_counter())],
            trace_id=tid, parent_span=pspan)
        status = "error"
        tried: set[str] = set()
        shed_hints: list[float] = []
        # prefix-locality fingerprint: hash the SAME canonical bytes
        # the member edge hashes (utils/fingerprint.py), so the chain
        # matches the hashes the fleet gossips in digest `prefixes`.
        # Non-JSON / non-chat bodies yield an empty chain = locality
        # off for that request, never an error.
        chain = (fingerprint.chain_from_bytes(data)
                 if request.method == "POST" else ())
        try:
            while True:
                node, rinfo = self.registry.route(
                    self.strategy, exclude=tried, chain=chain)
                if not tried:
                    # first attempt only: retries are breaker business,
                    # not routing-quality signal
                    res = rinfo["result"]
                    self.route_stats[res] = (
                        self.route_stats.get(res, 0) + 1)
                    tm.FEDERATION_ROUTE_LOCALITY.labels(
                        result=res).inc()
                    if rinfo["matched_tokens"]:
                        tm.FEDERATION_PREFIX_MATCHED.inc(
                            rinfo["matched_tokens"])
                if node is None:
                    if not self.registry.nodes():
                        # nothing has ever registered: a retry cannot
                        # help, tell the client the fleet is absent
                        status = "no_nodes"
                        TRACER.annotate(rid, "terminal",
                                        outcome="no_nodes")
                        raise web.HTTPServiceUnavailable(
                            reason="no federation nodes online")
                    # nodes exist but every eligible one is down or
                    # shedding. The status code preserves the semantic
                    # split: member sheds (any 429 hint collected) are
                    # a CAPACITY condition -> one aggregated 429; pure
                    # connect failures are an OUTAGE -> 503, so 5xx
                    # alerting still fires during a full-fleet failure.
                    # Both carry a Retry-After priced from the fleet's
                    # own drain predictions (satellite-3).
                    if tried:
                        tm.FEDERATION_RETRIES.labels(
                            outcome="exhausted").inc()
                    ra = self._retry_after_s(shed_hints)
                    status = "saturated" if shed_hints else "exhausted"
                    TRACER.annotate(rid, "terminal", outcome=status,
                                    tried=len(tried),
                                    shed=len(shed_hints),
                                    retry_after_s=ra)
                    if shed_hints:
                        raise web.HTTPTooManyRequests(
                            headers={"Retry-After": str(ra)},
                            reason="every federation node is shedding; "
                                   "retry after the predicted drain")
                    raise web.HTTPServiceUnavailable(
                        headers={"Retry-After": str(ra)},
                        reason="every eligible federation node is "
                               "unreachable or breaker-open")
                tried.add(node.id)
                TRACER.annotate(rid, "pick", node=node.name,
                                breaker=self.registry.state(node),
                                attempt=len(tried),
                                locality=rinfo["result"],
                                matched_tokens=rinfo["matched_tokens"])
                resp, shed_s = await self._proxy_once(
                    request, node, data, rerouted=len(tried) > 1,
                    rid=rid, trace_id=tid)
                if resp is not None:
                    status = "proxied"
                    TRACER.annotate(rid, "terminal", outcome="proxied",
                                    node=node.name)
                    return resp
                if shed_s is not None:
                    # upstream shed (429 before any bytes): not a node
                    # failure — keep its Retry-After hint and try the
                    # next node
                    shed_hints.append(shed_s)
                    TRACER.annotate(rid, "shed", node=node.name,
                                    retry_after_s=shed_s)
                    continue
                # connect failure before any bytes streamed: next node
                TRACER.annotate(rid, "retry", node=node.name,
                                error=node.last_error)
        finally:
            # every exit — proxied, exhausted, no_nodes, cancelled —
            # completes the trace entry (satellite-1 contract)
            TRACER.event(rid, "done")
            TRACER.finish(rid, status=status)

    def _retry_after_s(self, shed_hints: list) -> int:
        """Whole-second Retry-After for a saturated fleet: the minimum
        of the members' own shed hints, each node digest's predicted
        drain, and the soonest breaker re-open — i.e. the earliest
        moment ANY node plausibly takes traffic again. Falls back to
        the breaker backoff base when nothing is known."""
        import math

        now = time.monotonic()
        cands = [float(h) for h in shed_hints if h and h > 0]
        for n in self.registry.nodes():
            if n.digest is not None and n.digest.get("drain_s"):
                cands.append(float(n.digest["drain_s"]))
            if n.open_until > now:
                cands.append(n.open_until - now)
        horizon = min(cands) if cands else self.registry.breaker_base_s
        return int(math.ceil(min(60.0, max(1.0, horizon))))

    async def _proxy_once(self, request: web.Request, node: Node,
                          data: bytes, rerouted: bool, rid: str = "",
                          trace_id: str = "",
                          ) -> tuple[Optional[web.StreamResponse],
                                     Optional[float]]:
        """Proxy one attempt to `node`. Returns (response, None) on a
        completed attempt, (None, None) when the upstream failed before
        the response was prepared (the only case a retry is safe), and
        (None, retry_after_s) when the upstream SHED the request with a
        429 — not a node failure, the caller tries the next node and
        aggregates the hint."""
        node.in_flight += 1
        resp: Optional[web.StreamResponse] = None
        span = TRACER.begin_span(rid, "upstream")
        try:
            url = node.address.rstrip("/") + "/" + request.match_info["tail"]
            if request.query_string:
                url += "?" + request.query_string
            headers = {k: v for k, v in request.headers.items()
                       if k.lower() not in self.HOP_HEADERS
                       and k.lower() != "host"}
            if trace_id:
                # forward the SHARED trace id with a fresh span id per
                # attempt — the member's edge middleware adopts it, so
                # its /debug/traces entry joins this balancer's
                headers["traceparent"] = make_traceparent(trace_id)
            if faultinject.ACTIVE:
                # chaos surface: connect-failure path (no bytes sent);
                # fault_scope binds the delivery to this proxy trace
                with fault_scope((rid,)):
                    faultinject.fire("federated.upstream")
            async with self._client.request(
                request.method, url, headers=headers,
                data=data or None, allow_redirects=False,
            ) as upstream:
                if upstream.status == 429:
                    # the member shed at admission — a capacity signal,
                    # not a failure: leave the breaker alone, hand the
                    # drain hint back for aggregation (satellite-3)
                    try:
                        hint = float(
                            upstream.headers.get("Retry-After", "") or 0)
                    except ValueError:
                        hint = 0.0
                    if hint <= 0 and node.digest is not None:
                        hint = float(node.digest.get("drain_s") or 0)
                    return None, max(hint, 1.0)
                resp = web.StreamResponse(status=upstream.status)
                for k, v in upstream.headers.items():
                    if k.lower() not in self.HOP_HEADERS | {"content-length"}:
                        resp.headers[k] = v
                await resp.prepare(request)
                async for chunk in upstream.content.iter_chunked(1 << 16):
                    if faultinject.ACTIVE:
                        # chaos surface: upstream dies mid-stream
                        with fault_scope((rid,)):
                            faultinject.fire("federated.midstream")
                    await resp.write(chunk)
                await resp.write_eof()
                node.requests_served += 1
                self.registry.record_success(node)
                if rerouted:
                    tm.FEDERATION_RETRIES.labels(outcome="rerouted").inc()
                return resp, None
        except (ClientError, asyncio.TimeoutError,
                faultinject.InjectedFault) as e:
            self.registry.record_failure(node, repr(e))
            if resp is None or not resp.prepared:
                return None, None  # no bytes streamed; caller retries
            # bytes already went out: the stream cannot move to another
            # node, so end it CLEANLY — SSE clients get a terminal
            # error event instead of a silent truncation
            tm.FEDERATION_RETRIES.labels(outcome="midstream").inc()
            ctype = resp.headers.get("Content-Type", "")
            try:
                if "text/event-stream" in ctype:
                    frame = json.dumps({"error": {
                        "message": f"upstream node '{node.name}' failed "
                                   f"mid-stream: {e!r}",
                        "type": "upstream_error"}})
                    await resp.write(f"data: {frame}\n\n".encode())
                    await resp.write_eof()
                else:
                    await resp.write_eof()
            except (ConnectionResetError, ClientError, OSError):
                # client went away while we delivered the obituary —
                # nothing left to notify
                tm.RECOVERED_ERRORS.labels(
                    site="federated.midstream_notify").inc()
            return resp, None
        finally:
            TRACER.end_span(span, node=node.name)
            # timeline: one attempt span on the federated track (token
            # carries the begin timestamp at index 2)
            FLIGHT.span("proxy:" + node.name, "federated", span[2],
                        time.perf_counter() - span[2])
            node.in_flight -= 1


async def announce_forever(balancer_url: str, token: str, node_id: str,
                           name: str, address: str,
                           digest_fn=None) -> None:
    """Worker-side heartbeat loop (ref: ExposeService announce ticker).
    ``digest_fn`` (optional; sync or returning an awaitable) supplies
    this node's telemetry digest; it rides every register POST so the
    balancer has occupancy and latency buckets even with active probing
    disabled. Collection briefly takes each engine's lock, so callers
    should hand in an executor-wrapped fn (the same ``run_blocking``
    the /telemetry/digest route uses) — awaiting it here keeps the
    heartbeat from ever stalling the member's event loop. A digest
    failure never blocks the heartbeat — liveness outranks telemetry."""
    import inspect

    async with ClientSession(timeout=ClientTimeout(total=10)) as client:
        while True:
            body = {"token": token, "id": node_id, "name": name,
                    "address": address}
            if digest_fn is not None:
                try:
                    d = digest_fn()
                    if inspect.isawaitable(d):
                        d = await d
                    if d is not None:
                        body["digest"] = d
                except Exception:
                    tm.RECOVERED_ERRORS.labels(
                        site="federated.announce_digest").inc()
            try:
                async with client.post(
                    balancer_url.rstrip("/") + "/federation/register",
                    json=body,
                ) as resp:
                    if resp.status == 401:
                        log.error(
                            "federation register rejected (bad token) by "
                            "%s — this node will NOT receive traffic",
                            balancer_url,
                        )
                    elif resp.status != 200:
                        log.warning("federation register -> HTTP %s",
                                    resp.status)
            except Exception as e:
                log.warning("federation register failed: %s", e)
            await asyncio.sleep(HEARTBEAT_S)
