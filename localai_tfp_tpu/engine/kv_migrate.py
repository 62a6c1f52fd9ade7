"""Disaggregated prefill/decode serving: zero-copy KV page migration.

Long prompts and steady decode streams want OPPOSITE engine tunings: a
prefill flood fills the dispatch window with large compute-bound
chunks, and every token a decode stream emits while one is in flight
waits behind it — the inter-token gap balloons exactly when the server
is busiest. The fix here is the single-host form of disaggregated
serving (ref: DistServe/Splitwise; LocalAI runs one backend per model
and has no equivalent): TWO ``LLMEngine`` instances in one process
share one set of weights — a prefill engine tuned for big prompt
dispatches and a decode engine tuned for k-scan decode — joined by the
page-migration protocol in this module.

The relay, per disaggregated request:

1. ``DisaggRouter.submit_many`` routes the request (prompt length >=
   LOCALAI_DISAGG_MIN_PROMPT, priced against the cost model's
   prefill_token_ms when LOCALAI_DISAGG_MIN_MS is set). Local requests
   go straight to the decode engine — LOCALAI_DISAGG=off is
   byte-identical because the router is never constructed.
2. A prefill PROBE (same request, ``max_tokens=1``, id + ":prefill",
   same trace_id) runs on the prefill engine. Its final mixed
   step samples the first token with the request's own seeded
   sampler columns — identical semantics to the single-engine path —
   and with max_tokens=1 the slot finishes before any decode dispatch,
   so its pages cover EXACTLY the prompt.
3. At the probe's ``_finish`` the prefill-side ``Migrator`` gathers the
   slot's pages (async device->host copy enqueued in device order —
   later page reuse cannot outrun it) plus the slot's post-sample
   sampler ROW (rng, penalty counts, history window), and publishes the
   capture on the ``MigrationBus``.
4. The router's pump thread collects the capture into a content-
   addressed host-RAM interchange (pages dedup'd by token-prefix sha1,
   refcounted — two requests sharing a prompt prefix migrate one copy)
   and resubmits the ORIGINAL request to the decode engine with the
   ``KVHandoff`` attached and its original t_submit/deadline intact.
5. The decode engine's ``_admit`` calls ``Migrator.assign_migrated``:
   pages stage into a reserved pseudo-slot table (scatter in device
   order — never blocking the device step), the slot adopts them by
   reference (``PagePool.share``), the sampler row lands via a donated
   scatter, and the slot wakes in DECODE with the whole prompt resident
   and the probe's first token re-emitted. A migrated request
   re-prefills ZERO prompt tokens and streams from the decode engine
   from its first decode step.

Failure is graceful by construction: any capture/stage fault
(``disagg.migrate`` / ``disagg.handoff`` injection points, pool
pressure, validation) drops the handoff and the request re-prefills on
the decode engine — correct, just slower. Deadlines are enforced per
stage (queued/prefill/migrate/decode) and an overrun terminates with
``deadline_exceeded`` attributed to the stage that overran. Both
engines' pools stay ``leak_check``-clean: host blocks are refcounted on
the bus, pool pages only move by ensure/share/drop.

Transport: the interchange is deliberately a narrow interface —
``publish`` (device gather handles) / ``collect`` (host blocks) /
``blocks`` (stage reads) — so a multihost build can swap the host-RAM
hop for an ICI/DCN transfer without touching either engine's side of
the protocol. Today's single transport is process-local host RAM.
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import queue
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import knobs
from ..telemetry import metrics as tm
from ..telemetry.flightrec import FLIGHT, MIGRATE_TRACK
from ..telemetry.tracing import TRACER
from ..utils import faultinject
from .engine import GenRequest, SlotState, StreamEvent
from .kv_pool import TRASH_PAGE, PagePoolExhausted
from .kv_tier import _gather_pages, _pow2, _scatter_pages
from .tokenizer import StreamDecoder

log = logging.getLogger(__name__)


def _page_key(tokens, end: int) -> bytes:
    # content address of a page-aligned token prefix — same scheme as
    # the KV tier's dedup keys, kept separate so the interchange never
    # binds to a tier manager instance (the prefill engine runs none)
    return hashlib.sha1(
        np.asarray(tokens[:end], np.int64).tobytes()).digest()

# probe-request id suffix: the prefill engine serves "<rid>:prefill",
# the decode engine serves "<rid>" — distinct ids (each engine's
# tracked request lifecycle stays 1:1) on ONE shared trace_id
PREFILL_SUFFIX = ":prefill"

# decode-side staging pseudo-slot ids: kv_tier reserves
# n_slots+0..N_STAGE-1, migration staging starts above them so the two
# subsystems can never collide on a pool table id
_STAGE_BASE = 4
_N_STAGE = 2


@jax.jit
def _gather_row(state, idx):
    # one sampler row [fields...] off the [S, ...] state; every
    # SamplingState field is a registered pytree child so tree_map
    # covers rng/penalty counts/history in one expression
    return jax.tree_util.tree_map(lambda a: a[idx], state)


@partial(jax.jit, donate_argnums=(0,))
def _scatter_row(state, idx, row):
    return jax.tree_util.tree_map(
        lambda a, r: a.at[idx].set(r.astype(a.dtype)), state, row)


@dataclass
class _HostBlock:
    """One migrated KV page in the host-RAM interchange: native-dtype
    planes, refcounted (content-addressed pages shared by several
    in-flight migrations hold one copy)."""

    arrays: dict  # k/v [L, P, F]; k_scale/v_scale [L, P] when int8
    nbytes: int
    ref: int = 1
    key: Optional[bytes] = None


@dataclass
class _Capture:
    """A finished prefill slot's state, published by the prefill-side
    Migrator with device->host copies already in flight."""

    rid: str  # BASE request id (probe suffix stripped)
    tokens: list
    n: int
    first_token: int
    handles: tuple  # gathered page planes, copy_to_host_async'd
    names: tuple  # plane names aligned with handles
    row: Any  # sampler row pytree (device), post-first-sample
    npg: int
    prefill_ms: float
    enq_ms: float
    queued_ms: float
    t0: float  # gather enqueue time (migrate_out span start)


@dataclass
class KVHandoff:
    """The decode side's view of a migrated prompt: host block ids (refs
    held until release), the probe's first sampled token, the sampler
    row, and the timing the original request accrued before resubmit."""

    rid: str
    tokens: list
    n: int
    first_token: int
    hpids: list
    sampler_row: Any  # numpy pytree, scattered into the decode sampler
    nbytes: int
    npg: int
    prefill_ms: float
    enq_ms: float
    queued_ms: float
    migrate_ms: float = 0.0
    t_resubmit: float = 0.0
    _bus: Any = field(default=None, repr=False)
    _released: bool = False

    def release(self) -> None:
        """Drop this handoff's block refs (idempotent). The engine calls
        this on queued-death paths (shed/cancel/deadline while pending)
        so an adopted-never request cannot strand interchange RAM."""
        if self._released or self._bus is None:
            return
        self._released = True
        self._bus._deref(self.hpids, self.npg)


class MigrationBus:
    """The prefill->decode interchange: in-flight captures on one side,
    refcounted content-addressed host pages on the other.

    Unlike the KV tier's warm store this holds ONLY in-flight
    migrations — a handoff's blocks free at release (adoption or
    failure), and warm retention across requests stays the tier's job.
    All methods are thread-safe; ``collect`` runs the blocking
    host-copy finalize on the ROUTER's pump thread, never on either
    engine's scheduler thread."""

    def __init__(self, page: int) -> None:
        self.P = page
        self._cv = threading.Condition()
        self._want: set = set()  # lint: guarded-by self._cv
        self._caps: dict = {}  # lint: guarded-by self._cv
        self._failed: dict = {}  # lint: guarded-by self._cv
        self._blocks: dict = {}  # lint: guarded-by self._cv
        self._dedup: dict = {}  # lint: guarded-by self._cv
        self._next_id = 1  # lint: guarded-by self._cv
        self._bytes = 0  # lint: guarded-by self._cv
        self._closed = False  # lint: guarded-by self._cv
        self.counters = {
            "published": 0, "collected": 0, "failed": 0, "timeouts": 0,
            "dedup_pages": 0, "released_pages": 0,
        }

    # ------------------------------------------------- prefill side

    def register(self, rid: str) -> None:
        with self._cv:
            self._want.add(rid)

    def registered(self, rid: str) -> bool:
        with self._cv:
            return rid in self._want

    def publish(self, cap: _Capture) -> None:
        with self._cv:
            wanted = cap.rid in self._want
            if wanted:
                self._caps[cap.rid] = cap
                self.counters["published"] += 1
            self._cv.notify_all()
        if not wanted:
            # collector already gave up (deadline, cancel): the gathered
            # handles drop here and the device copies are simply unread
            log.debug("migration capture for %s arrived late", cap.rid)

    def fail(self, rid: str, why: str) -> None:
        with self._cv:
            if rid in self._want:
                self._failed[rid] = why
                self.counters["failed"] += 1
            self._cv.notify_all()

    # -------------------------------------------------- router side

    def collect(self, rid: str,
                timeout: float) -> tuple[Optional[KVHandoff], str]:
        """Wait for the probe's capture and finalize it into host
        blocks. Returns (handoff, "") or (None, why)."""
        deadline = time.perf_counter() + max(0.0, timeout)
        with self._cv:
            while (rid not in self._caps and rid not in self._failed
                   and not self._closed):
                left = deadline - time.perf_counter()
                if left <= 0:
                    self._want.discard(rid)
                    self.counters["timeouts"] += 1
                    return None, "timeout"
                self._cv.wait(timeout=min(left, 0.5))
            if rid in self._failed:
                self._want.discard(rid)
                return None, self._failed.pop(rid)
            if self._closed:
                return None, "closed"
            cap = self._caps.pop(rid)
            self._want.discard(rid)
        # finalize OFF the lock: np.asarray blocks until the async
        # device->host copies land — pump-thread time, not scheduler
        hostside = [np.asarray(h) for h in cap.handles]
        row = jax.tree_util.tree_map(np.asarray, cap.row)
        hpids: list = []
        nbytes = 0
        with self._cv:
            for i in range(cap.npg):
                end = (i + 1) * self.P
                key = (_page_key(cap.tokens, end)
                       if end <= cap.n else None)
                hit = self._dedup.get(key) if key is not None else None
                if hit is not None:
                    self._blocks[hit].ref += 1
                    self.counters["dedup_pages"] += 1
                    hpids.append(hit)
                    continue
                arrays = {nm: np.array(a[:, i])
                          for nm, a in zip(cap.names, hostside)}
                bn = sum(a.nbytes for a in arrays.values())
                bid = self._next_id
                self._next_id += 1
                self._blocks[bid] = _HostBlock(arrays, bn, ref=1, key=key)
                if key is not None:
                    self._dedup[key] = bid
                self._bytes += bn
                nbytes += bn
                hpids.append(bid)
            self.counters["collected"] += 1
        dur = time.perf_counter() - cap.t0
        FLIGHT.transfer("migrate_out", cap.t0, dur, cap.npg, nbytes,
                        track=MIGRATE_TRACK)
        return KVHandoff(
            rid=rid, tokens=cap.tokens, n=cap.n,
            first_token=cap.first_token, hpids=hpids,
            sampler_row=row, nbytes=nbytes, npg=cap.npg,
            prefill_ms=cap.prefill_ms, enq_ms=cap.enq_ms,
            queued_ms=cap.queued_ms, _bus=self), ""

    def forget(self, rid: str) -> None:
        with self._cv:
            self._want.discard(rid)
            self._caps.pop(rid, None)
            self._failed.pop(rid, None)

    # --------------------------------------------------- decode side

    def blocks(self, hpids: list) -> list:
        """The host blocks for a handoff's pages, in table order. The
        handoff's refs keep them live until its release."""
        with self._cv:
            return [self._blocks[h] for h in hpids]

    def _deref(self, hpids: list, npg: int) -> None:
        with self._cv:
            for h in hpids:
                blk = self._blocks.get(h)
                if blk is None:
                    continue
                blk.ref -= 1
                if blk.ref <= 0:
                    del self._blocks[h]
                    if blk.key is not None \
                            and self._dedup.get(blk.key) == h:
                        del self._dedup[blk.key]
                    self._bytes -= blk.nbytes
            self.counters["released_pages"] += npg

    # ------------------------------------------------------ lifecycle

    def host_bytes(self) -> int:
        with self._cv:
            return self._bytes

    def live_blocks(self) -> int:
        with self._cv:
            return len(self._blocks)

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()


class Migrator:
    """One engine's side of the migration protocol, attached as
    ``engine._migrator`` by the router. The prefill side captures
    finishing probe slots into the bus (``on_finish``, scheduler
    thread); the decode side stages + adopts handoffs at admission
    (``assign_migrated``, scheduler thread). Both paths are enqueue-
    only on the device: neither ever blocks a device step."""

    def __init__(self, eng, bus: MigrationBus, side: str) -> None:
        self.eng = eng
        self.bus = bus
        self.side = side
        self._stage_free = [eng.n_slots + _STAGE_BASE + i
                            for i in range(_N_STAGE)]
        self.counters = {
            "captures": 0, "capture_skips": 0, "capture_faults": 0,
            "adoptions": 0, "adopt_faults": 0, "reused_tokens": 0,
        }

    # ---------------------------------------------------- prefill side

    def on_finish(self, slot, reason: str) -> None:
        """Capture a finishing prefill probe's pages onto the bus.
        Called from the prefill engine's ``_finish`` BEFORE release —
        the gathers enqueue ahead of any later overwrite of these pages
        in device order, so the copy is coherent without a sync."""
        if self.side != "prefill":
            return
        req = slot.request
        rid = req.id
        if not rid.endswith(PREFILL_SUFFIX):
            return
        base = rid[: -len(PREFILL_SUFFIX)]
        if not self.bus.registered(base):
            return
        eng = self.eng
        n = slot.n_past
        npg = eng._pool.pages_for(n) if eng._paged else 0
        if (reason != "length" or not slot.generated
                or not eng._paged or req.soft_embeds is not None
                or n <= 0 or npg <= 0):
            self.counters["capture_skips"] += 1
            self.bus.fail(base, reason if reason != "length"
                          else "not_migratable")
            return
        try:
            if faultinject.ACTIVE:
                faultinject.fire("disagg.migrate")
        except faultinject.InjectedFault:
            # capture abandoned with NO bus or pool mutation: the
            # router's collect fails fast and the request re-prefills
            # on the decode engine
            self.counters["capture_faults"] += 1
            tm.ENGINE_KV_MIGRATED_PAGES.labels(
                model=eng._mlabel, outcome="fault").inc(npg)
            self.bus.fail(base, "fault")
            return
        table = eng._pool.table(slot.idx)[:npg]
        if len(table) < npg:
            self.counters["capture_skips"] += 1
            self.bus.fail(base, "short_table")
            return
        c = eng.cache
        tbl = jnp.asarray(np.asarray(
            list(table) + [TRASH_PAGE] * (_pow2(npg) - npg), np.int32))
        handles = [_gather_pages(c.k, tbl), _gather_pages(c.v, tbl)]
        names = ["k", "v"]
        if c.quantized:
            handles.append(_gather_pages(c.k_scale, tbl))
            handles.append(_gather_pages(c.v_scale, tbl))
            names += ["k_scale", "v_scale"]
        for h in handles:
            h.copy_to_host_async()
        # the sampler row AFTER the probe's first sample: rng advanced,
        # penalty counts/history include the prompt and first token —
        # scattering it into the decode sampler makes the continued
        # stream bit-identical to the single-engine stream
        row = _gather_row(eng.sampling, jnp.int32(slot.idx))
        queued = 0.0
        if req.t_submit:
            queued = max(0.0, (slot.t_start - req.t_submit) * 1e3)
        self.counters["captures"] += 1
        self.bus.publish(_Capture(
            rid=base, tokens=list(slot.cache_tokens), n=n,
            first_token=int(slot.generated[0]), handles=tuple(handles),
            names=tuple(names), row=row, npg=npg,
            prefill_ms=slot.t_prefill_ms, enq_ms=slot.t_prefill_enq_ms,
            queued_ms=queued, t0=time.perf_counter()))

    # ----------------------------------------------------- decode side

    def assign_migrated(self, slot, req: GenRequest, out) -> bool:
        """Stage a handoff's pages into ``slot`` and wake it in DECODE.
        Returns False (handoff released, caller re-prefills) on any
        staging failure — fault injection, pool pressure, plane
        mismatch. On success the slot owns private refs to the pages
        and the probe's first token has been emitted."""
        h: KVHandoff = req.disagg
        eng = self.eng
        try:
            if faultinject.ACTIVE:
                faultinject.fire("disagg.handoff")
        except faultinject.InjectedFault:
            # adoption abandoned with NO pool or cache mutation: the
            # caller falls through to _assign and re-prefills
            self.counters["adopt_faults"] += 1
            tm.ENGINE_KV_MIGRATED_PAGES.labels(
                model=eng._mlabel, outcome="dropped").inc(h.npg)
            h.release()
            return False
        if not eng._paged or h.n <= 0 or h.n >= eng.max_seq \
                or not self._stage_free:
            tm.ENGINE_KV_MIGRATED_PAGES.labels(
                model=eng._mlabel, outcome="dropped").inc(h.npg)
            h.release()
            return False
        t0 = time.perf_counter()
        sid = self._stage_free.pop()
        try:
            eng._pool.ensure(sid, h.n)
        except PagePoolExhausted:
            eng._pool.drop(sid)  # release any partial allocation
            self._stage_free.append(sid)
            tm.ENGINE_KV_MIGRATED_PAGES.labels(
                model=eng._mlabel, outcome="dropped").inc(h.npg)
            h.release()
            return False
        table = eng._pool.table(sid)
        npg = len(table)
        b = _pow2(npg)
        c = eng.cache
        blocks = self.bus.blocks(h.hpids[:npg])
        if c.quantized and "k_scale" not in blocks[0].arrays:
            # dtype drift between the two engines (misconfigured
            # prefill cache_dtype): adopt would scatter garbage scales
            eng._pool.drop(sid)
            self._stage_free.append(sid)
            tm.ENGINE_KV_MIGRATED_PAGES.labels(
                model=eng._mlabel, outcome="dropped").inc(h.npg)
            h.release()
            return False
        L, F = c.k.shape[0], c.k.shape[-1]
        P = self.bus.P
        rk = np.zeros((L, b, P, F), c.k.dtype)
        rv = np.zeros((L, b, P, F), c.v.dtype)
        rks = rvs = None
        if c.quantized:
            rks = np.zeros((L, b, P), np.float32)
            rvs = np.zeros((L, b, P), np.float32)
        for i, blk in enumerate(blocks):
            rk[:, i] = blk.arrays["k"]
            rv[:, i] = blk.arrays["v"]
            if rks is not None:
                rks[:, i] = blk.arrays["k_scale"]
                rvs[:, i] = blk.arrays["v_scale"]
        tbl = jnp.asarray(np.asarray(
            list(table) + [TRASH_PAGE] * (b - npg), np.int32))
        dk, dv = jax.device_put(rk), jax.device_put(rv)
        ck = _scatter_pages(c.k, tbl, dk)
        cv = _scatter_pages(c.v, tbl, dv)
        ks, vs = c.k_scale, c.v_scale
        nbytes = int(dk.nbytes) + int(dv.nbytes)
        if c.quantized:
            dks, dvs = jax.device_put(rks), jax.device_put(rvs)
            ks = _scatter_pages(ks, tbl, dks)
            vs = _scatter_pages(vs, tbl, dvs)
            nbytes += int(dks.nbytes) + int(dvs.nbytes)
        eng.cache = type(c)(k=ck, v=cv, k_scale=ks, v_scale=vs)
        # the slot adopts the staged pages by REFERENCE (refcount share,
        # no second copy); dropping the stage leaves the slot as sole
        # owner, so its decode write frontier is privately writable
        eng._pool.share(slot.idx, sid, npg)
        eng._pool.drop(sid)
        self._stage_free.append(sid)
        # sampler row: the probe's post-sample state lands in this
        # slot's column — seeded streams continue bit-identically
        eng.sampling = _scatter_row(
            eng.sampling, jnp.int32(slot.idx), h.sampler_row)
        now = time.perf_counter()
        TRACER.event(req.id, "admit", t=now, model=eng._mlabel)
        TRACER.annotate(req.id, "migrate_adopt", t=now, pages=npg,
                        bytes=nbytes, reused_tokens=h.n)
        wait = max(0.0, now - (h.t_resubmit or req.t_submit or now))
        tm.ENGINE_QUEUE_WAIT.labels(model=eng._mlabel).observe(wait)
        with eng._lock:
            eng._queue_waits.append(wait)
        slot.cache_loaded = None
        slot.request = req
        slot.out = out
        slot.state = SlotState.DECODE
        slot.n_past = h.n
        slot.n_prompt = len(req.prompt_ids)
        slot.cache_tokens = list(h.tokens)
        slot.n_reused = h.n
        if eng._prefix_enabled:
            eng._prefix_index.set_tokens(slot.idx, slot.cache_tokens)
            eng._prefix_index.touch(slot.idx)
            eng._prefix_index.set_chain(
                slot.idx, req.prefix_chain, len(req.prompt_ids))
        slot.generated = []
        slot.decoder = StreamDecoder(eng.tokenizer)
        slot.pending_text = ""
        slot.emit_buf = []
        slot.emit_tok = None
        slot.t_start = now
        slot.t_first = 0.0
        # prompt-processing attribution for a migrated request: the
        # prefill ENGINE's device time plus the migration wall — the
        # decode engine did zero prompt work (satellite: stage-correct
        # TTFT/timing for the disaggregated path)
        slot.t_prefill_ms = h.prefill_ms + h.migrate_ms
        slot.t_prefill_enq_ms = h.enq_ms
        slot.t_prefill_t0 = 0.0
        slot.t_decode_ms = 0.0
        slot.t_last = now
        slot.constraint_state = (
            req.constraint.initial_state() if req.constraint else None)
        FLIGHT.transfer("migrate_in", t0, now - t0, npg, nbytes,
                        track=MIGRATE_TRACK)
        tm.ENGINE_KV_MIGRATED_PAGES.labels(
            model=eng._mlabel, outcome="migrated").inc(npg)
        self.counters["adoptions"] += 1
        self.counters["reused_tokens"] += h.n
        h.release()
        # re-emit the probe's first token on the DECODE engine: stamps
        # t_first against the ORIGINAL t_submit (end-to-end TTFT),
        # observes prefill timing, and handles the EOS/stop/max_tokens
        # edges exactly like the single-engine first emit did
        eng._emit_token(slot, h.first_token)
        return True


class DisaggRouter:
    """The front door of a disaggregated pair: routes each request to
    the decode engine directly (local path) or through the prefill ->
    migrate -> decode relay. Everything the worker layer touches on an
    engine that is NOT explicitly overridden here delegates to the
    decode engine — the router is a drop-in for ``LLMEngine`` from the
    backend's point of view."""

    def __init__(self, prefill, decode) -> None:
        self.prefill = prefill
        self.decode = decode
        self.bus = MigrationBus(page=prefill._page)
        prefill._migrator = Migrator(prefill, self.bus, "prefill")
        decode._migrator = Migrator(decode, self.bus, "decode")
        # the prefill engine's active slots run PROMPTS: an expiry
        # there is a prefill-stage overrun, not a decode one
        prefill._deadline_stage = "prefill"
        self.min_prompt = max(1, knobs.int_("LOCALAI_DISAGG_MIN_PROMPT"))
        self.min_ms = knobs.float_("LOCALAI_DISAGG_MIN_MS")
        self.migrate_deadline_s = max(
            0.1, knobs.float_("LOCALAI_DISAGG_MIGRATE_DEADLINE_S"))
        self._mlabel = decode._mlabel
        self._pumps: set = set()  # lint: guarded-by self._plock
        self._plock = threading.Lock()
        self._closed = False

    # ------------------------------------------------------- routing

    def _use_disagg(self, req: GenRequest) -> bool:
        if req.soft_embeds is not None or req.prompt_cache_path:
            return False  # image KV / disk-cache paths stay local
        if req.max_tokens <= 1:
            return False  # the probe WOULD BE the whole request
        n = len(req.prompt_ids)
        if n < self.min_prompt or n >= self.decode.max_seq:
            return False
        if self.min_ms > 0:
            cm = getattr(self.prefill, "_costmodel", None)
            tok_ms = cm.prefill_token_ms() if cm is not None else None
            if tok_ms is not None and tok_ms * n < self.min_ms:
                return False  # predicted prefill too cheap to relay
        return True

    def submit(self, req: GenRequest) -> queue.SimpleQueue:
        return self.submit_many([req])[0]

    def submit_many(
            self, reqs: list[GenRequest]) -> list[queue.SimpleQueue]:
        outs: list = [None] * len(reqs)
        local_idx: list[int] = []
        for i, req in enumerate(reqs):
            if self._closed or not self._use_disagg(req):
                local_idx.append(i)
                continue
            out: queue.SimpleQueue = queue.SimpleQueue()
            outs[i] = out
            tname = f"disagg-pump-{req.id[:8]}"
            t = threading.Thread(target=self._pump, args=(req, out),
                                 daemon=True, name=tname)
            with self._plock:
                self._pumps.add(t)
            t.start()
        if local_idx:
            local_outs = self.decode.submit_many(
                [reqs[i] for i in local_idx])
            for i, out in zip(local_idx, local_outs):
                outs[i] = out
                tm.ENGINE_DISAGG_REQUESTS.labels(
                    model=self._mlabel, path="local").inc()
        return outs

    def generate(self, req: GenRequest) -> StreamEvent:
        q = self.submit(req)
        while True:
            ev = q.get()
            if ev.done:
                return ev

    def cancel(self, request_id: str) -> None:
        self.decode.cancel(request_id)
        self.prefill.cancel(request_id + PREFILL_SUFFIX)

    # --------------------------------------------------------- relay

    def _pump(self, req: GenRequest, out: queue.SimpleQueue) -> None:
        """One disaggregated request's relay thread: run the prefill
        probe, collect the migration, resubmit onto the decode engine
        (the client's queue rides along — no per-token forwarding hop).
        Exactly ONE terminal event reaches ``out`` on every path."""
        rid = req.id
        owned = True  # until the decode engine owns the client stream
        try:
            now0 = time.perf_counter()
            req.t_submit = now0
            budget = req.timeout_s or self.decode._default_deadline_s
            if budget > 0:
                req.deadline = now0 + budget
            # open (or extend) the request's trace before minting the
            # shared id — trace_id_of returns "" on a never-seen id
            TRACER.event(rid, "queue", t=now0, model=self._mlabel)
            if not req.trace_id:
                req.trace_id = TRACER.trace_id_of(rid)
            TRACER.annotate(rid, "disagg", t=now0,
                            prompt_tokens=len(req.prompt_ids))
            self.bus.register(rid)
            probe = dataclasses.replace(
                req, id=rid + PREFILL_SUFFIX, max_tokens=1,
                disagg=None, prompt_cache_path="",
                prompt_cache_all=False, t_submit=0.0, deadline=0.0,
                timeout_s=(max(0.05, req.deadline - now0)
                           if req.deadline else 0.0))
            # the probe rides the SAME distributed trace: one joined
            # trace covers queue -> prefill -> migrate -> decode
            TRACER.start(probe.id, model=self._mlabel,
                         trace_id=req.trace_id)
            probe_q = self.prefill.submit(probe)
            term: Optional[StreamEvent] = None
            buffered: list[StreamEvent] = []
            while term is None:
                ev = probe_q.get()
                if ev.done:
                    term = ev
                else:
                    buffered.append(ev)
            migratable = (term.finish_reason == "length"
                          and term.completion_tokens == 1
                          and not term.error)
            if not migratable:
                if term.finish_reason in ("error", "shed"):
                    # the decode engine may still serve it the plain
                    # way (its own queue/limits decide)
                    owned = self._fallback(req, out)
                    return
                # the request genuinely COMPLETED at its first token
                # (stop hit, EOS, max-length edge, deadline, cancel):
                # the probe's stream IS the answer — forward it
                for ev in buffered:
                    out.put(ev)
                out.put(term)
                owned = False
                tm.ENGINE_DISAGG_REQUESTS.labels(
                    model=self._mlabel, path="disagg").inc()
                TRACER.event(rid, "done")
                TRACER.annotate(rid, "terminal",
                                outcome=term.finish_reason,
                                stage="prefill")
                TRACER.finish(rid, status=term.finish_reason)
                return
            tm.ENGINE_DISAGG_STAGE.labels(
                model=self._mlabel, stage="queued").observe(
                max(0.0, term.timing_queue_ms) / 1e3)
            tm.ENGINE_DISAGG_STAGE.labels(
                model=self._mlabel, stage="prefill").observe(
                max(0.0, term.timing_prompt_processing_ms) / 1e3)
            nowm = time.perf_counter()
            tmo = self.migrate_deadline_s
            if req.deadline:
                tmo = min(tmo, max(0.0, req.deadline - nowm))
            h = why = None
            span = TRACER.begin_span(rid, "migrate", t=nowm)
            try:
                h, why = self.bus.collect(rid, timeout=tmo)
            finally:
                dur_ms = (time.perf_counter() - nowm) * 1e3
                if h is not None:
                    TRACER.end_span(span, bytes=h.nbytes, pages=h.npg,
                                    ms=round(dur_ms, 3))
                else:
                    TRACER.end_span(span, failed=why or "unknown",
                                    ms=round(dur_ms, 3))
            nowr = time.perf_counter()
            if h is None and req.deadline and nowr >= req.deadline:
                # the migrate stage overran the request deadline: emit
                # the terminal HERE with the stage attributed (neither
                # engine owns the request at this instant)
                out.put(StreamEvent(
                    done=True, finish_reason="deadline_exceeded",
                    error="deadline exceeded during KV migration"))
                owned = False
                tm.ENGINE_REQUESTS.labels(
                    model=self._mlabel,
                    reason="deadline_exceeded").inc()
                tm.ENGINE_DEADLINE_EXCEEDED.labels(
                    model=self._mlabel, stage="migrate").inc()
                tm.ENGINE_DISAGG_REQUESTS.labels(
                    model=self._mlabel, path="fallback").inc()
                TRACER.event(rid, "done")
                TRACER.annotate(rid, "terminal",
                                outcome="deadline_exceeded",
                                stage="migrate")
                TRACER.finish(rid, status="deadline_exceeded")
                return
            if h is None:
                owned = self._fallback(req, out)
                return
            mig_ms = (nowr - nowm) * 1e3
            h.migrate_ms = mig_ms
            h.t_resubmit = nowr
            tm.ENGINE_KV_MIGRATION.labels(
                model=self._mlabel).observe(mig_ms / 1e3)
            tm.ENGINE_DISAGG_STAGE.labels(
                model=self._mlabel, stage="migrate").observe(
                mig_ms / 1e3)
            req.disagg = h
            self.decode.submit_many([req], outs=[out])
            owned = False
            tm.ENGINE_DISAGG_REQUESTS.labels(
                model=self._mlabel, path="disagg").inc()
        except Exception:
            log.exception("disagg relay for %s failed", rid)
            if owned:
                out.put(StreamEvent(
                    done=True, finish_reason="error",
                    error="disaggregated relay failed"))
                owned = False
                tm.ENGINE_REQUESTS.labels(
                    model=self._mlabel, reason="error").inc()
                TRACER.event(rid, "done")
                TRACER.annotate(rid, "terminal", outcome="error",
                                detail="disagg relay failure")
                TRACER.finish(rid, status="error")
        finally:
            self.bus.forget(rid)
            with self._plock:
                self._pumps.discard(threading.current_thread())

    def _fallback(self, req: GenRequest, out) -> bool:
        """Re-prefill the request on the decode engine (migration
        failed or was never viable). Returns the new ``owned`` flag —
        False: the decode engine owns the stream now."""
        req.disagg = None
        tm.ENGINE_DISAGG_REQUESTS.labels(
            model=self._mlabel, path="fallback").inc()
        self.decode.submit_many([req], outs=[out])
        return False

    # ----------------------------------------------------- lifecycle

    @property
    def params(self):
        return self.decode.params

    @params.setter
    def params(self, value) -> None:
        # LoRA hot-merge swaps weights on BOTH engines: a migrated
        # prompt must have been prefilled by the same weights that
        # decode it
        self.decode.params = value
        self.prefill.params = value

    def start(self) -> None:
        self.prefill.start()
        self.decode.start()

    def warmup(self) -> None:
        self.decode.warmup()
        self.prefill.warmup()

    def close(self) -> None:
        self._closed = True
        self.bus.close()
        self.prefill.close()
        self.decode.close()

    def __getattr__(self, name: str):
        # everything not overridden (tokenize, embed, metrics, spec,
        # tokenizer, max_seq, ...) is the decode engine's
        return getattr(self.decode, name)


def build_prefill_engine(spec, params, tokenizer, *, decode,
                         cache_dtype=None, tag: str = ""):
    """A prefill-tuned sibling for ``decode``: few large slots (a
    prefill flood is compute-bound — slot count buys nothing), the same
    bucket ladder and context, k=2 decode scan (each probe decodes
    exactly one token past its prompt), no KV tier (probe slots live
    one prompt each; the migration bus is their interchange), and —
    CRITICALLY — the same sampler penalty window, so a captured sampler
    row scatters into the decode engine's state shape-exactly. Shares
    ``params`` by reference: no second copy of the weights in HBM."""
    from .engine import LLMEngine

    kwargs = dict(
        n_slots=max(1, knobs.int_("LOCALAI_DISAGG_PREFILL_SLOTS")),
        max_seq=decode.max_seq,
        prefill_buckets=decode.prefill_buckets,
        penalty_window=decode.sampling.window,
        decode_steps=2,
        latency_target_ms=None,
        autostart=False,
        kv_tier=False,
        # shares `params` by reference with the decode engine: paging
        # either side out would strand the other's dispatches
        weight_paging=False,
        tag=(tag + "-prefill") if tag else "prefill",
    )
    if cache_dtype is not None:
        kwargs["cache_dtype"] = cache_dtype
    return LLMEngine(spec, params, tokenizer, **kwargs)
