"""Tiered KV memory: HBM pages, host-RAM spill, on-disk cold sessions.

The paged pool (kv_pool.py) made HBM scale with live tokens, but a
RETURNING session — a chat user who steps away and comes back — still
costs either resident HBM pages held idle or a full re-prefill. This
module adds two tiers under the HBM arena so resident-session capacity
is bound by host RAM (and then disk), not HBM:

- HOT: pages in the device arena, owned by PagePool. Unchanged.
- WARM: pages spilled to host RAM as numpy arrays (native KV dtype,
  int8 scale planes included), moved by an async D2H gather enqueued on
  the device stream — ``copy_to_host_async`` + ``is_ready`` polling
  through ``TransferWindow.reap`` (models/staging.py), so a spill NEVER
  blocks a device step. The gather is written one ``dynamic_slice`` a
  page because plain ``arr[:, tbl]`` compiles, on a v5e, to a copy of
  the WHOLE plane into lane-wide pieces before it walks the pages
  (6.7 ms a call on a 2.2 GB plane against 0.1 ms for its 4 pages), a
  cost that follows the pool's size and not the spill's. Promotion
  stages pages into a pseudo-slot page table (ids >= n_slots — the
  pool is keyed by int, not bounded by the slot array) via an async
  H2D scatter overlapped with the request's queue wait, then adopts
  them into the assigned slot by reference (``share``), so a prefetch
  hit re-prefills zero tokens.
- COLD: whole sessions demoted to the on-disk prompt-cache format
  (np.savez tokens/k/v[/k_scale/v_scale], slot-contiguous [L, n, F]) —
  the SAME format ``prompt_cache_path`` reads and writes, produced and
  consumed here by background threads so the scheduler never waits on
  the filesystem. A request whose session is cold waits in the
  admission queue (bounded by a deadline) while the load runs; past
  the deadline it admits normally and re-prefills.

Why correctness is cheap here:

- Device-order serialization: a spill's gather is enqueued before any
  later dispatch can recycle its source pages, so the copy reads
  pre-overwrite content even if the table is dropped immediately (the
  same argument kv_pool.prepare_write makes for COW source pages). The
  pool-side ``pin`` exists to protect the ACCOUNTING of background
  spills, not the content.
- Content addressing: a KV page holding positions [0, (i+1)*page) is a
  pure function of the token prefix through the page end (causal
  attention), so warm pages dedup by token-prefix hash — a prefix
  shared by N sessions spills ONCE, with refcounts, and needs no
  invalidation machinery (the key never goes stale because it IS the
  content identity). This is the host-RAM mirror of the pool's
  refcounted prefix sharing.

All tier state is mutated on the engine scheduler thread; background
threads touch only their own file I/O and hand results back through
queues. ``LOCALAI_KV_TIER=off`` removes every hook (meshed, multihost,
follower and draft-model engines force it off — spilled main-model
pages would strand a draft cache, and the arena is single-chip-only).
"""

from __future__ import annotations

import hashlib
import os
import queue
import threading
import time
import uuid
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..config import knobs
import numpy as np

from ..models.staging import TransferWindow
from ..telemetry import metrics as tm
from ..telemetry.flightrec import FLIGHT
from ..utils import faultinject
from .kv_pool import TRASH_PAGE, PagePoolExhausted

__all__ = ["KVTierManager", "write_cache_file", "read_cache_file"]


# ------------------------------------------------------------ cold format
#
# The cold tier IS the prompt-cache on-disk format: one np.savez with
# tokens (int32 [n]) and slot-contiguous rows k/v ([L, n, F]; int8 adds
# k_scale/v_scale [L, n]). bf16 rows are widened to f32 (no portable
# numpy encoding); the restore path casts back. Files written here are
# readable through prompt_cache_path on any engine — paged or dense —
# and vice versa.


def write_cache_file(path: str, tokens: np.ndarray, k: np.ndarray,
                     v: np.ndarray,
                     scales: Optional[tuple] = None) -> None:
    """Atomically persist one session in the prompt-cache format."""

    def host(arr):  # bf16 has no portable numpy encoding
        out = np.asarray(arr)
        return out if out.dtype in (np.int8, np.float32) \
            else out.astype(np.float32)

    payload = {"tokens": np.asarray(tokens, np.int32),
               "k": host(k), "v": host(v)}
    if scales is not None:
        payload["k_scale"] = np.asarray(scales[0])
        payload["v_scale"] = np.asarray(scales[1])
    # unique temp name: concurrent saves to one path must not truncate
    # each other's half-written file before os.replace
    tmp = f"{path}.tmp.{uuid.uuid4().hex[:8]}"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(tmp, "wb") as f:
        np.savez(f, **payload)
    os.replace(tmp, path)


def read_cache_file(path: str):
    """Open a prompt-cache/cold-tier file (lazy NpzFile mapping with
    keys tokens/k/v[/k_scale/v_scale])."""
    return np.load(path)


# --------------------------------------------------------- device helpers


@jax.jit
def _gather_pages(arr, tbl):
    # [L, n_pages, ...] x [b] -> [L, b, ...]; padded entries read the
    # trash page (no data) and are ignored by the finalize slicing.
    # One dynamic slice a page (b is static, a power of two): the call
    # reads b pages and writes b, whatever the pool (module docstring)
    return lax.concatenate(
        [lax.dynamic_slice_in_dim(arr, tbl[i], 1, axis=1)
         for i in range(tbl.shape[0])], 1)


@partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(arr, tbl, rows):
    # padded entries write the trash page — the established discard
    # target for routed-away writes
    return arr.at[:, tbl].set(rows.astype(arr.dtype))


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _common_prefix(a, b) -> int:
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


# ------------------------------------------------------------ host store


@dataclass
class _HostPage:
    """One spilled KV page in host RAM: native-dtype rows plus scale
    planes, refcounted across entries (content-addressed pages shared
    by several sessions hold one copy)."""
    arrays: dict  # k/v [L, P, F]; k_scale/v_scale [L, P] when int8
    nbytes: int
    ref: int = 0
    key: Optional[bytes] = None  # content hash; full pages only


@dataclass
class _Entry:
    """One demoted session: an exact token prefix and the host (or
    disk) pages holding its KV."""
    eid: int
    tokens: list
    n: int
    hpids: list  # warm/saving; emptied when cold
    state: str  # warm | saving | cold | loading
    path: Optional[str] = None
    last_used: float = 0.0


@dataclass
class _Spill:
    slot_idx: int
    tokens: list
    n: int
    plan: list  # ("dup", hpid) | ("copy", j, key-or-None) per page
    copies: list  # device page ids gathered (unpin set)
    handles: tuple  # gather outputs bound for host
    nbytes: int
    t0: float
    urgent: bool
    pinned: bool


@dataclass
class _Fetch:
    entry: _Entry
    stage: int  # pseudo-slot id holding the staged table
    n: int
    t0: float


class KVTierManager:
    """Demotion/promotion policy and bookkeeping for the three tiers.

    Owned by one paged, single-chip engine; every public method runs on
    its scheduler thread (tests may call ``tick``/``settle`` only while
    the scheduler is quiescent). ``self._lock`` guards the host store
    for cross-thread readers (stats endpoints, profilers); background
    save/load threads never touch tier state directly — they post to
    ``_done_saves``/``_done_loads`` and the next ``tick`` applies."""

    # pseudo-slot ids for staged promotions (bounded: a fetch holds one)
    N_STAGE = 4
    # staged pages not adopted within this window are abandoned (the
    # request was cancelled or its admission stalled behind a full pool)
    STAGE_TTL_S = 5.0
    _SCAN_EVERY_S = 0.05  # demotion/eviction policy cadence

    def __init__(self, eng) -> None:
        self.eng = eng
        self.P = eng._page
        self._mlabel = eng._mlabel
        self.host_budget = int(
            knobs.float_("LOCALAI_KV_TIER_HOST_MB") * (1 << 20))
        self.watermark = min(1.0, max(0.05, knobs.float_(
            "LOCALAI_KV_TIER_WATERMARK")))
        self.idle_s = max(0.0, knobs.float_("LOCALAI_KV_TIER_IDLE_S"))
        self.cold_s = max(0.0, knobs.float_("LOCALAI_KV_TIER_COLD_S"))
        self.fetch_deadline_s = max(0.05, knobs.float_(
            "LOCALAI_KV_TIER_FETCH_DEADLINE_S"))
        self.cold_dir = knobs.str_("LOCALAI_KV_TIER_DIR")
        self._lock = threading.Lock()
        self._host: dict[int, _HostPage] = {}  # lint: guarded-by self._lock
        self._dedup: dict[bytes, int] = {}  # lint: guarded-by self._lock
        self._entries: dict[int, _Entry] = {}  # lint: guarded-by self._lock
        self._host_bytes = 0
        self._disk_pages = 0
        self._next_id = 1
        # in-flight transfers (scheduler-thread-owned)
        self._swin = TransferWindow(int(
            knobs.float_("LOCALAI_KV_TIER_INFLIGHT_MB") * (1 << 20)))
        self._fwin = TransferWindow(1 << 62)  # tracking only, no cap
        self._spilling: set[int] = set()  # slot idxs with a spill aloft
        self._fetches: dict[str, _Fetch] = {}  # req.id -> staged fetch
        self._stage_free = [eng.n_slots + i for i in range(self.N_STAGE)]
        self._waiting: dict[str, float] = {}  # req.id -> cold deadline
        self._late: set[str] = set()  # deadline passed: re-prefill
        self._done_loads: queue.SimpleQueue = queue.SimpleQueue()
        self._done_saves: queue.SimpleQueue = queue.SimpleQueue()
        self._io_threads: list[threading.Thread] = []
        self._last_active: dict[int, float] = {}
        self._t_scan = 0.0
        self._t_born = time.perf_counter()
        # host-side tallies for tools/profile_kv.py and bench extras
        # (the Prometheus families are process-cumulative; these are
        # per-engine ground truth)
        self.counters = {
            "spills": 0, "spilled_pages": 0, "dedup_pages": 0,
            "fetches": 0, "reused_tokens": 0, "prefetch_hit": 0,
            "prefetch_late": 0, "prefetch_miss": 0,
            "prefetch_expired": 0, "saves": 0, "loads": 0,
            "spill_faults": 0, "fetch_faults": 0,
        }

    # ------------------------------------------------------------- policy

    def tick(self) -> None:
        """One policy step, piggybacked on the scheduler's admission
        pass: harvest completed transfers, apply background-thread
        results, expire stale stages, and (rate-limited) run the
        demotion/eviction watermarks. Never blocks on the device."""
        now = time.perf_counter()
        for sp in self._swin.reap():
            self._finalize_spill(sp, now)
        for npg, nbytes, t0 in self._fwin.reap():
            FLIGHT.transfer("fetch", t0, now - t0, npg, nbytes)
            tm.ENGINE_KV_TIER_MOVES.labels(
                model=self._mlabel, direction="fetch", outcome="ok").inc()
            tm.ENGINE_KV_TIER_BYTES.labels(
                model=self._mlabel, direction="fetch").inc(nbytes)
        self._apply_io_results(now)
        self._expire_stages(now)
        if now - self._t_scan >= self._SCAN_EVERY_S:
            self._t_scan = now
            self._scan(now)

    def _scan(self, now: float) -> None:
        eng = self.eng
        for s in eng.slots:
            if s.active:
                self._last_active[s.idx] = now
        st = eng._pool.stats()
        if st.total and st.in_use / st.total >= self.watermark:
            cands = [
                s for s in eng.slots
                if not s.active and s.cache_tokens
                and s.idx not in self._spilling
                and eng._pool.held(s.idx)
                and now - self._last_active.get(s.idx, self._t_born)
                >= self.idle_s]
            mono = time.monotonic()
            cands.sort(key=lambda s: eng._prefix_index.value(s.idx, mono))
            for s in cands[:2]:
                self._spill(s, urgent=False, now=now)
        if self.cold_s and self.cold_dir:
            with self._lock:
                stale = [e for e in self._entries.values()
                         if e.state == "warm"
                         and now - e.last_used >= self.cold_s]
            for e in stale[:2]:
                self._start_save(e)
        evicted = 0
        while self._host_bytes > self.host_budget and evicted < 4:
            if not self._evict_one(now):
                break
            evicted += 1

    def _evict_one(self, now: float) -> bool:
        """Push the least-recently-used warm entry down a tier: save to
        disk when a cold dir is configured, discard otherwise."""
        with self._lock:
            warm = [e for e in self._entries.values()
                    if e.state == "warm"]
        if not warm:
            return False
        victim = min(warm, key=lambda e: e.last_used)
        if self.cold_dir:
            self._start_save(victim)
            # saving frees host pages only at completion; stop the
            # eviction sweep here rather than queue every warm entry
            return False
        self._drop_entry(victim)
        tm.ENGINE_KV_TIER_MOVES.labels(
            model=self._mlabel, direction="save",
            outcome="aborted").inc()
        return True

    # -------------------------------------------------------------- spill

    def capture(self, slot, req) -> None:
        """Demote-on-reuse: the slot is about to be reassigned and
        _assign's prepare_write will discard every resident page beyond
        the new request's common prefix. Enqueue the spill FIRST —
        device-order serialization lets the gather read pre-overwrite
        content even though the pages recycle right after — so slot
        churn moves sessions down a tier instead of erasing them."""
        common = _common_prefix(slot.cache_tokens, req.prompt_ids)
        if len(slot.cache_tokens) - common >= self.P:
            self._spill(slot, urgent=True, now=time.perf_counter())

    def demote_urgent(self, slot) -> bool:
        """Pool-pressure demotion: called by the engine's reclaim path
        immediately before it drops the victim's table. Enqueues the
        D2H gather and returns — the caller's drop proceeds regardless
        (device-order keeps the copy coherent), so the allocator's
        observable behavior is identical to a plain reclaim."""
        return self._spill(slot, urgent=True, now=time.perf_counter())

    def _spill(self, slot, urgent: bool, now: float) -> bool:
        eng = self.eng
        if slot.idx in self._spilling:
            return True  # the in-flight spill already covers this state
        tokens = list(slot.cache_tokens)
        n = min(len(tokens), eng.max_seq)
        if n < self.P:
            return False  # under one page: re-prefill is cheaper
        if self._covered(tokens, n):
            self._touch_covering(tokens, n, now)
            tm.ENGINE_KV_TIER_MOVES.labels(
                model=self._mlabel, direction="spill",
                outcome="dedup").inc()
            return True
        if not urgent and self._swin.over(1):
            return False  # in-flight spill budget full: retry next scan
        try:
            if faultinject.ACTIVE:
                faultinject.fire("kv_tier.spill")
        except faultinject.InjectedFault:
            # spill abandoned BEFORE any bookkeeping: for an urgent
            # demote the caller's drop falls back to today's plain
            # reclaim (the session re-prefills on return); pool state
            # stays leak_check-clean by construction
            self.counters["spill_faults"] += 1
            tm.ENGINE_KV_TIER_MOVES.labels(
                model=self._mlabel, direction="spill",
                outcome="fault").inc()
            return False
        npg = eng._pool.pages_for(n)
        table = eng._pool.table(slot.idx)[:npg]
        if len(table) < npg:
            return False  # table shorter than the token run: skip
        plan: list = []
        copies: list[int] = []
        with self._lock:
            for i in range(npg):
                end = (i + 1) * self.P
                key = self._page_key(tokens, end) if end <= n else None
                hpid = self._dedup.get(key) if key is not None else None
                if hpid is not None:
                    # hold the shared page for the in-flight spill so
                    # eviction cannot free it before finalize
                    self._host[hpid].ref += 1
                    plan.append(("dup", hpid))
                else:
                    plan.append(("copy", len(copies), key))
                    copies.append(table[i])
        if not copies:
            # every page dedup'd: the entry materializes with no DMA
            sp = _Spill(slot.idx, tokens, n, plan, [], (), 0, now,
                        urgent, False)
            self._finalize_spill(sp, now)
            return True
        c = eng.cache
        tbl = jnp.asarray(np.asarray(
            copies + [TRASH_PAGE] * (_pow2(len(copies)) - len(copies)),
            np.int32))
        # under the engine's load watch: one program per (page count,
        # plane shape), each loaded on first use inside the serving loop
        def gather(arr):
            return eng._loads.call(
                _gather_pages, "kv_gather",
                ("kv_gather", int(tbl.shape[0]), tuple(arr.shape[2:]),
                 str(arr.dtype)), arr, tbl)

        handles = [gather(c.k), gather(c.v)]
        if c.quantized:
            handles.append(gather(c.k_scale))
            handles.append(gather(c.v_scale))
        for h in handles:
            h.copy_to_host_async()
        nbytes = sum(int(h.nbytes) for h in handles)
        pinned = not urgent
        if pinned:
            # background spill: the slot stays resident until the copy
            # lands; pin the gathered pages so a concurrent reclaim's
            # drop can't recycle their ids under the bookkeeping
            eng._pool.pin(copies)
            self._spilling.add(slot.idx)
        sp = _Spill(slot.idx, tokens, n, plan, copies, tuple(handles),
                    nbytes, now, urgent, pinned)
        self._swin.add(sp, nbytes, sp.handles)
        return True

    def _finalize_spill(self, sp: _Spill, now: float) -> None:
        """Turn a landed spill into warm host pages + an entry. Runs at
        harvest (handles already ready), so the np.asarray calls are
        host-memory copies, not device syncs."""
        eng = self.eng
        hostside = [np.asarray(h) for h in sp.handles]
        names = ["k", "v", "k_scale", "v_scale"][:len(hostside)]
        hpids: list[int] = []
        with self._lock:
            for step in sp.plan:
                if step[0] == "dup":
                    hpids.append(step[1])  # ref already held at plan
                    self.counters["dedup_pages"] += 1
                    continue
                _, j, key = step
                arrays = {nm: np.array(a[:, j])
                          for nm, a in zip(names, hostside)}
                nbytes = sum(a.nbytes for a in arrays.values())
                hpid = self._next_id
                self._next_id += 1
                if key is not None and key in self._dedup:
                    key = None  # racing spill published it first
                self._host[hpid] = _HostPage(arrays, nbytes, ref=1,
                                             key=key)
                if key is not None:
                    self._dedup[key] = hpid
                self._host_bytes += nbytes
                hpids.append(hpid)
            ent = _Entry(self._next_id, sp.tokens, sp.n, hpids, "warm",
                         last_used=now)
            self._next_id += 1
            self._entries[ent.eid] = ent
            # an older entry that is a strict prefix of this one is
            # subsumed (its pages live on via the dedup refs)
            for old in [e for e in self._entries.values()
                        if e is not ent and e.state == "warm"
                        and e.n <= sp.n
                        and e.tokens[:e.n] == sp.tokens[:e.n]]:
                self._drop_entry_locked(old)
        self.counters["spills"] += 1
        self.counters["spilled_pages"] += len(sp.copies)
        if sp.pinned:
            eng._pool.unpin(sp.copies)
            self._spilling.discard(sp.slot_idx)
            slot = eng.slots[sp.slot_idx]
            if not slot.active and slot.cache_tokens == sp.tokens:
                # the demotion's point: the resident copy moves DOWN —
                # release the HBM pages now that host RAM holds them
                eng._pool.drop(slot.idx)
                slot.cache_tokens = []
                slot.n_past = 0
                eng._prefix_index.remove(slot.idx)
        if sp.copies:
            FLIGHT.transfer("spill", sp.t0, now - sp.t0,
                            len(sp.copies), sp.nbytes)
            tm.ENGINE_KV_TIER_BYTES.labels(
                model=self._mlabel, direction="spill").inc(sp.nbytes)
        tm.ENGINE_KV_TIER_MOVES.labels(
            model=self._mlabel, direction="spill", outcome="ok").inc()

    # -------------------------------------------------------- promotion

    def plan(self, req, now: float) -> bool:
        """Admission-time prefetch: when a tier entry covers the
        request's prompt, stage its pages back into the arena (async
        H2D, overlapped with the rest of the wave). Returns True when
        the request should requeue — its session is cold and the disk
        load is still inside the deadline window."""
        rid = req.id
        if rid in self._fetches or rid in self._late:
            return False
        ent, n = self._lookup(req.prompt_ids)
        if ent is None or not self._worth(req, n):
            return False
        if ent.state in ("warm", "saving"):
            self._stage(req, ent, n, now)
            return False
        # cold / loading: hold the request while the background load
        # runs, but never past the deadline — a slow disk degrades to
        # today's re-prefill, it cannot stall admission
        deadline = self._waiting.get(rid)
        if deadline is None:
            self._waiting[rid] = now + self.fetch_deadline_s
            if ent.state == "cold":
                self._start_load(ent)
            return True
        if now > deadline:
            self._waiting.pop(rid, None)
            self._late.add(rid)
            return False
        return True

    def adopt(self, slot, req) -> int:
        """Attach a staged fetch to the slot the request was assigned:
        the stage table is shared in by reference and the slot's
        resident prefix becomes the promoted session, so _assign's
        ordinary prefix-reuse path skips the covered tokens. Returns
        the number of promoted tokens (0 = re-prefill)."""
        now = time.perf_counter()
        rid = req.id
        self._waiting.pop(rid, None)
        f = self._fetches.pop(rid, None)
        if f is None and rid not in self._late:
            ent, n = self._lookup(req.prompt_ids)
            if ent is not None and ent.state in ("warm", "saving") \
                    and self._worth(req, n):
                # not planned ahead (e.g. zero queue wait): stage now —
                # the scatter is still only ENQUEUED before the prefill
                # that follows it in program order, so it costs no sync
                if self._stage(req, ent, n, now):
                    f = self._fetches.pop(rid, None)
        if f is None:
            result = "late" if rid in self._late else "miss"
            self._late.discard(rid)
            self.counters["prefetch_" + result] += 1
            tm.ENGINE_KV_TIER_PREFETCH.labels(
                model=self._mlabel, result=result).inc()
            return 0
        eng = self.eng
        if _common_prefix(slot.cache_tokens, req.prompt_ids) >= f.n:
            # the assigned slot already holds a better resident prefix;
            # the staged copy is redundant — abandon it
            self._abandon_fetch(rid, f)
            return 0
        npg = eng._pool.held(f.stage)
        eng._pool.share(slot.idx, f.stage, npg)
        eng._pool.drop(f.stage)
        self._stage_free.append(f.stage)
        slot.cache_tokens = list(f.entry.tokens[:f.n])
        slot.n_past = f.n
        if eng._prefix_enabled:
            eng._prefix_index.set_tokens(slot.idx, slot.cache_tokens)
        f.entry.last_used = now
        self.counters["prefetch_hit"] += 1
        self.counters["reused_tokens"] += f.n
        tm.ENGINE_KV_TIER_PREFETCH.labels(
            model=self._mlabel, result="hit").inc()
        return f.n

    def _stage(self, req, ent: _Entry, n: int, now: float) -> bool:
        eng = self.eng
        try:
            if faultinject.ACTIVE:
                faultinject.fire("kv_tier.fetch")
        except faultinject.InjectedFault:
            # promotion abandoned with NO pool or cache mutation: the
            # request admits normally and re-prefills (the warm entry
            # survives for the next attempt)
            self.counters["fetch_faults"] += 1
            self._late.add(req.id)
            tm.ENGINE_KV_TIER_MOVES.labels(
                model=self._mlabel, direction="fetch",
                outcome="fault").inc()
            return False
        if not self._stage_free:
            return False
        sid = self._stage_free.pop()
        try:
            eng._pool.ensure(sid, n)
        except PagePoolExhausted:
            eng._pool.drop(sid)  # release any partial allocation
            self._stage_free.append(sid)
            return False
        table = eng._pool.table(sid)
        npg = len(table)
        b = _pow2(npg)
        c = eng.cache
        L, F = c.k.shape[0], c.k.shape[-1]
        rk = np.zeros((L, b, self.P, F), c.k.dtype)
        rv = np.zeros((L, b, self.P, F), c.v.dtype)
        rks = rvs = None
        if c.quantized:
            rks = np.zeros((L, b, self.P), np.float32)
            rvs = np.zeros((L, b, self.P), np.float32)
        with self._lock:
            for i, hpid in enumerate(ent.hpids[:npg]):
                hp = self._host[hpid]
                rk[:, i] = hp.arrays["k"]
                rv[:, i] = hp.arrays["v"]
                if rks is not None:
                    rks[:, i] = hp.arrays["k_scale"]
                    rvs[:, i] = hp.arrays["v_scale"]
        tbl = jnp.asarray(np.asarray(
            table + [TRASH_PAGE] * (b - npg), np.int32))
        dk, dv = jax.device_put(rk), jax.device_put(rv)
        def scatter(arr, rows):
            return eng._loads.call(
                _scatter_pages, "kv_scatter",
                ("kv_scatter", b, tuple(arr.shape[2:]), str(arr.dtype)),
                arr, tbl, rows)

        ck = scatter(c.k, dk)
        cv = scatter(c.v, dv)
        ks, vs = c.k_scale, c.v_scale
        handles = [dk, dv]
        if c.quantized:
            dks, dvs = jax.device_put(rks), jax.device_put(rvs)
            ks = scatter(ks, dks)
            vs = scatter(vs, dvs)
            handles += [dks, dvs]
        eng.cache = type(c)(k=ck, v=cv, k_scale=ks, v_scale=vs)
        nbytes = sum(int(h.nbytes) for h in handles)
        self._fwin.add((npg, nbytes, now), nbytes, tuple(handles))
        self._fetches[req.id] = _Fetch(ent, sid, n, now)
        self.counters["fetches"] += 1
        ent.last_used = now
        return True

    def _abandon_fetch(self, rid: str, f: _Fetch) -> None:
        self.eng._pool.drop(f.stage)
        self._stage_free.append(f.stage)
        self.counters["prefetch_expired"] += 1
        tm.ENGINE_KV_TIER_PREFETCH.labels(
            model=self._mlabel, result="expired").inc()

    def _expire_stages(self, now: float) -> None:
        for rid, f in list(self._fetches.items()):
            if now - f.t0 > self.STAGE_TTL_S:
                del self._fetches[rid]
                self._abandon_fetch(rid, f)

    def _worth(self, req, n: int) -> bool:
        if n < self.P:
            return False
        eng = self.eng
        have = max((_common_prefix(s.cache_tokens, req.prompt_ids)
                    for s in eng.slots if not s.active), default=0)
        if eng._prefix_enabled:
            have = max(have, eng._prefix_index.match(req.prompt_ids)[0])
        # a resident/copyable prefix at least as long makes the host
        # fetch redundant; require one full page of net gain
        return n >= have + self.P

    def _lookup(self, prompt_ids) -> tuple[Optional[_Entry], int]:
        best, best_n = None, 0
        with self._lock:
            for e in self._entries.values():
                n = min(_common_prefix(e.tokens, prompt_ids), e.n,
                        self.eng.max_seq - 1)
                if n > best_n:
                    best, best_n = e, n
        return best, best_n

    # ------------------------------------------------------- cold tier IO

    def _cold_path(self, ent: _Entry) -> str:
        h = hashlib.sha1(np.asarray(ent.tokens[:ent.n],
                                    np.int64).tobytes()).hexdigest()[:24]
        return os.path.join(self.cold_dir,
                            f"kvtier-{self._mlabel}-{h}.npz")

    def _start_save(self, ent: _Entry) -> None:
        """Warm -> cold: background thread assembles the contiguous
        rows and writes the prompt-cache file; host pages release when
        the tick applies the completion."""
        if ent.state != "warm":
            return
        ent.state = "saving"
        with self._lock:
            pages = [self._host[h].arrays for h in ent.hpids]
        tokens = np.asarray(ent.tokens[:ent.n], np.int32)
        n, path, q = ent.n, self._cold_path(ent), self._done_saves

        def save():
            try:
                k = np.concatenate([p["k"] for p in pages],
                                   axis=1)[:, :n]
                v = np.concatenate([p["v"] for p in pages],
                                   axis=1)[:, :n]
                scales = None
                if "k_scale" in pages[0]:
                    scales = (
                        np.concatenate([p["k_scale"] for p in pages],
                                       axis=1)[:, :n],
                        np.concatenate([p["v_scale"] for p in pages],
                                       axis=1)[:, :n])
                write_cache_file(path, tokens, k, v, scales)
                q.put((ent.eid, path, None))
            except OSError as e:
                q.put((ent.eid, path, e))

        t = threading.Thread(target=save, daemon=True,
                             name="kv-tier-save")
        t.start()
        self._io_threads.append(t)

    def _start_load(self, ent: _Entry) -> None:
        if ent.state != "cold":
            return
        try:
            if faultinject.ACTIVE:
                faultinject.fire("kv_tier.fetch")
        except faultinject.InjectedFault:
            # the cold copy is unreachable this round: drop the entry so
            # waiting requests fall through to re-prefill at deadline
            self.counters["fetch_faults"] += 1
            tm.ENGINE_KV_TIER_MOVES.labels(
                model=self._mlabel, direction="load",
                outcome="fault").inc()
            self._drop_entry(ent)
            return
        ent.state = "loading"
        path, q = ent.path, self._done_loads

        def load():
            try:
                with read_cache_file(path) as data:
                    arrs = {nm: np.array(data[nm]) for nm in data.files}
                q.put((ent.eid, arrs, None))
            except (OSError, ValueError, KeyError) as e:
                q.put((ent.eid, None, e))

        t = threading.Thread(target=load, daemon=True,
                             name="kv-tier-load")
        t.start()
        self._io_threads.append(t)

    def _apply_io_results(self, now: float) -> None:
        while True:
            try:
                eid, path, err = self._done_saves.get_nowait()
            except queue.Empty:
                break
            with self._lock:
                ent = self._entries.get(eid)
            if ent is None or ent.state != "saving":
                continue
            if err is not None:
                ent.state = "warm"  # host pages still held: no loss
                tm.ENGINE_KV_TIER_MOVES.labels(
                    model=self._mlabel, direction="save",
                    outcome="fault").inc()
                continue
            ent.state = "cold"
            ent.path = path
            with self._lock:
                for hpid in ent.hpids:
                    self._deref_locked(hpid)
                ent.hpids = []
            npg = -(-ent.n // self.P)
            self._disk_pages += npg
            self.counters["saves"] += 1
            tm.ENGINE_KV_TIER_MOVES.labels(
                model=self._mlabel, direction="save", outcome="ok").inc()
            tm.ENGINE_KV_TIER_BYTES.labels(
                model=self._mlabel, direction="save").inc(
                self._entry_bytes(ent))
        while True:
            try:
                eid, arrs, err = self._done_loads.get_nowait()
            except queue.Empty:
                break
            with self._lock:
                ent = self._entries.get(eid)
            if ent is None or ent.state != "loading":
                continue
            if err is not None or "k" not in (arrs or {}):
                tm.ENGINE_KV_TIER_MOVES.labels(
                    model=self._mlabel, direction="load",
                    outcome="fault").inc()
                self._drop_entry(ent)
                continue
            self._install_loaded(ent, arrs, now)

    def _install_loaded(self, ent: _Entry, arrs: dict,
                        now: float) -> None:
        """Disk rows -> warm host pages (chopped to page granularity,
        full pages re-entering the dedup index)."""
        P = self.P
        n = min(ent.n, arrs["k"].shape[1])
        if n < P:
            self._drop_entry(ent)
            return
        ent.n = n
        npg = -(-n // P)
        names = ["k", "v"] + (
            ["k_scale", "v_scale"] if "k_scale" in arrs else [])
        hpids: list[int] = []
        nbytes_total = 0
        with self._lock:
            for i in range(npg):
                lo, hi = i * P, min((i + 1) * P, n)
                key = (self._page_key(ent.tokens, hi)
                       if hi == (i + 1) * P else None)
                hpid = self._dedup.get(key) if key is not None else None
                if hpid is not None:
                    self._host[hpid].ref += 1
                    hpids.append(hpid)
                    continue
                arrays = {}
                for nm in names:
                    a = np.zeros(
                        (arrs[nm].shape[0], P) + arrs[nm].shape[2:],
                        arrs[nm].dtype)
                    a[:, : hi - lo] = arrs[nm][:, lo:hi]
                    arrays[nm] = a
                nbytes = sum(a.nbytes for a in arrays.values())
                hpid = self._next_id
                self._next_id += 1
                self._host[hpid] = _HostPage(arrays, nbytes, ref=1,
                                             key=key)
                if key is not None:
                    self._dedup[key] = hpid
                self._host_bytes += nbytes
                nbytes_total += nbytes
                hpids.append(hpid)
            ent.hpids = hpids
            ent.state = "warm"
            ent.last_used = now
        self._disk_pages = max(0, self._disk_pages - npg)
        self.counters["loads"] += 1
        tm.ENGINE_KV_TIER_MOVES.labels(
            model=self._mlabel, direction="load", outcome="ok").inc()
        tm.ENGINE_KV_TIER_BYTES.labels(
            model=self._mlabel, direction="load").inc(nbytes_total)

    # --------------------------------------------------------- host store

    def _page_key(self, tokens, end: int) -> bytes:
        # causal attention: KV rows for positions [0, end) are a pure
        # function of tokens[:end], so the prefix hash IS the content id
        return hashlib.sha1(
            np.asarray(tokens[:end], np.int64).tobytes()).digest()

    def _covered(self, tokens, n: int) -> bool:
        with self._lock:
            return any(e.n >= n and e.tokens[:n] == tokens[:n]
                       for e in self._entries.values()
                       if e.state != "loading")

    def _touch_covering(self, tokens, n: int, now: float) -> None:
        with self._lock:
            for e in self._entries.values():
                if e.n >= n and e.tokens[:n] == tokens[:n]:
                    e.last_used = now

    def _deref_locked(self, hpid: int) -> None:
        # lint: holds self._lock
        hp = self._host[hpid]
        hp.ref -= 1
        if hp.ref > 0:
            return
        del self._host[hpid]
        self._host_bytes -= hp.nbytes
        if hp.key is not None and self._dedup.get(hp.key) == hpid:
            del self._dedup[hp.key]

    def _drop_entry(self, ent: _Entry) -> None:
        with self._lock:
            self._drop_entry_locked(ent)

    def _drop_entry_locked(self, ent: _Entry) -> None:
        # lint: holds self._lock
        if self._entries.pop(ent.eid, None) is None:
            return
        for hpid in ent.hpids:
            self._deref_locked(hpid)
        if ent.state == "cold":
            self._disk_pages = max(
                0, self._disk_pages - (-(-ent.n // self.P)))
        ent.hpids = []
        ent.state = "dropped"

    # ------------------------------------------------------- diagnostics

    def tier_pages(self, hbm_in_use: int) -> dict:
        with self._lock:
            return {"hbm": hbm_in_use, "host": len(self._host),
                    "disk": self._disk_pages}

    def stats(self) -> dict:
        with self._lock:
            warm = sum(1 for e in self._entries.values()
                       if e.state in ("warm", "saving"))
            cold = sum(1 for e in self._entries.values()
                       if e.state in ("cold", "loading"))
            return {
                "entries_warm": warm, "entries_cold": cold,
                "host_pages": len(self._host),
                "host_bytes": self._host_bytes,
                "disk_pages": self._disk_pages,
                **self.counters,
            }

    def busy(self) -> bool:
        """Transfers or IO still in flight (settle/close use this)."""
        return bool(len(self._swin) or len(self._fwin)
                    or any(t.is_alive() for t in self._io_threads)
                    or any(e.state in ("saving", "loading")
                           for e in list(self._entries.values())))

    def _entry_bytes(self, ent: _Entry) -> int:
        c = self.eng.cache
        per_tok = 2 * c.k.dtype.itemsize * c.k.shape[0] * c.k.shape[-1]
        if c.quantized:
            per_tok += 2 * 4 * c.k.shape[0]
        return ent.n * per_tok

    def leak_check(self) -> None:
        """Cross-tier accounting invariants: host-page refcounts equal
        their referencing entries plus in-flight spill holds, the
        dedup index points at live pages that carry its keys, and the
        byte tally matches the store. Raises AssertionError."""
        expect: dict[int, int] = {}
        for sp in [t for t, _, _ in self._swin._q]:
            for step in sp.plan:
                if step[0] == "dup":
                    expect[step[1]] = expect.get(step[1], 0) + 1
        with self._lock:
            for e in self._entries.values():
                for hpid in e.hpids:
                    expect[hpid] = expect.get(hpid, 0) + 1
            for hpid, hp in self._host.items():
                if hp.ref != expect.get(hpid, 0):
                    raise AssertionError(
                        f"host page {hpid}: ref {hp.ref} != "
                        f"{expect.get(hpid, 0)} references")
                if hp.key is not None \
                        and self._dedup.get(hp.key) != hpid:
                    raise AssertionError(
                        f"host page {hpid} carries a dedup key the "
                        "index does not map to it")
            for key, hpid in self._dedup.items():
                if hpid not in self._host:
                    raise AssertionError("dedup key maps to a freed "
                                         f"host page {hpid}")
            orphans = set(expect) - set(self._host)
            if orphans:
                raise AssertionError(
                    f"entries reference freed host pages: {orphans}")
            if self._host_bytes != sum(h.nbytes
                                       for h in self._host.values()):
                raise AssertionError("host byte tally drifted")
        staged = {f.stage for f in self._fetches.values()}
        if staged & set(self._stage_free):
            raise AssertionError("stage id both free and in use")

    # ---------------------------------------------------------- lifecycle

    def settle(self, timeout_s: float = 10.0) -> None:
        """Drive ticks until every in-flight transfer and IO thread
        lands. ONLY for tests/tools while the scheduler is quiescent
        (engine closed, or idle with no pending work)."""
        self._t_scan = 0.0  # force one policy scan past the rate limit
        self.tick()
        deadline = time.perf_counter() + timeout_s
        while self.busy() and time.perf_counter() < deadline:
            self.tick()
            time.sleep(0.005)
        self.tick()

    def close(self) -> None:
        """Engine teardown: complete (blocking is fine here — the
        scheduler is gone) and account every in-flight transfer, then
        abandon staged fetches so the pool's leak_check stays clean."""
        now = time.perf_counter()
        while len(self._swin):
            for h in self._swin._q[0][2]:
                jax.block_until_ready(h)
            for sp in self._swin.reap():
                self._finalize_spill(sp, now)
        for rid, f in list(self._fetches.items()):
            del self._fetches[rid]
            self._abandon_fetch(rid, f)
        for t in self._io_threads:
            t.join(timeout=2.0)
        self._apply_io_results(now)
        self._io_threads = [t for t in self._io_threads
                            if t.is_alive()]
