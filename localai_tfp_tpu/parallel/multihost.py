"""Coordinator→follower dispatch replay for multi-host SPMD serving.

The reference distributes one model across machines by shipping tensor
ops to llama.cpp RPC workers (SURVEY.md §2.5: worker_p2p.go, ggml RPC —
one network round trip per op). On TPU the model is sharded with GSPMD
over a multi-host mesh instead, which imposes the multi-controller rule:
EVERY host must issue the SAME jitted dispatches in the SAME order, while
only rank 0 sees HTTP traffic (SURVEY.md §7 hard part #5: "coordinator
serves, others follow").

This module is the control plane that makes that true. The coordinator's
engine publishes a compact *dispatch record* — ``(kind, payload)`` where
the payload is the tiny host-side input (token ids, positions, flags) —
immediately before every device dispatch; follower hosts replay the
records through the same ``LLMEngine._dev_exec`` entry point, so each
host's XLA dispatch sequence is identical and collectives line up. Device
state (params, KV cache, sampler) never crosses the wire: each host holds
its own shard and advances it by replaying.

Transports:
  * ``JaxBroadcastChannel`` — real multi-host path over
    ``multihost_utils.broadcast_one_to_all`` (rides DCN/ICI). Records are
    pickled and padded to power-of-two sizes so the broadcast compiles a
    bounded number of shapes.
  * ``LocalChannel`` — in-process queue fan-out used by the test suite to
    prove leader/follower replay equivalence without a second process.

Lifecycle and engine records share ONE lockstep stream, but a slow
``load`` does NOT pause in-flight generation for other models:
``FollowerRouter`` executes load records asynchronously (a load issues
no cross-host collectives — see the invariant note on FollowerRouter)
and rejoins the lockstep stream at the new model's first engine record.
"""

from __future__ import annotations

import logging
import pickle
import queue
import threading
import time
from typing import Any, Optional, Tuple

import numpy as np

from ..telemetry.flightrec import FLIGHT, PhaseClock
from ..telemetry.tracing import TRACER
from ..utils import faultinject

log = logging.getLogger(__name__)

Record = Tuple[str, Any]

# ------------------------------------------------------------ codec contract
#
# The replay codec whitelist: every engine dispatch record kind and the
# exact payload fields its followers know how to replay. Dispatch
# payloads must stay SCALAR-ONLY — python ints/floats/bools/strs and
# small index/token ndarrays (plus the bit-packed mask dict and the
# reset column dict) — so records pickle small, broadcast in bounded
# shapes, and replay with zero leader-side state.
#
# Adding a field HERE is the reviewed act that acknowledges the replay
# contract; ``tools.lint``'s scalar-payload rule statically checks every
# ``LLMEngine._run`` site against this table, so a new dispatch kind or
# field that skips this table fails tier-1 instead of diverging SPMD
# programs at runtime. (Plain literal on purpose: the linter reads it
# from the AST without importing jax.)
PAYLOAD_FIELDS = {
    "prefill": ("toks", "pos0", "slot_ids", "window"),
    "mixed": ("toks", "pos0", "slot_ids", "n_chunk", "final", "tails",
              "tail_lens", "masks", "reset", "soft", "window", "carry",
              "dtoks", "dpos", "active", "pt", "wb"),
    "decode1": ("tokens", "pos0", "active", "masks", "pt", "wb"),
    "decodek": ("k", "window", "depth", "carry", "tokens", "pos0",
                "active", "pt", "wb"),
    "spec": ("kd", "rounds", "tokens", "pos0", "active", "pt", "wb"),
    "spec_s": ("kd", "rounds", "tokens", "pos0", "active", "pt", "wb"),
    "kvcopy": ("src", "dst", "n"),
    "embed": ("toks", "bucket"),
}


def validate_payload(kind: str, payload: Any) -> None:
    """Raise on a dispatch record the follower codec cannot replay.

    Called by the test transport (``LocalChannel``) on every publish so
    codec drift fails loudly in the suite; the broadcast path skips the
    check (the static scalar-payload lint rule already gates merges).
    """
    if kind in ("load", "unload", "stop"):
        return  # lifecycle records carry their own option objects
    allowed = PAYLOAD_FIELDS.get(kind)
    if allowed is None:
        raise ValueError(
            f"dispatch kind {kind!r} is not in the multihost codec "
            "whitelist (PAYLOAD_FIELDS) — followers cannot replay it")
    data = payload.get("data") if isinstance(payload, dict) else None
    if not isinstance(data, dict):
        raise ValueError(
            f"record for {kind!r} must be {{'model', 'data'}} with a "
            f"dict payload; got {type(data).__name__}")
    extra = set(data) - set(allowed)
    if extra:
        raise ValueError(
            f"payload field(s) {sorted(extra)} for kind {kind!r} are "
            "not in the multihost codec whitelist (PAYLOAD_FIELDS)")


# ---------------------------------------------------------------- encoding


def encode_record(kind: str, payload: Any) -> tuple[np.ndarray, np.ndarray]:
    """(header [n, padded], padded uint8 buffer) for a record."""
    raw = pickle.dumps((kind, payload), protocol=pickle.HIGHEST_PROTOCOL)
    n = len(raw)
    padded = 1 << max(10, (n - 1).bit_length())
    buf = np.zeros(padded, np.uint8)
    buf[:n] = np.frombuffer(raw, np.uint8)
    return np.array([n, padded], np.int64), buf


def decode_record(n: int, buf: np.ndarray) -> Record:
    return pickle.loads(bytes(bytearray(buf[:n])))


# --------------------------------------------------------------- transports


class LocalChannel:
    """In-process fan-out channel: one leader, N follower ends (tests)."""

    is_leader = True

    def __init__(self) -> None:
        # publishers hold order_lock across publish+device-enqueue so the
        # follower's replay order equals the leader's XLA dispatch order
        # (RLock: publish() re-acquires under _run's critical section)
        self.order_lock = threading.RLock()
        # fan-out ends join while engines publish (a test attaching a
        # follower mid-stream), so membership shares the order lock
        self._ends: list["LocalFollowerEnd"] = []  # lint: guarded-by self.order_lock

    def follower_end(self) -> "LocalFollowerEnd":
        end = LocalFollowerEnd()
        with self.order_lock:
            self._ends.append(end)
        return end

    def publish(self, kind: str, payload: Any) -> None:
        if faultinject.ACTIVE:
            # chaos surface: a fault here models the cross-host
            # broadcast dying mid-dispatch; it surfaces inside _run's
            # critical section exactly like a transport error
            faultinject.fire("multihost.publish")
        # the test transport enforces the codec whitelist on every
        # record, so a payload field the follower codec doesn't know
        # fails the suite at publish time (the broadcast transport
        # skips this; the static scalar-payload rule gates merges)
        validate_payload(kind, payload)
        # pickle round trip: followers must see a snapshot, not objects
        # the leader's scheduler thread keeps mutating
        with self.order_lock:
            hdr, buf = encode_record(kind, payload)
            rec = decode_record(int(hdr[0]), buf)
            for end in self._ends:
                end._q.put(rec)


class LocalFollowerEnd:
    def __init__(self) -> None:
        self._q: "queue.SimpleQueue[Record]" = queue.SimpleQueue()

    def recv(self, timeout: Optional[float] = None) -> Record:
        # timeout supported here (queue-backed); the collective transport's
        # recv() is bare by design — see JaxBroadcastChannel.recv
        return self._q.get(timeout=timeout)


class JaxBroadcastChannel:
    """Multi-host transport over XLA collectives.

    ``publish``/``recv`` are two matched ``broadcast_one_to_all`` calls
    (fixed-size header, then the padded record). All hosts must make the
    same sequence of calls — the publish lock keeps the coordinator's
    threads (engine scheduler, model loader) from interleaving records.
    """

    def __init__(self) -> None:
        import jax
        from jax.experimental import multihost_utils

        self._mh = multihost_utils
        self.is_leader = jax.process_index() == 0
        self.order_lock = threading.RLock()

    def publish(self, kind: str, payload: Any) -> None:
        if in_follower_load():  # not assert: must survive python -O
            raise RuntimeError(
                "collective publish from inside an async follower load — "
                "loads must stay collective-free (FollowerRouter "
                "invariant)")
        if faultinject.ACTIVE:
            faultinject.fire("multihost.publish")
        hdr, buf = encode_record(kind, payload)
        with self.order_lock:
            self._mh.broadcast_one_to_all(hdr)
            self._mh.broadcast_one_to_all(buf)

    def recv(self) -> Record:
        if in_follower_load():
            raise RuntimeError(
                "collective recv from inside an async follower load — "
                "loads must stay collective-free (FollowerRouter "
                "invariant)")
        # no timeout parameter by design: a collective cannot time out
        # partially — callers must not assume a bounded wait on this
        # transport (LocalFollowerEnd.recv does honor one, tests only)
        hdr = self._mh.broadcast_one_to_all(np.zeros(2, np.int64))
        n, padded = int(hdr[0]), int(hdr[1])
        buf = self._mh.broadcast_one_to_all(np.zeros(padded, np.uint8))
        return decode_record(n, np.asarray(buf))


# ------------------------------------------------------------ global wiring

_CHANNEL: Optional[Any] = None
_ROLE = "solo"  # solo | leader | follower

# FollowerRouter's async-load safety rests on "a load issues no
# cross-host collectives" — this thread-local marks follower-load
# threads so the collective entry points can ASSERT the invariant
# instead of trusting it (parallel/sharding.py checks it before any
# multi-process resharding; the broadcast channel checks it on use).
_load_tls = threading.local()


def in_follower_load() -> bool:
    return bool(getattr(_load_tls, "loading", False))


class _follower_load_scope:
    def __enter__(self):
        _load_tls.loading = True

    def __exit__(self, *exc):
        _load_tls.loading = False


def enable(channel: Any, role: str) -> None:
    """Install the process-wide channel (called from the CLI once
    jax.distributed is up; tests install a LocalChannel)."""
    global _CHANNEL, _ROLE
    _CHANNEL = channel
    _ROLE = role


def disable() -> None:
    global _CHANNEL, _ROLE
    _CHANNEL = None
    _ROLE = "solo"


def active_channel() -> Optional[Any]:
    return _CHANNEL


def role() -> str:
    return _ROLE


# ------------------------------------------------------------ follower loops


class Replayer:
    """Shared engine-record executor for follower loops: runs _dev_exec
    and drains the device queue every DRAIN records so replay can't race
    unboundedly ahead of execution.

    Distributed tracing: leader records carry the trace ids of the
    requests occupying the dispatch's slots (the ``trace`` envelope
    field, stamped by ``LLMEngine._run``). The replayer opens ONE local
    TRACER entry per leader trace id (``replay:<tid16>``, joined by the
    shared trace id) and annotates it with the kinds replayed, so a
    ``/debug/traces?id=<trace id>`` on the follower shows the leader's
    request flowing through this host. Entries close when their trace
    id leaves the live set of a later record."""

    DRAIN = 64

    def __init__(self) -> None:
        self._n = 0
        self._open: set = set()  # leader trace ids with a live entry
        # the leader's enqueue phase, on this host: the same span (ring
        # at >= 1 ms, TraceAnnotation while a capture runs)
        self._phases = PhaseClock("follower")

    def _note_trace(self, kind: str, trace: tuple) -> None:
        live = set(trace)
        for tid in tuple(self._open - live):
            rid = "replay:" + tid[:16]
            TRACER.event(rid, "done")
            TRACER.finish(rid, status="replayed")
            self._open.discard(tid)
        for tid in trace:
            rid = "replay:" + tid[:16]
            if tid not in self._open:
                self._open.add(tid)
                TRACER.start(rid, model="follower",
                             events=[("receive", time.perf_counter())],
                             trace_id=tid)
            TRACER.annotate(rid, "replay", kind=kind)

    def exec(self, engine: Any, kind: str, payload: Any,
             trace: tuple = ()) -> None:
        self._note_trace(kind, trace)
        t0 = time.perf_counter()
        with self._phases.span("sched:enqueue:" + kind, root=True):
            engine._dev_exec(kind, payload)
        # host-side enqueue span only — _dev_exec returns as soon as the
        # dispatch is queued, so no sync is implied by timing it
        FLIGHT.span("replay:" + kind, "follower", t0,
                    time.perf_counter() - t0)
        self._n += 1
        if self._n % self.DRAIN == 0:
            import jax

            jax.block_until_ready(engine.cache.k)


def run_follower_engine(engine: Any, end: Any,
                        timeout: Optional[float] = None) -> None:
    """Replay engine-scoped records until a ``stop`` record arrives.

    ``engine`` is an ``LLMEngine`` built with ``follower=True`` over the
    SAME checkpoint/config as the coordinator's; ``end`` is any object
    with ``recv()``. Model-lifecycle records are ignored — this loop (used
    by tests and embedders of a single engine) replays exactly one
    engine's dispatch stream."""
    rp = Replayer()
    while True:
        # collective transports (JaxBroadcastChannel) expose a bare
        # recv(); only pass a timeout to ends that can honor one
        kind, rec = end.recv() if timeout is None \
            else end.recv(timeout=timeout)
        if kind == "stop":
            return
        if kind in ("load", "unload"):
            continue
        rp.exec(engine, kind, rec["data"],
                trace=tuple(rec.get("trace") or ()))


class FollowerRouter:
    """Routes coordinator records to per-model engines, executing model
    LOADS asynchronously so an in-flight generation never pauses for a
    second model's checkpoint IO (VERDICT r1 weak #3).

    Why this is safe: engine records keep ONE global lockstep stream —
    cross-model device-dispatch order must match the leader's exactly,
    or same-device collectives interleave differently across hosts and
    deadlock. A ``load``, however, issues no cross-host collectives
    (checkpoint read + per-host device_put + compile), so it may run
    out-of-band. The leader publishes a model's first engine record only
    AFTER its own equally-long local load returns, so by the time model
    B's records arrive, this host's async load is (nearly) done; any
    residual skew blocks only at B's first record, not during A's
    decode."""

    def __init__(self, make_backend: Any = None) -> None:
        if make_backend is None:
            def make_backend():
                from ..workers.llm import JaxLLMBackend

                return JaxLLMBackend(role="follower")
        self._make_backend = make_backend
        # the router's maps are shared between the follower loop thread
        # and the async load threads (run() publishes its backend from
        # the load thread), so mutations take the lock; the loop's
        # hot-path reads stay lock-free by design (worst case they see
        # a load as still-pending and join it)
        self._lock = threading.Lock()
        self.backends: dict[str, Any] = {}  # lint: guarded-by self._lock
        self.failed: set[str] = set()  # lint: guarded-by self._lock
        self._loading: dict[str, threading.Thread] = {}  # lint: guarded-by self._lock
        self._rp = Replayer()

    def _join_load(self, tag: str) -> None:
        with self._lock:
            th = self._loading.pop(tag, None)
        if th is not None:  # join OUTSIDE the lock: loads take minutes
            th.join()

    def _load_async(self, rec: Any) -> None:
        tag = rec.model
        self._join_load(tag)  # a reload chains behind the previous load
        with self._lock:
            old = self.backends.pop(tag, None)
        if old is not None:  # leader reloaded the same model
            old.shutdown()

        def run() -> None:
            backend = self._make_backend()
            with _follower_load_scope():  # pins "no collectives in load"
                res = backend.load_model(rec)
            if res.success:
                with self._lock:
                    self.failed.discard(tag)
                    self.backends[tag] = backend
            else:
                # symmetric failures (bad checkpoint on every host) are
                # recoverable: the leader's own load fails too and it
                # publishes a compensating unload. Only an ASYMMETRIC
                # failure — engine records arriving for a model this
                # host could not load — is fatal (handle()).
                log.error("follower load of %r failed: %s", tag,
                          res.message)
                with self._lock:
                    self.failed.add(tag)

        th = threading.Thread(target=run, name=f"follower-load-{tag}",
                              daemon=True)
        with self._lock:
            self._loading[tag] = th
        th.start()

    def handle(self, kind: str, rec: Any) -> bool:
        """Process one record; returns False on ``stop``."""
        if kind == "stop":
            return False
        if kind == "load":
            self._load_async(rec)
            return True
        if kind == "unload":
            tag = rec["model"]
            self._join_load(tag)
            with self._lock:
                self.failed.discard(tag)
                backend = self.backends.pop(tag, None)
            if backend is not None:
                backend.shutdown()
            return True
        tag = rec.get("model")
        if tag in self._loading:
            # residual skew: the leader finished its load and started
            # dispatching before we did — wait out the remainder
            self._join_load(tag)
        backend = self.backends.get(tag)
        if backend is not None and backend.engine is not None:
            self._rp.exec(backend.engine, kind, rec["data"],
                          trace=tuple(rec.get("trace") or ()))
        elif tag in self.failed:
            # the leader IS serving this model but this host has no
            # engine for it: the SPMD programs have already diverged.
            # Die loudly — a dead follower is visible to the operator;
            # silently dropping records would hang the leader's
            # collectives with no diagnostic.
            log.critical(
                "follower received %r for model %r it failed to load; "
                "terminating so the divergence fails loudly", kind, tag)
            raise SystemExit(1)
        else:
            log.warning("follower dropped %r for unknown model %r",
                        kind, tag)
        return True

    def shutdown(self) -> None:
        with self._lock:
            loading = list(self._loading.values())
            self._loading.clear()
        for th in loading:
            th.join()
        with self._lock:
            backends = list(self.backends.values())
            self.backends.clear()
        for backend in backends:
            backend.shutdown()


def follower_main() -> None:
    """Whole-process follower loop for `localai-tpu run` on rank>0 hosts.

    Mirrors the coordinator's model lifecycle: a ``load`` record carries
    the coordinator's ModelLoadOptions, the follower loads the identical
    checkpoint from its own disk (paths must match across hosts, as with
    any SPMD launcher) and routes engine records to the matching model
    until ``unload`` or process ``stop``. Multiple live models replay
    side by side, keyed by the records' model tag; loads run
    asynchronously so in-flight generation never pauses (FollowerRouter).
    """
    channel = JaxBroadcastChannel()
    enable(channel, "follower")
    router = FollowerRouter()
    log.info("follower dispatch loop up; waiting for coordinator records")
    while True:
        kind, rec = channel.recv()
        if not router.handle(kind, rec):
            break
    router.shutdown()
    log.info("follower dispatch loop stopped")
