"""Device-streaming parameter commit for quantized single-chip loads.

``hf_loader.load_params(defer_transpose=True)`` returns transposed
leaves as ``DeferredT`` raw host arrays (torch [..., out, in] layout,
on-disk dtype). This module streams each leaf to the accelerator and
runs cast + transpose (+ int8 quantize for the serving projections) as
ONE jitted XLA computation there, donating the raw buffer so HBM holds
at most the growing committed tree plus one in-flight stack.

Why: the previous host-staged pipeline (numpy strided transpose, eager
CPU quantize) measured ~10 minutes for an 8B checkpoint on a small
host; the device path is bounded by the host->device link instead. Capability counterpart of the reference's
quantized-checkpoint loading (GGUF mmap in llama.cpp — the reference
never pays a quantize at load; our artifact cache in
``artifact_cache.py`` restores that property after the first load).

The quantize math is ``quant.quantize_raw_tensor`` — bit-identical to
``quantize_tensor`` on the transposed array (tested in
tests/test_staging.py), applied per layer under ``lax.map`` so the f32
intermediate stays one layer wide instead of one stack wide.
"""

from __future__ import annotations

import contextlib
import os
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import knobs
from .hf_loader import F32_LEAVES, DeferredT
from .quant import QTensor, quantizable, quantize_raw_tensor


def _per_layer(fn, x: jax.Array):
    """Apply ``fn`` over the leading (layer) axis when one exists, so
    per-layer f32 temporaries replace stack-wide ones; single tensors
    (lm_head) apply directly."""
    if x.ndim >= 3:
        return jax.lax.map(fn, x)
    return fn(x)


class TransferWindow:
    """Bounded-byte window of in-flight host<->device transfers.

    The double-buffer discipline both transfer directions share: enqueue
    without waiting, track (tag, nbytes, handles) in FIFO order, and
    bound the bytes in flight so small items stream back-to-back while a
    budget-sized item keeps the old one-at-a-time memory peak.

    Two completion modes, one per direction:

    - ``drain(need)`` — BLOCKING, host->device (checkpoint commit): pop
      from the head with ``jax.block_until_ready`` until ``need`` more
      bytes fit under the budget. The loader thread owns the wait.
    - ``reap()`` — NON-BLOCKING, device->host (KV tier spill): pop every
      head entry whose handles are already ready (``is_ready()``) and
      return them. The engine scheduler polls this between steps, so a
      spill DMA never blocks a device dispatch.
    """

    def __init__(self, budget_bytes: int,
                 ledger: Optional[Any] = None) -> None:
        self.budget = max(1, budget_bytes)
        self._q: deque[tuple[Any, int, tuple]] = deque()
        self.flying = 0  # bytes in flight
        if ledger is not None:
            # HBM ledger hookup: the "staging" component reads the live
            # in-flight byte count (telemetry/hbm_ledger.py callable
            # source), so commit/spill transfer buffers are attributed
            ledger.register("staging", lambda: self.flying)

    def __len__(self) -> int:
        return len(self._q)

    def add(self, tag: Any, nbytes: int, handles: tuple) -> None:
        """Track an already-enqueued transfer."""
        self._q.append((tag, nbytes, handles))
        self.flying += nbytes

    def over(self, need: int) -> bool:
        """Would ``need`` more in-flight bytes exceed the budget?"""
        return self.flying + need > self.budget

    def drain(self, need: int) -> None:
        """Blocking head-pop until ``need`` more bytes fit (an
        over-budget item waits for an empty pipe)."""
        while self._q and (self.flying + need > self.budget
                           or (need > self.budget and self.flying)):
            _, b, handles = self._q.popleft()
            for h in handles:
                jax.block_until_ready(h)
            self.flying -= b

    def reap(self) -> list:
        """Non-blocking: pop head entries whose handles are all ready
        and return their tags (FIFO readiness is monotone per stream,
        so a not-ready head ends the sweep)."""
        done = []
        while self._q:
            tag, b, handles = self._q[0]
            if not all(h.is_ready() for h in handles):
                break
            self._q.popleft()
            self.flying -= b
            done.append(tag)
        return done

    def flush(self) -> None:
        """Blocking: complete every tracked transfer."""
        while self._q:
            _, b, handles = self._q.popleft()
            for h in handles:
                jax.block_until_ready(h)
            self.flying -= b

    def forget(self) -> list:
        """Non-blocking: drop every tracked entry and return their tags
        WITHOUT waiting for the transfers. For abandoned streams (an
        aborted weight demotion — engine/weight_pager.py) where the
        caller no longer wants the data; the in-flight DMAs still
        complete on their own, the window just stops accounting them."""
        tags = [t for t, _, _ in self._q]
        self._q.clear()
        self.flying = 0
        return tags


_PRECISION_BITS = {"bfloat16": (8, 7), "float16": (5, 10)}


def _jit_quant(dtype):
    bits = _PRECISION_BITS.get(jnp.dtype(dtype).name)

    def f(x):
        def one(w):
            # round to the serving dtype FIRST so the quantization sees
            # exactly what the host-staged path quantized (an f32
            # checkpoint must not produce different int8 codes between
            # the two load paths). A plain astype(dtype).astype(f32)
            # would be elided by XLA's excess-precision optimization
            # under jit; reduce_precision applies the rounding
            # unconditionally.
            wf = w.astype(jnp.float32)
            if bits is not None:
                wf = jax.lax.reduce_precision(wf, *bits)
            return quantize_raw_tensor(wf)

        return _per_layer(one, x)

    return jax.jit(f, donate_argnums=0)


def _jit_swap(dtype):
    def f(x):
        def one(w):
            return jnp.swapaxes(w.astype(dtype), -1, -2)

        return _per_layer(one, x)

    return jax.jit(f, donate_argnums=0)


def _jit_cast(dtype):
    def f(x):
        return x.astype(dtype)

    return jax.jit(f, donate_argnums=0)


def commit_deferred(
    params: dict[str, Any],
    dtype: Any,
    device,
    quantize: bool,
    quantize_embeddings: bool,
    phases: Optional[Any] = None,  # LoadPhases: read_s = main-thread
    # wait on leaf materialization, transfer_s = device placement+commit
    readers: int = 2,
    ledger: Optional[Any] = None,  # HBMLedger: attributes the in-flight
    # transfer window to the "staging" component during the commit
) -> dict[str, Any]:
    """Stream a ``defer_transpose`` parameter tree onto ``device``.

    DeferredT leaves: device_put raw -> fused cast+transpose(+quantize).
    Plain leaves: device_put (+cast; embed/lm_head quantize when
    ``quantize_embeddings``). Returns the committed tree; the input
    dict's raw buffers are released as each leaf lands.

    Pipelined: LAZY leaves (thunk-backed DeferredT from ``load_params``)
    are materialized by a small reader thread pool a bounded window
    ahead, so checkpoint reads overlap the previous leaves' host->device
    transfer + fused commit instead of serializing read -> transfer per
    leaf. Device transfers are double-buffered the same way: a leaf's
    ``block_until_ready`` is deferred until the in-flight raw bytes
    exceed ``LOCALAI_COMMIT_INFLIGHT_MB`` (default 1024), so small
    leaves stream back-to-back while the multi-GB stacks keep the old
    one-at-a-time HBM bound (an over-budget leaf waits for an empty
    pipe). Peak HBM stays committed-tree + max(budget, one big stack);
    peak host RAM drops from the whole raw tree to the prefetch window.
    """
    from .quant import quantize_embed

    out: dict[str, Any] = {}
    jq = _jit_quant(dtype)
    jswap = _jit_swap(dtype)
    jcast = _jit_cast(dtype)
    timed = (phases.timed if phases is not None
             else lambda _p: contextlib.nullcontext())
    # largest-last: the committed tree grows with small leaves first so
    # peak HBM = tree + one big in-flight stack, not two. Lazy leaves
    # (size unknown until read) are exactly the big projection stacks,
    # so they sort last as a class; order within them is immaterial for
    # the peak (each is ~the same size and commits one at a time).
    names = sorted(params, key=lambda n: _leaf_bytes(params[n]))
    budget = knobs.int_("LOCALAI_COMMIT_INFLIGHT_MB") * (1 << 20)
    window = TransferWindow(budget, ledger=ledger)

    def drain(need: int) -> None:
        if len(window) and window.over(need):
            with timed("transfer_s"):
                window.drain(need)

    pool = ThreadPoolExecutor(
        max_workers=max(1, readers), thread_name_prefix="ckpt-reader")
    try:
        # prefetch window: materialize the next few lazy leaves while
        # the current one transfers. One leaf per future; window kept
        # small so host RAM holds a few raw stacks, not the whole tree.
        ahead = max(1, readers)
        futures: dict[str, Any] = {}
        lazy = [n for n in names
                if isinstance(params[n], DeferredT)
                and not params[n].materialized]

        def _materialize(leaf: DeferredT):
            ctx = phases.muted() if phases is not None \
                else contextlib.nullcontext()
            with ctx:
                return leaf.materialize()

        def top_up() -> None:
            for n in lazy:
                if len(futures) >= ahead:
                    break
                if n not in futures and n in params:
                    futures[n] = pool.submit(_materialize, params[n])

        top_up()
        for name in names:
            fut = futures.pop(name, None)
            if fut is not None:
                with timed("read_s"):
                    fut.result()  # re-raises reader failures
            leaf = params.pop(name)
            if isinstance(leaf, DeferredT):
                # mute inner instrumentation (load_params wraps the
                # getter): the outer timer bills this read once; exit
                # order un-mutes before the timer adds
                with timed("read_s"), (
                        phases.muted() if phases is not None
                        else contextlib.nullcontext()):
                    raw = leaf.materialize()  # no-op when prefetched
                top_up()  # next reads overlap this leaf's transfer
                nbytes = int(getattr(raw, "nbytes", 0))
                drain(nbytes)
                with timed("transfer_s"):
                    x = jax.device_put(raw, device)
                    del raw, leaf
                    if (quantize and quantizable(name)) or (
                        name == "lm_head" and quantize
                        and quantize_embeddings
                    ):
                        out[name] = jq(x)
                    else:
                        out[name] = jswap(x)
                window.add(name, nbytes, (out[name],))
                continue
            nbytes = int(getattr(leaf, "nbytes", 0))
            drain(nbytes)
            with timed("transfer_s"):
                # plain leaves from load_params are already jax arrays
                # (on the default device); np.asarray on those would
                # round-trip through host memory
                if isinstance(leaf, jax.Array):
                    x = jax.device_put(leaf, device)
                else:
                    x = jax.device_put(np.asarray(leaf), device)
                if (name == "embed" and quantize and quantize_embeddings
                        and not isinstance(x, QTensor)):
                    out[name] = jax.jit(quantize_embed, donate_argnums=0)(
                        x.astype(dtype))
                elif (hasattr(x, "astype") and not isinstance(x, QTensor)
                      and name not in F32_LEAVES):
                    out[name] = jcast(x) if x.dtype != dtype else x
                else:
                    out[name] = x
            window.add(name, nbytes, (out[name],))
        with timed("transfer_s"):
            window.flush()
    finally:
        pool.shutdown(wait=True)
    return out


def _leaf_bytes(leaf) -> int:
    if isinstance(leaf, DeferredT):
        if not leaf.materialized:
            # lazy = unread big stack; sort after every known leaf
            return 1 << 62
        raw = leaf.raw
    else:
        raw = leaf
    return getattr(raw, "nbytes", 0)
