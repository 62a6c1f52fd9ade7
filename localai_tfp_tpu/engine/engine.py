"""Continuous-batching LLM serving engine (the TPU counterpart of the
reference's C++ llama.cpp engine).

Reference semantics mirrored (backend/cpp/llama/grpc-server.cpp):
- N slots share the device; each owns a row of the KV cache
  (`llama_client_slot` :188-385, `initialize()` :568-616).
- scheduler loop = `update_slots()` :1639-2075 — admit queued requests,
  chunked prompt prefill with common-prefix KV reuse (`common_part` :67,
  cache trim :1893), batched decode of all running slots, per-slot sampling
  + stop handling (`process_token` :1069-1160).
- context exhaustion ends the generation (LocalAI patch :1673-1683;
  context-shift intentionally disabled :2415).
- per-phase timings (`print_timings` :346-385) surfaced per request
  (backend.proto:163-164 timing_prompt_processing/timing_token_generation).

TPU-first re-design rather than translation:
- All shapes static: decode always dispatches [n_slots, 1]; prefill chunks
  are padded to a small set of buckets — the jit cache holds ≤ len(buckets)+1
  executables, so the hot loop never recompiles (SURVEY.md §7 hard part #1).
- Sampling state lives on device as arrays indexed by slot and the sampler
  fuses into the decode dispatch (ops/sampling.py).
- KV cache rows are donated through jit every step (no reallocation).
- Inactive slots still flow through the batched decode but write their K/V
  at their own row's tail position, so a free slot's cached prefix stays
  intact for prefix reuse.
- Prefix reuse is GLOBAL, not per-slot: a radix index over every slot's
  resident prefix (engine/prefix_index.py) plus an on-device row-to-row
  KV copy dispatch ("kvcopy") let an admitted request start from the
  best matching prefix held by ANY slot — free or active — with
  prefix-aware wave admission and LRU x length victim selection
  (see the README "Serving: cross-slot prefix KV cache" section).
- A prompt is admitted ONE way, whether or not a row decodes: the
  "mixed" step carries the wave's prompt rows [R, bucket] and one
  token for every decoding row [n_slots, 1] in the SAME device
  dispatch (the ragged-batch discipline of RTP-LLM / Ragged Paged
  Attention, PAPERS.md), sized to what it carries, and hands the
  rows it admits to the decode carry on the device (see the README
  "Scheduling" section).
- Paged engines serve every row kind — decode rows, prefill chunks,
  prefill finals, spec-decode verify rows — through ONE ragged paged
  attention path (ops/ragged_paged_attention.py): page tables ride
  dispatches at FULL width, so the jit cache holds one variant per
  token-budget shape (no bucket x window ladder) and kernel-eligible
  engines never materialize a gathered KV window on the prefill/mixed
  hot path. How a program reaches the cache is ONE route chosen at
  construction (engine/cache_route.py; README "Kernels" section).
"""

from __future__ import annotations

import json
import logging
import queue
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from enum import Enum
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import knobs
from ..models.llm_spec import LLMSpec
from ..models.transformer import (
    KVCache, Params, Rows, forward, forward_hidden, forward_rows,
)
from ..ops.sampling import (
    SamplingState, sample, seed_windows,
)
from ..telemetry import costmodel, hbm_ledger
from ..telemetry import metrics as tm
from ..telemetry.flightrec import (
    FLIGHT, LoadWatch, PhaseClock, name_os_thread, note_jit,
)
from ..telemetry.tracing import TRACER, fault_scope
from ..utils import faultinject
from .cache_route import choose_route, window_bucket
from .kv_pool import TRASH_PAGE, PagePool, PagePoolExhausted
from .prefix_index import PrefixIndex, common_prefix_len
from .tokenizer import StreamDecoder, Tokenizer

log = logging.getLogger(__name__)

# Padded-prefill size ladder. The 4-bucket exists for the prefix-reuse
# fast path: a warm request re-processes only its last token(s), and at
# a 64-deep admission wave the difference between padding those rows to
# 32 columns vs 4 is ~2048 vs ~256 dead token-positions of 8B forward —
# measured ~400 ms vs ~30 ms on v5e, the difference between missing and
# making a <200 ms TTFT.
DEFAULT_PREFILL_BUCKETS = (4, 16, 128, 512, 2048)


@dataclass
class GenRequest:
    """One generation request (ref: backend.proto PredictOptions surface)."""

    prompt_ids: list[int]
    max_tokens: int = 128
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    min_p: float = 0.0
    repeat_penalty: float = 0.0
    repeat_last_n: int = 64
    frequency_penalty: float = 0.0
    presence_penalty: float = 0.0
    typical_p: float = 1.0  # locally typical sampling (>=1 disabled)
    mirostat: int = 0  # 0 off | 1 v1 | 2 v2 (ref: grpc-server.cpp:708)
    mirostat_tau: float = 5.0
    mirostat_eta: float = 0.1
    seed: Optional[int] = None
    stop: list[str] = field(default_factory=list)
    ignore_eos: bool = False
    logit_bias: Optional[dict[int, float]] = None
    # grammar-constrained decoding: object with next_mask(state)->np.bool_[V]
    # and advance(state, token)->state (see grammars/constrain.py)
    constraint: Optional[Any] = None
    # on-disk prompt cache (ref: backend.proto:135-141 PromptCachePath/
    # PromptCacheAll/PromptCacheRO — llama.cpp prompt state save/restore)
    prompt_cache_path: str = ""
    prompt_cache_all: bool = False
    prompt_cache_ro: bool = False
    correlation_id: str = ""
    # multimodal soft tokens (ref: llava mmproj embedding path,
    # grpc-server.cpp:1476-1502): precomputed embeddings [N, d_model] f32
    # replacing the prompt tokens at soft_positions (absolute indices into
    # prompt_ids — usually the <image_soft_token> runs)
    soft_embeds: Optional[np.ndarray] = None
    soft_positions: Optional[np.ndarray] = None
    id: str = field(default_factory=lambda: uuid.uuid4().hex)
    # distributed trace id (32 hex, telemetry/tracing.py): adopted from
    # the request's trace at submit so dispatch records can carry it to
    # multihost followers without a recorder lookup per dispatch
    trace_id: str = ""
    t_submit: float = 0.0  # perf_counter at submit (queue-wait/TTFT
    # attribution; set by submit_many, 0 for directly-assigned tests)
    # request deadline: client-supplied budget in seconds (0 = use the
    # engine's LOCALAI_REQUEST_DEADLINE_S default, which may itself be
    # 0 = no deadline). submit_many converts it to the absolute
    # `deadline` (perf_counter clock); _apply_deadlines enforces it
    # while queued AND while decoding
    timeout_s: float = 0.0
    deadline: float = 0.0
    # HTTP-edge message-boundary fingerprint chain
    # (utils/fingerprint.py): (hash_hex, cum_canonical_bytes) pairs
    # registered with the prefix index at slot assignment so digest
    # gossip advertises hashes the federated balancer can recompute
    # from a raw request body without a tokenizer
    prefix_chain: tuple = ()
    # disaggregated serving handoff (engine/kv_migrate.KVHandoff): set
    # by the DisaggRouter on the decode-engine resubmit of a request
    # whose prompt was prefilled on the prefill engine. _admit adopts
    # the migrated pages instead of prefilling, and submit_many
    # preserves the ORIGINAL t_submit/deadline it carries so TTFT and
    # deadline enforcement stay end-to-end. Host-only — never rides a
    # dispatch payload.
    disagg: Optional[Any] = None


class _PadReq:
    """Neutral sampler params for prefill-group pad rows (their state
    writes target the out-of-bounds sentinel slot and are dropped)."""

    temperature = 0.0
    top_k = 0
    top_p = 1.0
    min_p = 0.0
    repeat_penalty = 0.0
    frequency_penalty = 0.0
    presence_penalty = 0.0
    repeat_last_n = 0
    typical_p = 1.0
    mirostat = 0
    mirostat_tau = 5.0
    mirostat_eta = 0.1
    seed = None


@dataclass
class StreamEvent:
    """Streamed to the caller per emitted text span; final carries stats."""

    text: str = ""
    token_id: Optional[int] = None
    done: bool = False
    finish_reason: str = ""  # stop | length | error
    error: str = ""
    full_text: str = ""
    prompt_tokens: int = 0
    completion_tokens: int = 0
    timing_prompt_processing_ms: float = 0.0
    timing_token_generation_ms: float = 0.0
    # request-lifecycle attribution (Extra-Usage surface): time queued
    # before admission, and submit-to-first-token latency
    timing_queue_ms: float = 0.0
    timing_first_token_ms: float = 0.0
    # prefill phase split: timing_prompt_processing_ms is DEVICE time
    # attributed at harvest of the covering flight(s); this is the
    # host-side enqueue component (payload build + dispatch call),
    # which used to be miscounted as prompt processing for chunked
    # prompts
    timing_prefill_enqueue_ms: float = 0.0
    # load-shed hint: suggested client backoff in seconds, set only on
    # finish_reason="shed" events (the HTTP layer maps it to a 429
    # Retry-After header)
    retry_after_s: float = 0.0


class SlotState(Enum):
    FREE = 0
    PREFILL = 1
    DECODE = 2
    # final prompt chunk dispatched; first sampled token still on device.
    # The slot joins decode scans once its prefill flight harvests.
    PENDING_FIRST = 3


@dataclass
class _Flight:
    """An in-flight device dispatch whose host-visible results are still
    pending. The scheduler enqueues dispatches without blocking (device
    queue time — hundreds of ms of scan work at serving shapes —
    pipelines behind host work) and harvests results in FIFO order —
    device execution is serialized by the donated cache/sampling
    buffers, so flight N's arrays are always ready no later than flight
    N+1's."""

    kind: str  # "mixed" | "decodek"
    arrays: list  # device arrays to harvest (copy_to_host_async started)
    meta: dict
    t_enqueue: float

    def ready(self) -> bool:
        return all(a.is_ready() for a in self.arrays)


@dataclass
class _Slot:
    idx: int
    state: SlotState = SlotState.FREE
    request: Optional[GenRequest] = None
    out: Optional[queue.SimpleQueue] = None
    cache_tokens: list[int] = field(default_factory=list)  # KV-resident ids
    n_past: int = 0  # valid prefix length in this slot's cache row
    n_prompt: int = 0
    generated: list[int] = field(default_factory=list)
    decoder: Optional[StreamDecoder] = None
    pending_text: str = ""  # withheld tail that may begin a stop string
    emit_buf: list[str] = field(default_factory=list)  # deferred text
    # spans coalesced into ONE stream event per harvest (a k=16 scan
    # over 64 slots otherwise wakes the consumers 1024 times)
    emit_tok: Optional[int] = None  # first token id of the buffered span
    constraint_state: Any = None
    cache_loaded: Any = None  # (path, n) the on-disk prompt cache holds
    n_reused: int = 0  # prompt tokens served from resident/copied KV
    # instead of prefill (set at _assign; read at prefill harvest)
    t_start: float = 0.0
    t_first: float = 0.0  # perf_counter at first emitted token
    t_prefill_ms: float = 0.0  # DEVICE prefill time, attributed at
    # harvest of the covering flight(s) — enqueue-only host time must
    # not land here (it made chunked prompts report near-zero prefill)
    t_prefill_enq_ms: float = 0.0  # host-side prefill enqueue time
    t_prefill_t0: float = 0.0  # perf_counter at the slot's FIRST
    # prefill dispatch; the covering flight's harvest attributes
    # (harvest - t0) as device+queue prefill time, so chunk dispatches
    # enqueued in earlier iterations are not lost
    t_decode_ms: float = 0.0
    t_last: float = 0.0

    @property
    def active(self) -> bool:
        return self.state is not SlotState.FREE


@dataclass
class EngineMetrics:
    """ref: backend.proto MetricsResponse / llama_metrics grpc-server.cpp
    :387-417."""

    requests_completed: int = 0
    tokens_generated: int = 0
    prompt_tokens_processed: int = 0
    tokens_per_second: float = 0.0
    prompt_tokens_per_second: float = 0.0
    slots_busy: int = 0
    spec_tokens: int = 0  # tokens emitted via speculative decoding
    spec_dispatches: int = 0
    # cross-slot prefix cache: tokens served from KV-resident prefixes
    # (same-slot resident, cross-slot copy, or disk restore) vs tokens
    # actually pushed through prefill dispatches
    prefix_reused_tokens: int = 0
    prefill_tokens: int = 0
    prefix_copies: int = 0  # kvcopy dispatches enqueued


def _soft_expand(tokens: jax.Array, rows: jax.Array, brow: jax.Array,
                 bpos: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Inside jit: compact multimodal rows -> the dense (embeds [B,T,D],
    mask [B,T]) override forward() consumes. Padding entries carry an
    out-of-range batch row and are dropped by the scatter, so the host
    ships only R×D real bytes instead of a B×T×D zero sea."""
    B, T = tokens.shape
    emb = jnp.zeros((B, T, rows.shape[-1]), rows.dtype)
    emb = emb.at[brow, bpos].set(rows, mode="drop")
    mask = jnp.zeros((B, T), bool).at[brow, bpos].set(True, mode="drop")
    return emb, mask


def _pack_masks(masks: Optional[np.ndarray]) -> Optional[dict]:
    """[B, V] bool → bit-packed record payload (multihost dispatch records
    must stay small; a dense 256k-vocab mask is 8x the packed size)."""
    if masks is None:
        return None
    return {"bits": np.packbits(masks, axis=1), "v": masks.shape[1]}


def _unpack_masks(p) -> Optional[jax.Array]:
    """Accepts None, a raw [B, V] bool array (solo mode, no wire), or a
    bit-packed record from _pack_masks (multihost replay)."""
    if p is None:
        return None
    if isinstance(p, dict):
        return jnp.asarray(
            np.unpackbits(p["bits"], axis=1, count=p["v"]).astype(bool)
        )
    return jnp.asarray(p)


# vectorized common-prefix (one elementwise compare + argmax instead of
# a per-token Python loop — this ran O(n_slots) times per admission);
# kept as the radix-index fallback and for the on-disk cache path
_common_prefix = common_prefix_len


def _sel_active(active, new, old):
    """Select new vs old leaves per slot (keeps inactive slots' state)."""
    if new.ndim == 0:
        return new
    a = active
    while a.ndim < new.ndim:
        a = a[..., None]
    return jnp.where(a, new, old)


def _expert_stats(experts):
    """A step program's last output: forward_rows' expert statistics,
    [E + 1] i32, or nothing to read ([0]) for a model without experts —
    one arity whatever the model."""
    return jnp.zeros((0,), jnp.int32) if experts is None else experts


def _rows_kw(kw: dict) -> tuple[dict, dict]:
    """A route's forward_kw split into what belongs to a group of rows
    (``Rows`` fields) and what belongs to the whole pass."""
    per = {k: v for k, v in kw.items() if k in Rows._fields}
    return per, {k: v for k, v in kw.items() if k not in per}


def _forward_step(spec, params, tokens, pos0, view, active, kw):
    """One decode step of the whole slot batch through the cache route
    (``kw``: the route's forward_kw): forward + LM head, with the rows
    that do not decode marked not live. -> (logits [S, 1, V], view,
    expert statistics)."""
    from ..models.transformer import _lm_head

    per, kw = _rows_kw(kw)
    (hidden,), view, experts = forward_rows(
        spec, params, (Rows(tokens, pos0, live=active, **per),), view, **kw)
    return _lm_head(spec, params, hidden), view, _expert_stats(experts)


def _sample_masked(sampling, slot_ids, logits, active, masks):
    with jax.named_scope("sample"):
        toks, new_sampling = sample(sampling, slot_ids, logits,
                                    mask=masks)
    merged = jax.tree_util.tree_map(
        lambda new, old: _sel_active(active, new, old), new_sampling, sampling
    )
    return jnp.where(active, toks, 0), merged


class LLMEngine:
    """Continuous-batching engine over one jitted model."""

    def __init__(
        self,
        spec: LLMSpec,
        params: Params,
        tokenizer: Tokenizer,
        *,
        n_slots: int = 8,
        max_seq: int = 4096,
        prefill_buckets: tuple[int, ...] = DEFAULT_PREFILL_BUCKETS,
        cache_dtype: Any = jnp.bfloat16,
        penalty_window: int = 256,
        decode_steps: int = 8,
        mesh: Any = None,  # jax.sharding.Mesh: TP/DP serving (the GSPMD
        # counterpart of tensor_split / tensor_parallel_size — SURVEY §2.5)
        draft: Optional[tuple[LLMSpec, Params]] = None,  # speculative
        # decoding draft model (ref: proto DraftModel/NDraft plumbing)
        n_draft: int = 4,
        latency_target_ms: Optional[float] = None,  # open-capacity
        # latency/throughput knob: bound in-flight decode device-time to
        # this budget whenever a slot is free, so an unpredicted
        # arrival's prefill queues behind at most ~one short scan.
        # None = balanced (scans stay long enough to cover the dispatch
        # RTT; see _latency_k)
        autostart: bool = True,
        kv_pages: Optional[int] = None,  # paged KV pool size (data
        # pages). None: LOCALAI_KV_PAGES env, else full worst-case
        # capacity (n_slots * max_seq / page — no memory saving, no
        # admission failure). Sizing it below worst case is the paged
        # pool's point: HBM follows EXPECTED context, so n_slots can
        # grow past what a dense cache of the same budget allows.
        channel: Any = None,  # multihost dispatch publisher (leader side);
        # every device dispatch is published as a (kind, payload) record
        # before executing so follower hosts replay the identical SPMD
        # program (parallel/multihost.py, SURVEY.md §7 hard part #5)
        follower: bool = False,  # replay-only engine: no scheduler thread,
        # device ops arrive via _dev_exec from the follower loop
        tag: str = "",  # model tag routing this engine's records when
        # several models publish on one channel
        state_dir: Optional[str] = None,  # where OOM post-mortems and
        # profiler captures land (None: $STATE_DIR, else ./run)
        kv_tier: Optional[bool] = None,  # tiered KV memory override:
        # None follows LOCALAI_KV_TIER; the disaggregated prefill
        # engine passes False (its slots live one prompt each — the
        # migration interchange replaces warm-tier churn there)
        weight_paging: Optional[bool] = None,  # layer-granular weight
        # paging override: None follows LOCALAI_WEIGHT_PAGING; disagg
        # workers pass False (prefill/decode engines share one tree by
        # reference — paging either side would strand the other)
    ) -> None:
        self.channel = channel
        self.follower = follower
        self.tag = tag
        # the device this engine's dispatches run on, as JAX reports it
        # (host-held: /backend/monitor and the cost model read these)
        dev = (mesh.devices.flat[0] if mesh is not None
               else jax.config.jax_default_device or jax.devices()[0])
        self.platform: str = dev.platform
        self.device_kind: str = dev.device_kind
        # Prometheus model label: the serving tag, or a stable fallback
        # for directly-constructed engines (tests/bench)
        self._mlabel = tag or "default"
        if follower:
            autostart = False
        self.decode_steps = max(1, decode_steps)
        self.latency_target_ms = latency_target_ms
        self.mesh = mesh
        self.draft = draft
        self.n_draft = max(2, n_draft)
        self._autostart = autostart
        self.spec = spec
        self.params = params
        self.tokenizer = tokenizer
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.prefill_buckets = tuple(
            b for b in sorted(prefill_buckets) if b <= max_seq
        ) or (max_seq,)

        # Paged KV pool (engine/kv_pool.py + models/transformer.py
        # gather/scatter views): one [L, n_pages, page, F] arena backs
        # every slot through host-owned page tables, so HBM scales with
        # live tokens and prefix pages share by reference. Dispatches
        # carry the tables as plain index arrays (multihost-replayable).
        # LOCALAI_PAGED_KV=off restores the dense per-slot cache.
        # Meshed serving pages too: the arena has no slot dim, so it
        # shards its head-flat F dim over "model"
        # (parallel/sharding.PAGED_KV_SPEC — each device holds its
        # kv-head slice of EVERY page) while the host-owned page tables
        # stay global. One meshed carve-out stays dense: seq-sharded
        # meshes (the paged prefill path has no ring-attention branch).
        # kv_dim not dividing the tp axis is a CONFIG ERROR, not a
        # fallback: shard_engine_state raises for dense and paged alike
        # (silent replication is a tp-times HBM regression), so such a
        # mesh fails engine construction with the actionable message.
        mesh_seq = 1 if mesh is None else mesh.shape.get("seq", 1)
        mesh_tp = 1 if mesh is None else mesh.shape.get("model", 1)
        self._paged = (
            (mesh is None or mesh_seq == 1)
            and knobs.flag("LOCALAI_PAGED_KV"))
        # page size: largest power of two <= min(256, max_seq) dividing
        # max_seq, so every window bucket (powers of two >= 256, capped
        # at max_seq) is page-aligned; LOCALAI_KV_PAGE overrides within
        # the same constraints. 256 matches the fused decode kernel's
        # native DMA granularity.
        page_cap = min(256, max_seq)
        pg = 1
        while pg * 2 <= page_cap and max_seq % (pg * 2) == 0:
            pg *= 2
        want_pg = knobs.int_("LOCALAI_KV_PAGE")
        if (want_pg >= 8 and want_pg <= page_cap
                and max_seq % want_pg == 0
                and want_pg & (want_pg - 1) == 0):
            pg = want_pg
        self._page = pg
        if pg < 8:  # degenerate geometry (tiny/odd max_seq): dense
            self._paged = False
        if self._paged:
            self._max_pages = max_seq // pg  # logical pages per slot
            pages_default = n_slots * self._max_pages + 1  # + trash
            self.kv_pages = max(2, int(
                kv_pages or knobs.int_("LOCALAI_KV_PAGES")
                or pages_default))
            self._pool = PagePool(self.kv_pages, pg)
            self.cache = KVCache.create(spec, self.kv_pages, pg,
                                        cache_dtype)
            self.draft_cache = (
                KVCache.create(draft[0], self.kv_pages, pg, cache_dtype)
                if draft is not None else None
            )
        else:
            self.kv_pages = 0
            self._pool = None
            self.cache = KVCache.create(spec, n_slots, max_seq,
                                        cache_dtype)
            self.draft_cache = (
                KVCache.create(draft[0], n_slots, max_seq, cache_dtype)
                if draft is not None else None
            )
        self.warmup_variants = 0  # dispatch variants precompiled by the
        # last completed warmup() pass (engine_dispatch_compile_variants
        # gauge; 0 until warmup runs or when it was marker-skipped)
        self._alloc_sync: dict[str, int] = {}  # pool alloc counters
        # already exported to engine_kv_page_alloc_total
        self.sampling = SamplingState.create(
            n_slots, spec.vocab_size, window=penalty_window
        )
        if mesh is not None:
            from ..models import quant
            from ..parallel.sharding import shard_engine_state, shard_params

            # GSPMD cannot partition the fused int8 pallas call; meshed
            # serving takes the XLA dequant path (models/quant.py)
            quant.set_meshed_serving(True)
            self.params = shard_params(self.params, mesh)
            self.cache, self.sampling = shard_engine_state(
                self.cache, self.sampling, mesh, paged=self._paged
            )
            if self._paged and self.draft_cache is not None:
                # the draft arena shares the pool's geometry/tables, so
                # it shards the same way; a non-divisible draft kv_dim
                # is device_put REPLICATED on the mesh — explicitly, so
                # a multi-GB operand never reaches the first dispatch
                # with an uncommitted single-device placement for GSPMD
                # to guess at (the spec paths then run the GSPMD gather
                # fallback — _kernel_ineligible gates the shard_map route
                # on draft eligibility)
                from ..parallel.sharding import PAGED_KV_SPEC, REPLICATED
                from jax.sharding import NamedSharding

                arena_sp = (PAGED_KV_SPEC
                            if draft[0].kv_dim % mesh_tp == 0
                            else REPLICATED)

                def _put_arena(arr, sp):
                    return jax.device_put(arr, NamedSharding(mesh, sp))

                dc = self.draft_cache
                self.draft_cache = type(dc)(
                    k=_put_arena(dc.k, arena_sp),
                    v=_put_arena(dc.v, arena_sp),
                    k_scale=(_put_arena(dc.k_scale, REPLICATED)
                             if dc.quantized else None),
                    v_scale=(_put_arena(dc.v_scale, REPLICATED)
                             if dc.quantized else None),
                )
        # what a program returns is COMMITTED to its device, and a
        # program lowers (and compiles) again for an argument that is
        # not. So host-made state that stands in for a program's output
        # is committed as it is put (no copy: it is on that device):
        # the caches and the sampler state here (the first dispatch
        # would otherwise load a variant nothing reuses), a dispatch's
        # decode tokens and positions when it does not chain on the
        # carry (_dev_exec) — ONE executable per shape, the one warmup
        # compiled. A meshed engine (None) leaves the placement to
        # GSPMD as before.
        self._device = (None if mesh is not None
                        else next(iter(self.cache.k.devices())))
        self.cache, self.draft_cache, self.sampling = jax.device_put(
            (self.cache, self.draft_cache, self.sampling), self._device)
        self.slots = [_Slot(i) for i in range(n_slots)]
        # "" when the Pallas kernel route is taken, else the condition
        # that ruled it out (surfaced by engine_stats)
        self.kernel_ineligible: str = self._kernel_ineligible()
        self._use_kernel = not self.kernel_ineligible
        # how every dispatch program reaches the cache, decided here and
        # nowhere else (engine/cache_route.py)
        self._route = route = choose_route(
            paged=self._paged, kernel=self._use_kernel, max_seq=max_seq,
            page=pg, mesh=mesh)
        self.attention_path: str = route.name
        log.info(
            "attention path %s on %s (%s)%s", self.attention_path,
            self.platform, self.device_kind,
            f" — kernel not eligible: {self.kernel_ineligible}"
            if self.kernel_ineligible else "")
        # the replica's tensor-parallel footprint on /metrics: how many
        # devices this engine's dispatches fan out over (1 unsharded)
        tm.ENGINE_MESH_DEVICES.labels(model=self._mlabel).set(
            1 if mesh is None else int(mesh.devices.size))
        # cross-slot prefix cache: radix index over every slot's
        # resident cache_tokens + on-device row-to-row KV copies
        # (engine/prefix_index.py). LOCALAI_PREFIX_CACHE=off restores
        # the old own-slot-only reuse.
        self._prefix_enabled = knobs.flag("LOCALAI_PREFIX_CACHE")
        # minimum token GAIN over the destination's own resident prefix
        # before a copy is worth dispatching (a copy is a sub-ms HBM
        # move, so the floor is low)
        self._prefix_min_copy = max(
            1, knobs.int_("LOCALAI_PREFIX_CACHE_MIN"))
        # minimum SHARED-prefix length before a same-wave request
        # defers behind a wave-mate's prefill: deferral delays the
        # sharer's TTFT by a scheduler iteration and splits the wave's
        # prefill group, so it must buy substantially more than the
        # ~6-token chat-template prefix every request shares
        self._prefix_defer_min = max(
            self._prefix_min_copy,
            knobs.int_("LOCALAI_PREFIX_CACHE_DEFER_MIN"))
        # token budget of one admission step's prompt group: the XLA
        # prefill attention materializes [B, H, T, window] f32 scores,
        # so B*bucket must stay bounded or big-bucket groups OOM at
        # compile (measured: a 64x2048 group at 1B/2048-ctx needs
        # 34 GB of scores on a 16 GB chip). Read once at construction:
        # the warmup variant set is sized from it, so a mid-life
        # change would dispatch never-warmed shapes.
        self._prefill_group_tokens = max(
            1, knobs.int_("LOCALAI_PREFILL_GROUP_TOKENS"))
        self._prefix_index = PrefixIndex()
        # fleet-digest prefix gossip: top-k (hash, tokens) summary,
        # recomputed on the scheduler thread ~1/s (the index has no
        # locking) and swapped in atomically for any-thread readers
        self._prefix_summary: tuple = ()
        self._prefix_summary_t = 0.0
        self._prefix_summary_rev = -1  # index revision last summarized
        # same-wave prefix grouping: request id -> (deadline, want_len)
        # for admissions deferred one scheduler iteration so a
        # wave-mate's prefill commits the shared prefix they copy from
        self._deferred: dict[str, tuple[float, int]] = {}
        self._pending: list[tuple[GenRequest, queue.SimpleQueue]] = []  # lint: guarded-by self._lock
        self._cancelled: dict[str, float] = {}  # lint: guarded-by self._lock
        # request lifecycle guards. Both knobs default OFF so the
        # unset path is byte-identical to the unguarded engine:
        # - LOCALAI_REQUEST_DEADLINE_S: default per-request deadline
        #   (seconds; a request's own timeout_s overrides)
        # - LOCALAI_MAX_QUEUE: admission queue cap — submit_many sheds
        #   beyond it with an immediate terminal "shed" event instead
        #   of queueing unbounded latency
        self._default_deadline_s = max(
            0.0, knobs.float_("LOCALAI_REQUEST_DEADLINE_S"))
        self.max_queue = max(0, knobs.int_("LOCALAI_MAX_QUEUE"))
        # sticky arm: flips on the first request that carries any
        # deadline, so deadline-free serving never pays the sweep
        self._deadlines_armed = self._default_deadline_s > 0
        # recent admission queue waits (seconds) — the live sample the
        # shed path turns into a Retry-After hint
        self._queue_waits: deque[float] = deque(maxlen=64)  # lint: guarded-by self._lock
        self._lock = threading.Condition()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self.metrics = EngineMetrics()
        self._all_slot_ids = jnp.arange(n_slots, dtype=jnp.int32)
        # Tiered KV memory (engine/kv_tier.py): hot HBM pages, warm
        # host-RAM pages (async spill + prefetch), cold on-disk
        # sessions in the prompt-cache format — resident sessions
        # become bound by host RAM instead of the arena. Single-chip
        # paged engines only: multihost/follower engines and
        # draft-model pairs force it off (spilled main-model pages
        # would strand the draft cache), and LOCALAI_KV_TIER=off
        # restores today's behavior byte-identically everywhere.
        self._tier = None
        # meshed engines force tiering off until spill learns to gather
        # the model-sharded arena (a host copy of a PAGED_KV_SPEC page
        # would be an implicit cross-shard all-gather per spill)
        if (self._paged and channel is None and not follower
                and draft is None and mesh is None
                and (knobs.flag("LOCALAI_KV_TIER") if kv_tier is None
                     else kv_tier)):
            from .kv_tier import KVTierManager

            self._tier = KVTierManager(self)
        # layer-granular weight paging (engine/weight_pager.py): the
        # parameter tree can leave the chip for a host-RAM warm mirror
        # while the engine (slots, KV, dispatch cache, tokenizer) stays
        # up, and streams back layer-by-layer ahead of first token.
        # Single-chip engines only: meshed trees don't round-trip
        # through one host mirror, follower/channel engines replay a
        # leader whose tree must stay put, and a draft pair would
        # strand its second tree. LOCALAI_WEIGHT_PAGING=off restores
        # the fully-resident path byte-identically (the pager never
        # touches eng.params while hot).
        # Nor a tree of two layer stacks (spec.n_dense_layers): the
        # pager's page li is row li of EVERY stacked leaf.
        self._pager = None
        if (channel is None and not follower and draft is None
                and mesh is None and not spec.n_dense_layers
                and (knobs.flag("LOCALAI_WEIGHT_PAGING")
                     if weight_paging is None else weight_paging)):
            from .weight_pager import WeightPager

            self._pager = WeightPager(self)
        # disaggregated serving hooks (engine/kv_migrate.Migrator): the
        # DisaggRouter attaches one per engine before start() — prefill
        # side captures finished slots' pages into the migration bus,
        # decode side adopts them at admission. None = no hooks, the
        # single-engine path byte-identical.
        self._migrator = None
        # stage label for active-slot deadline expiry: "decode" for a
        # normal engine, "prefill" for the disaggregated prefill engine
        # (its active slots are running prompts, not streams)
        self._deadline_stage = "decode"

        @partial(jax.jit, donate_argnums=(2, 5))
        def dispatch_decode1(params, tokens, cache, pos0, slot_ids,
                             sampling, active, masks, *tables):
            # q_len 1 per row; the batch is every cache row in order
            # (slot_ids feeds the sampler only), so a dense KV write is
            # a per-row DUS, not a cache-sized scatter
            view = route.open(cache, tables, max_seq)
            logits, view, experts = _forward_step(
                spec, params, tokens, pos0, view, active,
                route.forward_kw(
                    tables, jnp.ones(tokens.shape[:1], jnp.int32),
                    decode=True))
            cache = route.close(cache, view, tables)
            last = logits[:, -1, :]
            toks, sampling = _sample_masked(sampling, slot_ids, last,
                                            active, masks)
            return toks, cache, sampling, experts

        @jax.jit
        def _sample_only(sampling, slot_ids, logits, masks):
            return sample(sampling, slot_ids, logits, mask=masks)

        @jax.jit
        def dispatch_embed(params, tokens, cache, pos0, slot_ids):
            return forward_hidden(spec, params, tokens, pos0, cache, slot_ids)

        self._decode_fn = dispatch_decode1
        self._sample_fn = _sample_only
        self._hidden_fn = dispatch_embed
        # every jitted dispatch is a function named dispatch_<kind>, so
        # its XLA module (jit_dispatch_<kind>) keeps one name in device
        # traces whatever the variant
        self._decode_k_fns: dict[tuple, Any] = {}  # ("decode", k, W) |
        # ("spec", kd, rounds) | ("draft_prefill",) | ("prefill", W) |
        # ("mixed", W)
        # device-resident decode state: the next token and position of
        # every row the newest decode-advancing dispatch (a k-step scan
        # or a mixed step) advanced or admitted; the next one chains on
        # it without a host round trip. _dev_rows says whose they are
        # (slot index -> the request the row held then): a dispatch
        # takes the carry only when it holds every row it advances
        # (_carry_for), so rows that finish simply drop out of the
        # active mask and rows a mixed step admits join on the device
        self._dev_tokens: Any = None
        self._dev_pos: Any = None
        self._dev_rows: dict[int, GenRequest] = {}
        self._dev_window = 0  # context window of that dispatch: rows
        # parked on the device stay where a chain's windows cover them
        # async dispatch pipeline (see step()): FIFO of in-flight device
        # dispatches awaiting host-side harvest
        self._flights: deque[_Flight] = deque()
        self._pipeline_depth = 2  # decode scans kept in flight
        self._harvest_last: dict[int, int] = {}  # per slot, the token
        # the newest harvested flight sampled last (what a chained
        # flight's first step consumed)
        self._last_harvest_t = 0.0
        self._last_arrival = 0.0  # submit time of the newest request —
        # decode scheduling keeps scans short around an arrival
        self._step_ms = 0.0  # EWMA of device ms per decode step,
        # measured at scan harvest; _latency_k sizes open-capacity
        # scans from it
        self._last_decode_adv = 0.0  # perf_counter of the last dispatch
        # that advanced >=1 decode row; gaps between consecutive ones
        # while a slot decodes feed engine_decode_stall_seconds
        self.warmup_reused = False  # True when warmup() was skipped
        # because an identical variant set is already in the persistent
        # compile cache (see warmup docstring); surfaced in the load
        # phase breakdown
        self.state_dir = state_dir or hbm_ledger.default_state_dir()
        # what the scheduler was doing (telemetry/flightrec.py): phase
        # spans whose self times are plain floats here, published as
        # engine_sched_phase_seconds_total by _update_gauges through
        # children bound once; program loads attributed to the dispatch
        # that stood still for them; token counts at the dispatch sites
        self._phases = PhaseClock()
        self._phase_pub = dict.fromkeys(self._phases.totals, 0.0)
        self._phase_ctr = {
            ph: tm.ENGINE_SCHED_PHASE.labels(model=self._mlabel, phase=ph)
            for ph in self._phases.totals}
        self._loads = LoadWatch(self._mlabel)
        self._in_warmup = False
        self._tok_ctr: dict = {}  # kind -> its bound counter children
        self._expert_ctr: dict = {}  # the same for the expert counters
        self._expert_out: list = []  # expert statistics of the newest
        # dispatch's programs (_dev_exec), until its flight takes them
        # the layers that route (every layer of the main stack: a
        # qwen2_moe dense-only layer still runs its zeroed router)
        self._n_expert_layers = (
            spec.n_layers - spec.n_dense_layers if spec.n_experts else 0)
        # {window: layers that have it}; 0 = full attention
        from ..models.transformer import _layer_windows

        lw = _layer_windows(spec)
        self._layer_windows: dict = (
            {int(spec.sliding_window or 0): spec.n_layers} if lw is None
            else {int(w): int(n) for w, n in zip(
                *np.unique(np.asarray(lw), return_counts=True))})
        self._tick_t = 0.0  # last ~1 Hz gauge tick
        # warmup-captured XLA cost model: per-dispatch FLOPs/bytes
        # accounting + the MFU gauge (telemetry/costmodel.py). Host-held
        # counters only — the hot path never syncs for accounting.
        self._costmodel: Optional[costmodel.CostModel] = None
        if knobs.flag("LOCALAI_COSTMODEL"):
            # raises for a device_kind the peak table does not know:
            # its peaks size dispatches, so no other device's row may
            # stand in
            self._costmodel = costmodel.CostModel(
                self._mlabel, self.device_kind,
                1 if mesh is None else int(mesh.devices.size))
        # component-level HBM ledger (telemetry/hbm_ledger.py):
        # long-lived device allocations registered here, reconciled
        # against device.memory_stats() each gauge sweep
        self._ledger: Optional[hbm_ledger.HBMLedger] = None
        if knobs.flag("LOCALAI_HBM_LEDGER"):
            led = hbm_ledger.HBMLedger(self._mlabel)
            if self._pager is not None:
                # paged weights attribute by tier: hot follows the
                # device-resident bytes (the promotion cursor's fraction
                # mid-stream), warm is the host mirror — host=True keeps
                # it out of the device drift sum
                pager = self._pager
                led.register("weights_hot", pager.device_bytes)
                led.register("weights_warm", pager.host_bytes,
                             host=True)
            else:
                led.register("weights", self.params)
            led.register("kv_arena",
                         (self.cache.k, self.cache.v))
            if getattr(self.cache, "k_scale", None) is not None:
                led.register("kv_scales",
                             (self.cache.k_scale, self.cache.v_scale))
            if self.draft_cache is not None:
                led.register("draft_cache", self.draft_cache)
            led.register("sampler", self.sampling)
            if self._tier is not None:
                # in-flight tier spill/fetch DMA buffers (callable
                # source: the windows' byte counts move every sweep)
                tier = self._tier
                led.register(
                    "staging",
                    lambda: tier._swin.flying + tier._fwin.flying)
            self._ledger = led

    def _kernel_ineligible(self) -> str:
        """Why this engine does NOT take the Pallas attention kernel —
        the first condition that rules it out — or "" when it does
        (Mosaic compiles on this platform and the shapes qualify). Env
        override: LOCALAI_DECODE_KERNEL=0/1; forcing =1 also allows the
        (slow) interpret path so CPU tests exercise the kernel engine."""
        from ..ops.decode_attention import PAGE, _interpret

        env = knobs.str_("LOCALAI_DECODE_KERNEL")
        if env in ("0", "false", "off"):
            return f"LOCALAI_DECODE_KERNEL={env}"
        forced = env in ("1", "true", "on")
        if self.mesh is not None:
            # meshed serving runs the kernel per-shard under shard_map;
            # shapes must split evenly over the mesh axes
            if self._paged:
                # paged meshed engines have exactly ONE kernel route:
                # the ragged kernel over the model-sharded arena
                # (ops.ragged_paged_attention.sharded_ragged_append_
                # attend); ineligible shapes take the GSPMD gather route
                from ..ops.ragged_paged_attention import (
                    mesh_ragged_eligible,
                )

                if not mesh_ragged_eligible(
                    self.mesh, self.spec.n_kv_heads, self.spec.n_heads,
                    self.spec.kv_dim,
                ):
                    return ("kv heads / kv_dim do not split into "
                            "128-lane bands over the mesh \"model\" axis")
                if self.draft is not None and not mesh_ragged_eligible(
                    self.mesh, self.draft[0].n_kv_heads,
                    self.draft[0].n_heads, self.draft[0].kv_dim,
                ):
                    # spec-decode rounds run the draft through the same
                    # shard_map route; an ineligible draft keeps the
                    # whole engine on the GSPMD gather fallback
                    return ("draft model kv heads do not split over the "
                            "mesh \"model\" axis")
            else:
                # dense meshed: ops.decode_attention.sharded_append_attend
                from ..ops.decode_attention import mesh_kernel_eligible

                if not mesh_kernel_eligible(
                    self.mesh, self.spec.n_kv_heads, self.spec.n_heads,
                    self.spec.kv_dim, self.n_slots,
                ):
                    return ("kv heads / slots do not split over the "
                            "mesh axes")
        if not forced and _interpret():
            return (f"platform {self.platform}: Mosaic compiles on tpu "
                    "only")
        # paged arenas DMA whole pool pages (page-table lookups), so
        # the pool's own divisibility guarantee replaces the dense
        # kernel's max_seq % PAGE requirement
        if not self._paged and self.max_seq % PAGE:
            return f"dense cache: max_seq {self.max_seq} % {PAGE} != 0"
        if self.spec.kv_dim % 128:
            return f"kv_dim {self.spec.kv_dim} % 128 != 0"
        if self.spec.attn_logit_softcap:
            return "attn_logit_softcap"
        # int8 caches qualify (the kernel reads int8 pages + per-row
        # scales directly), and so do per-layer sliding windows (the
        # window is the kernel's operand, carried by the layer scan)
        return ""

    # ------------------------------------------- paged KV pool (host side)

    def _phys_rows(self, slot_rows: list, window: int) -> np.ndarray:
        """Per-batch-row physical page tables [B, window//page] for a
        dispatch payload (plain int32 — multihost followers replay it
        like any scalar). ``slot_rows`` maps batch row -> slot index;
        None rows and entries beyond a slot's allocation point at the
        trash page, whose garbage reads are causally masked."""
        wp = window // self._page
        out = np.full((len(slot_rows), wp), TRASH_PAGE, np.int32)
        for r, si in enumerate(slot_rows):
            if si is None:
                continue
            t = self._pool.table(si)
            n = min(len(t), wp)
            if n:
                out[r, :n] = t[:n]
        return out

    def _wb_rows(self, entries: list, window: int) -> np.ndarray:
        """Write-back page tables [B, window//page]: the physical page
        for every window page intersecting the row's write span, trash
        everywhere else — so a dispatch persists exactly its own writes
        and can never touch a shared (refcount > 1) prefix page or a
        parked row's resident pages. ``entries``: (slot index | None,
        (start, end) token span | None) per batch row."""
        wp = window // self._page
        P = self._page
        out = np.full((len(entries), wp), TRASH_PAGE, np.int32)
        for r, (si, span) in enumerate(entries):
            if si is None or span is None:
                continue
            start, end = span
            if end <= start:
                continue
            t = self._pool.table(si)
            for p in range(start // P, min(-(-end // P), wp)):
                if p >= len(t) or not self._pool.writable(t[p]):
                    raise RuntimeError(
                        f"paged KV: slot {si} write span page {p} is not "
                        "privately writable — allocator invariant broken")
                out[r, p] = t[p]
        return out

    def _pool_ensure(self, slot: "_Slot", n_tokens: int) -> bool:
        """Grow the slot's page table to cover ``n_tokens`` positions,
        reclaiming free slots' resident prefixes (least valuable first,
        prefix_index LRU x length) under pool pressure. False = the
        arena is genuinely full of ACTIVE state; the caller ends or
        requeues the work."""
        if faultinject.ACTIVE:
            # chaos surface for the OOM-forensics path: a fault here is
            # the deterministic stand-in for a device RESOURCE_EXHAUSTED
            # during KV growth — _loop's catch writes the HBM
            # post-mortem before failing the active slots
            faultinject.fire("engine.hbm_alloc")
        try:
            self._pool.ensure(slot.idx, n_tokens)
            return True
        except PagePoolExhausted:
            pass
        now = time.monotonic()
        victims = sorted(
            (s for s in self.slots
             if not s.active and s is not slot
             and self._pool.held(s.idx)),
            key=lambda s: self._prefix_index.value(s.idx, now))
        for v in victims:
            if self._tier is not None:
                # enqueue an async D2H spill FIRST: the reclaim then
                # DEMOTES the resident prefix to host RAM instead of
                # discarding it — device-order serialization keeps the
                # copy coherent across the drop below, and an injected
                # spill fault simply falls back to today's plain drop
                self._tier.demote_urgent(v)
            self._pool.drop(v.idx)
            v.cache_tokens = []
            v.n_past = 0
            self._prefix_index.remove(v.idx)
            tm.ENGINE_KV_PAGE_ALLOC.labels(
                model=self._mlabel, outcome="reclaimed").inc()
            try:
                self._pool.ensure(slot.idx, n_tokens)
                return True
            except PagePoolExhausted:
                continue
        tm.ENGINE_KV_PAGE_ALLOC.labels(
            model=self._mlabel, outcome="exhausted").inc()
        log.warning("KV page pool exhausted: slot %d needs %d tokens",
                    slot.idx, n_tokens)
        return False

    def _page_headroom(self, req: GenRequest) -> bool:
        """Admission gate: worst-case pages for the prompt must fit in
        free + reclaimable (free slots' private pages) capacity, or the
        request waits in the queue instead of thrashing an admit/finish
        cycle. Soft check — dispatch-time _pool_ensure is the backstop."""
        st = self._pool.stats()
        need = self._pool.pages_for(len(req.prompt_ids) + 1)
        if st.free >= need:
            return True
        reclaim = sum(
            1 for s in self.slots if not s.active
            for p in self._pool.table(s.idx)
            if self._pool.writable(p) and not self._pool.pinned(p))
        return st.free + reclaim >= need

    def _spec_decode_fn(self, kd: int, rounds: int):
        """Jitted speculative decoding: ``rounds`` iterations of
        (draft kd-1 greedy tokens -> ONE main verify forward of T=kd ->
        on-device cumulative acceptance) per host dispatch. Greedy
        acceptance reproduces the main model's greedy sequence EXACTLY
        while paying ~1 main forward per accepted run instead of per
        token (ref: the proto's DraftModel/NDraft surface; greenfield on
        TPU). Rejected-draft cache rows land beyond the valid prefix and
        are rewritten next round — the same invariant the multi-step
        overshoot discard relies on."""
        key = ("spec", kd, rounds)
        fn = self._decode_k_fns.get(key)
        if fn is not None:
            return fn
        spec = self.spec
        dspec = self.draft[0]  # static; draft params passed per call
        route, max_seq = self._route, self.max_seq

        @partial(jax.jit, donate_argnums=(2, 3))
        def dispatch_spec(params, dparams, cache, dcache, tokens, pos0,
                          active, *tables):
            # both caches open once for all rounds; the close persists
            # only the eligible rows' verify/draft spans (ineligible
            # rows' write tables are trash)
            arena, darena = cache, dcache
            cache = route.open(arena, tables, max_seq)
            dcache = route.open(darena, tables, max_seq)
            ones = jnp.ones(tokens.shape[:1], jnp.int32)

            def rag(n):
                # verify rows are q_len == kd rows, draft steps q_len 1
                return route.forward_kw(tables, ones * n)

            def round_(carry, _):
                tok, pos, cache, dcache = carry

                def dstep(c, _):
                    t, p, dc = c
                    lg, dc = forward(dspec, dparams, t, p, dc, **rag(1))
                    nt = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
                    p2 = jnp.where(active, p + 1, p)
                    return (nt[:, None], p2, dc), nt

                # kd steps (not kd-1): the extra step's sampled token is
                # discarded, but it writes d_{kd-1}'s K/V so the draft
                # cache covers the full accepted prefix after a clean
                # round (otherwise a stale row sits inside the draft's
                # attended prefix and quietly kills the acceptance rate)
                (_, _, dcache2), dts = lax.scan(
                    dstep, (tok, pos, dcache), None, length=kd)
                d_toks = dts[: kd - 1].T  # [S, kd-1]
                xin = jnp.concatenate([tok, d_toks], axis=1)  # [S, kd]
                lg, cache2 = forward(spec, params, xin, pos, cache,
                                     **rag(kd))
                m_toks = jnp.argmax(lg, -1).astype(jnp.int32)  # [S, kd]
                ok = (m_toks[:, : kd - 1] == d_toks).astype(jnp.int32)
                j = 1 + jnp.cumprod(ok, axis=1).sum(1)  # [S] in 1..kd
                j = jnp.where(active, j, 0)
                last = jnp.take_along_axis(
                    m_toks, (jnp.maximum(j, 1) - 1)[:, None], axis=1)
                pos2 = jnp.where(active, pos + j, pos)
                return (last, pos2, cache2, dcache2), (d_toks, m_toks, j)

            (tok_f, pos_f, cache, dcache), (D, Mt, J) = lax.scan(
                round_, (tokens, pos0, cache, dcache), None, length=rounds)
            return (D, Mt, J, tok_f, pos_f,
                    route.close(arena, cache, tables),
                    route.close(darena, dcache, tables))

        self._decode_k_fns[key] = dispatch_spec
        return dispatch_spec

    def _spec_sampled_fn(self, kd: int, rounds: int):
        """Jitted speculative REJECTION sampling (Leviathan et al.): the
        draft SAMPLES kd-1 tokens from its filtered distribution q, one
        main forward computes the filtered p at every position, and each
        draft token is accepted with prob min(1, p(t)/q(t)); the first
        rejection resamples from norm(max(p-q, 0)), and a fully-accepted
        run samples its last token from p directly. This reproduces exact
        samples from the main model's distribution — the sampled-path
        counterpart of the greedy _spec_decode_fn (ref: the proto's
        DraftModel/NDraft surface; greenfield on TPU). Temp<=0 slots
        collapse to exact one-hot distributions, so mixed greedy/sampled
        batches stay correct. RNG rides SamplingState.rng per slot with a
        static number of draws per round."""
        key = ("spec_s", kd, rounds)
        fn = self._decode_k_fns.get(key)
        if fn is not None:
            return fn
        from ..ops.sampling import NEG_INF, filtered_candidates

        spec = self.spec
        dspec = self.draft[0]
        S = self.n_slots

        def split_rows(rng):  # [S, 2] -> (carry keys, use keys)
            s = jax.vmap(jax.random.split)(rng)
            return s[:, 0], s[:, 1]

        def gumbel_pick(keys, probs):  # [R,2], [R,C] -> [R] candidate idx
            logp = jnp.where(probs > 0,
                             jnp.log(jnp.maximum(probs, 1e-30)), NEG_INF)
            g = jax.vmap(
                lambda k, row: jax.random.gumbel(k, row.shape, jnp.float32)
            )(keys, logp)
            return jnp.argmax(logp + g, axis=-1)

        route, max_seq = self._route, self.max_seq

        @partial(jax.jit, donate_argnums=(3, 4))
        def dispatch_spec_s(params, dparams, sampling, cache, dcache,
                            tokens, pos0, active, *tables):
            arena, darena = cache, dcache
            cache = route.open(arena, tables, max_seq)
            dcache = route.open(darena, tables, max_seq)
            all_slots = jnp.arange(S, dtype=jnp.int32)
            rep_slots = jnp.repeat(all_slots, kd)
            ones = jnp.ones(tokens.shape[:1], jnp.int32)

            def rag(n):
                return route.forward_kw(tables, ones * n)

            def round_(carry, _):
                tok, pos, cache, dcache, rng = carry

                def dstep(c, _):
                    t, p, dc, rng = c
                    lg, dc = forward(dspec, dparams, t, p, dc, **rag(1))
                    qp, qidx = filtered_candidates(
                        sampling, all_slots, lg[:, -1])
                    rng, k1 = split_rows(rng)
                    j = gumbel_pick(k1, qp)
                    nt = jnp.take_along_axis(
                        qidx, j[:, None], 1)[:, 0].astype(jnp.int32)
                    qsel = jnp.take_along_axis(qp, j[:, None], 1)[:, 0]
                    p2 = jnp.where(active, p + 1, p)
                    return (nt[:, None], p2, dc, rng), (nt, qsel, qp, qidx)

                # kd steps like the greedy path: the last step's K/V write
                # keeps the draft cache covering the full accepted prefix
                (_, _, dcache2, rng), (dts, qsel, qps, qidxs) = lax.scan(
                    dstep, (tok, pos, dcache, rng), None, length=kd)
                d_toks = dts[: kd - 1].T  # [S, kd-1]
                xin = jnp.concatenate([tok, d_toks], axis=1)  # [S, kd]
                lg, cache2 = forward(spec, params, xin, pos, cache,
                                     **rag(kd))
                pp, pidx = filtered_candidates(
                    sampling, rep_slots, lg.reshape(S * kd, -1))
                C = pp.shape[-1]
                pp = pp.reshape(S, kd, C)
                pidx = pidx.reshape(S, kd, C)
                qps_t = qps.transpose(1, 0, 2)  # [S, kd, C]
                qidxs_t = qidxs.transpose(1, 0, 2)
                d_all = dts.T  # [S, kd]
                # p_i(d_i): main filtered prob of each draft token
                p_at_d = jnp.sum(
                    pp * (pidx == d_all[:, :, None]), axis=-1)  # [S, kd]
                rng, ku = split_rows(rng)
                u = jax.vmap(
                    lambda k: jax.random.uniform(k, (kd - 1,))
                )(ku)  # [S, kd-1]
                ratio = p_at_d[:, : kd - 1] / jnp.maximum(
                    qsel.T[:, : kd - 1], 1e-30)
                ok = (u < jnp.minimum(ratio, 1.0)).astype(jnp.int32)
                j = 1 + jnp.cumprod(ok, axis=1).sum(1)  # [S] in 1..kd
                j = jnp.where(active, j, 0)
                # replacement token per position: residual norm(max(p-q,0))
                # at a rejection, p itself at the bonus position kd-1
                match = (qidxs_t[:, :, :, None] == pidx[:, :, None, :])
                q_on_p = jnp.sum(qps_t[:, :, :, None] * match, 2)  # [S,kd,C]
                residual = jnp.maximum(pp - q_on_p, 0.0)
                rsum = residual.sum(-1, keepdims=True)
                res_dist = jnp.where(rsum > 1e-9, residual / rsum, pp)
                is_bonus = (jnp.arange(kd) == kd - 1)[None, :, None]
                dist = jnp.where(is_bonus, pp, res_dist)
                rng, kr = split_rows(rng)
                kr_all = jax.vmap(
                    lambda k: jax.random.split(k, kd))(kr)  # [S, kd, 2]
                fj = gumbel_pick(
                    kr_all.reshape(S * kd, 2), dist.reshape(S * kd, C))
                fin = jnp.take_along_axis(
                    pidx.reshape(S * kd, C), fj[:, None], 1
                )[:, 0].astype(jnp.int32).reshape(S, kd)
                last = jnp.take_along_axis(
                    fin, (jnp.maximum(j, 1) - 1)[:, None], axis=1)
                pos2 = jnp.where(active, pos + j, pos)
                return (last, pos2, cache2, dcache2, rng), (d_toks, fin, j)

            (_, _, cache, dcache, rng), (D, Fin, J) = lax.scan(
                round_, (tokens, pos0, cache, dcache, sampling.rng),
                None, length=rounds)
            return (D, Fin, J, rng, route.close(arena, cache, tables),
                    route.close(darena, dcache, tables))

        self._decode_k_fns[key] = dispatch_spec_s
        return dispatch_spec_s

    def _prefill_fn(self, window: int):
        """The FIRST chunk of a long prompt on a seq-sharded serving
        mesh: the chunk's attention runs as seq-parallel ring attention
        over the mesh's "seq" axis (VERDICT r3: long-context must flow
        through the SERVING path, not just exist as an op). K/V writes
        only; every other chunk of every prompt rides the mixed step."""
        key = ("prefill", window)
        fn = self._decode_k_fns.get(key)
        if fn is not None:
            return fn
        spec = self.spec
        route = self._route

        @partial(jax.jit, donate_argnums=(2,))
        def dispatch_prefill(params, tokens, cache, pos0, slot_ids,
                             *tables):
            # only the K/V writes matter — materializing [B, T, V]
            # logits would waste bucket*V f32 of HBM per row
            view = route.open(cache, tables, window)
            # chunk dispatches are always full-bucket wide
            qlens = jnp.full(tokens.shape[:1], tokens.shape[1], jnp.int32)
            _, view = forward_hidden(
                spec, params, tokens, pos0, view,
                **route.forward_kw(tables, qlens, slot_ids=slot_ids,
                                   ring=True))
            return route.close(cache, view, tables)

        self._decode_k_fns[key] = dispatch_prefill
        return dispatch_prefill

    def _mixed_fn(self, window: int):
        """The ONE step that admits prompts, sized to what it carries:
        two row groups through the one cache route in one dispatch.

        - the decode group ``[n_slots, 1]`` — the decodek step's
          contract: identity rows (row b == slot b), an ``active``
          mask, sampler state untouched for parked rows;
        - the prompt group ``[R, bucket]`` with ``slot_ids`` (pad rows
          carry the out-of-bounds sentinel ``n_slots``): ``final`` rows
          reset their sampler state, seed the penalty window and sample
          their first token in THIS dispatch (one round trip off TTFT
          for singles and waves alike); the other rows are non-final
          chunks of a prompt longer than the bucket and write K/V only.

        No row decoding is the same program with the decode group all
        inactive. It returns the decode carry (``tok_next``,
        ``pos_next``) for every row that decodes from here on — the
        rows that decoded and the finals — so the next decodek scan
        chains on device state and newly admitted rows join it.

        Per-slot sampler math is that of the decode scan (active-mask
        merge) and of a solo admission (sentinel-id scatter drops), so a
        request yields the tokens it yields with the engine to itself
        (tests/test_mixed_dispatch.py)."""
        key = ("mixed", window)
        fn = self._decode_k_fns.get(key)
        if fn is not None:
            return fn
        spec = self.spec
        route = self._route
        S = self.n_slots

        @partial(jax.jit, donate_argnums=(1, 2))
        def dispatch_mixed(params, cache, sampling, dtoks, dpos, active,
                           toks, pos0, slot_ids, n_chunk, final, tails,
                           tail_lens, dmasks, pmasks, reset, *tables,
                           soft=None):
            from ..models.transformer import _lm_head
            from ..ops.sampling import reset_slots

            def rows(tokens, pos0, row0, q_lens, soft=None, live=None,
                     **kw):
                # page tables ride as ONE [S + R, pages] pair, the
                # decode group's rows first: this group's share
                tabs = tuple(t[row0:row0 + tokens.shape[0]]
                             for t in tables)
                per, kw = _rows_kw(
                    route.forward_kw(tabs, q_lens, row0=row0, **kw))
                return Rows(tokens, pos0, soft=soft, live=live, **per), kw

            # decode group. Parked rows: trash write pages on the pool,
            # a no-op rewrite (write_mask) on the dense cache — their
            # resident prefixes survive whatever position they carry
            dgroup, pass_kw = rows(dtoks, dpos, 0,
                                   jnp.ones((S,), jnp.int32),
                                   live=active, write_mask=active)
            # prompt group: n_chunk IS the per-row ragged query length
            # (pad rows carry 1); rows map to slots through slot_ids on
            # the dense cache and through the tables on the pool
            if soft is not None:
                soft = _soft_expand(toks, *soft)
            pgroup, _ = rows(toks, pos0, S, n_chunk, soft=soft,
                             live=slot_ids < S, slot_ids=slot_ids)
            # ONE pass: every weight is read once for both groups
            view = route.open(cache, tables, window)
            (dhidden, hidden), view, experts = forward_rows(
                spec, params, (dgroup, pgroup), view, **pass_kw)
            cache = route.close(cache, view, tables)
            dsamp, sampling = _sample_masked(
                sampling, jnp.arange(S, dtype=jnp.int32),
                _lm_head(spec, params, dhidden)[:, -1], active, dmasks)
            # reset -> closed-form penalty-window seed -> sample, for
            # the final rows only (every other row's scatter drops)
            fin = jnp.where(final, slot_ids, S)
            sampling = reset_slots(sampling, fin, *reset)
            sampling = seed_windows(sampling, fin, tails, tail_lens)
            # LM head on each row's LAST position only: [R, T, V] logits
            # would cost bucket*V f32 per row for values nobody reads
            last_h = jax.vmap(
                lambda h, n: lax.dynamic_slice_in_dim(h, n - 1, 1, 0)[0]
            )(hidden, n_chunk)
            logits = _lm_head(spec, params, last_h[:, None, :])[:, 0]
            with jax.named_scope("sample"):
                ptoks, sampling = sample(sampling, fin, logits,
                                         mask=pmasks)
            # the carry: decode rows step on, finals join at the end of
            # their prompt, and every prompt row parks there (a later
            # scan writes a parked row's K/V at its carried position,
            # which has to lie beyond what the row holds)
            tok_next = dsamp.at[fin].set(ptoks, mode="drop")[:, None]
            pos_next = jnp.where(active, dpos + 1, dpos).at[slot_ids].set(
                pos0 + n_chunk, mode="drop")
            return (jnp.concatenate([dsamp, ptoks]), tok_next, pos_next,
                    cache, sampling, _expert_stats(experts))

        self._decode_k_fns[key] = dispatch_mixed
        return dispatch_mixed

    # the most prompt tokens a row takes in one step. A step's matmuls
    # grow with every row they carry (v5e, int8 weights: 9.6 ms of
    # weight fusions at 16 rows, 17.9 at 144; PERF §6 PR 37) while each
    # decoding row stands still, so a chunk is no longer than this and
    # the decoding rows get a token between chunks; a row ladder finer
    # than this many tokens buys programs to compile, not time. An expert
    # model's step hardly grows with its rows (the grouped matmul reads
    # the experts that have tokens, most of them from 144 rows on:
    # 2.96 ms a layer at 144 rows, 3.65 at 528; PERF §6 PR 38), so its
    # chunk is four times as long and a prompt holds the rows that
    # decode for a third of the steps — and that chunk is all the
    # prompt tokens its STEP takes (_row_ladder): one long prompt a
    # step, the next one after it. Prompts that ride together get
    # their first token together, replies of equal length then end
    # together and are replaced together, groups merge, and what a
    # token costs hangs on how the slots have grouped (served: 14.1 ms
    # in a group of four, 14.7 in a pair, 15.3 alone; PERF §6 PR 38);
    # admitted one after the other they stay a prompt's steps apart
    _STEP_TOKENS = 128
    _EXPERT_STEP_TOKENS = 512

    @property
    def _step_tokens(self) -> int:
        return (self._EXPERT_STEP_TOKENS if self.spec.n_experts
                else self._STEP_TOKENS)

    # the most tokens one pass of the embeddings path takes (see
    # _dev_exec "embed"); every prefill bucket above it is its multiple
    _EMBED_CHUNK = 1024

    def _row_ladder(self, bucket: int) -> tuple[int, ...]:
        """Row counts a prompt group of this bucket is padded up to:
        powers of two up to what the group-token budget
        (LOCALAI_PREFILL_GROUP_TOKENS) and n_slots allow — for an
        expert model one chunk's worth of tokens (_step_tokens) — the
        small rungs merged into the first one worth a program."""
        cap = max(1, min(self.n_slots,
                         self._prefill_group_tokens // bucket))
        if self.spec.n_experts:
            cap = max(1, min(cap, self._step_tokens // bucket))
        rungs, r = [], 1
        while r < cap:
            if r * bucket >= self._step_tokens:
                rungs.append(r)
            r *= 2
        return (*rungs, cap)

    @property
    def _step_buckets(self) -> tuple[int, ...]:
        """The prefill buckets a step's prompt group takes: up to the
        first one that holds _step_tokens."""
        bs = self.prefill_buckets
        top = next((i for i, b in enumerate(bs)
                    if b >= self._step_tokens), len(bs) - 1)
        return bs[:top + 1]

    def _mixed_shape(self, rems: list[int],
                     budget_ms: float = 0.0,
                     window_of: Any = None) -> tuple[int, int]:
        """(rows, bucket) of the prompt group for a wave whose rows
        have ``rems`` prompt tokens left — every row rides every step,
        whatever its bucket: the smallest step bucket covering the
        largest remainder, the row count rounded up that bucket's
        ladder (rows beyond its cap ride the next step). A row longer
        than the bucket takes a bucket-wide non-final chunk and rides
        on: how a prompt is chunked hangs on its own length alone,
        never on what rides beside it.

        Cost scheduling (LOCALAI_COST_SCHED + LOCALAI_ITL_BUDGET_MS,
        ``budget_ms`` > 0 while rows decode): no bucket over the
        largest whose PREDICTED device time fits the budget; when none
        fits, the smallest predicted one (progress beats stalling);
        buckets with no prediction never constrain."""
        buckets = self._step_buckets
        bucket = next((b for b in buckets if b >= max(rems)), buckets[-1])

        def rows_at(b):
            ladder = self._row_ladder(b)
            n = min(len(rems), ladder[-1])
            return next(r for r in ladder if r >= n)

        if budget_ms > 0.0:
            pred = [(self._costmodel.predict_ms(
                "mixed", ("mixed", (rows_at(b), b), window_of(b))), b)
                for b in buckets if b <= bucket]
            pred = [p for p in pred if p[0] is not None]
            if pred:
                fit = [b for ms, b in pred if ms <= budget_ms]
                bucket = min(bucket, fit[-1] if fit else pred[0][1])
        return rows_at(bucket), bucket

    def _mixed_variants(self):
        """Every (rows, bucket, window) a mixed step can be dispatched
        at — warmup() compiles exactly these."""
        prev = 0
        for b in self._step_buckets:
            # a step picks this bucket only when some row's chunk
            # exceeds the previous one, so its window covers at least
            # prev + 2: smaller rungs can never be dispatched
            for w in self._route.ladder("mixed", prev + 2):
                for rows in self._row_ladder(b):
                    yield rows, b, w
            prev = b

    def _itl_budget_ms(self) -> float:
        """The explicit inter-token-latency budget cost scheduling
        packs against, in ms; 0.0 when cost scheduling is off, the
        cost model is absent, or no budget is set — every caller
        treats 0.0 as 'token-budget sizing only'."""
        if self._costmodel is None or not knobs.flag("LOCALAI_COST_SCHED"):
            return 0.0
        return max(0.0, knobs.float_("LOCALAI_ITL_BUDGET_MS"))

    def _cost_sched_on(self) -> bool:
        """Whether predictor-driven admission/deadline decisions are
        active (independent of the ITL packing budget)."""
        return (self._costmodel is not None
                and knobs.flag("LOCALAI_COST_SCHED"))

    def _mixed_window(self, riding: list, decoding: list, ahead: dict,
                      bucket: int) -> int:
        """Context window of a mixed step: the route's window covering
        every advancing row — decode rows at their position on the
        device (``ahead`` of the host's by the scans in flight), prompt
        rows to the end of their chunk."""
        need_w = max(
            [s.n_past + ahead.get(s.idx, 0) + 1 for s in decoding]
            + [s.n_past + min(s.n_prompt - s.n_past, bucket)
               for s in riding]) + 1
        return self._route.window(
            need_w, "mixed",
            (k[1] for k in self._decode_k_fns if k[0] == "mixed"))

    def _draft_prefill_fn(self):
        """Draft-model prefill (the draft cache must mirror the main
        cache's token positions for speculative decoding)."""
        fn = self._decode_k_fns.get(("draft_prefill",))
        if fn is not None:
            return fn
        dspec = self.draft[0]
        route, max_seq = self._route, self.max_seq

        @partial(jax.jit, donate_argnums=(2,))
        def dispatch_draft_prefill(dparams, tokens, dcache, pos0,
                                   slot_ids, *tables, q_lens=None):
            # the draft arena shares the main pool's page geometry and
            # tables; the write table carries ONLY the rows whose draft
            # K/V must land (prefill rows — decode rows never mirror)
            view = route.open(dcache, tables, max_seq)
            _, view = forward(
                dspec, dparams, tokens, pos0, view,
                **route.forward_kw(tables, q_lens, slot_ids=slot_ids))
            return route.close(dcache, view, tables)

        self._decode_k_fns[("draft_prefill",)] = dispatch_draft_prefill
        return dispatch_draft_prefill

    def _kv_copy_fn(self, n: int, with_draft: bool):
        """Jitted, donated row-to-row KV prefix copy: ``n`` (static,
        power-of-two bucket) leading positions of the src slot's rows —
        k/v and, when quantized, k_scale/v_scale — land in the dst
        slot's rows via per-layer dynamic_slice/dynamic_update_slice.
        Copying past the actual match length is harmless (positions
        beyond dst's valid prefix are rewritten by prefill or causally
        invisible) and keeps the jit variant set tiny. ``with_draft``
        copies the draft cache rows in the SAME dispatch so speculative
        decoding's draft prefix stays exactly as coherent at dst as it
        was at src."""
        key = ("kvcopy", n, with_draft)
        fn = self._decode_k_fns.get(key)
        if fn is not None:
            return fn

        def _copy_rows(cache: KVCache, src, dst) -> KVCache:
            def cp4(a):
                L, _, _, F = a.shape
                row = lax.dynamic_slice(a, (0, src, 0, 0), (L, 1, n, F))
                return lax.dynamic_update_slice(a, row, (0, dst, 0, 0))

            def cp3(a):
                row = lax.dynamic_slice(a, (0, src, 0),
                                        (a.shape[0], 1, n))
                return lax.dynamic_update_slice(a, row, (0, dst, 0))

            return KVCache(
                k=cp4(cache.k), v=cp4(cache.v),
                k_scale=cp3(cache.k_scale) if cache.quantized else None,
                v_scale=cp3(cache.v_scale) if cache.quantized else None,
            )

        if with_draft:
            @partial(jax.jit, donate_argnums=(0, 1))
            def dispatch_kvcopy(cache, dcache, src, dst):
                return (_copy_rows(cache, src, dst),
                        _copy_rows(dcache, src, dst))
        else:
            @partial(jax.jit, donate_argnums=(0,))
            def dispatch_kvcopy(cache, src, dst):
                return _copy_rows(cache, src, dst)

        self._decode_k_fns[key] = dispatch_kvcopy
        return dispatch_kvcopy

    @staticmethod
    def _spec_eligible(s: _Slot) -> bool:
        """Penalty/grammar/bias/multimodal/mirostat slots need per-token
        sampler state the speculative path does not thread (mm: the draft
        cache never saw the image soft tokens; mirostat: mu adapts per
        emitted token)."""
        r = s.request
        return not (
            r is None or r.constraint or r.logit_bias
            or r.repeat_penalty not in (0.0, 1.0)
            or r.frequency_penalty or r.presence_penalty
            or r.soft_embeds is not None
            or r.mirostat
        )

    def _spec_mode(
        self, decoding: list[_Slot]
    ) -> tuple[Optional[str], list[_Slot]]:
        """PER-SLOT speculative eligibility (VERDICT r1 weak #7: one
        penalty slot must not disable spec decoding for the whole
        batch). Returns (mode, eligible slots): "greedy" when every
        eligible slot is temp<=0 (exact argmax replay), "sampled"
        otherwise (rejection sampling reproduces the main model's
        distribution exactly); (None, []) when spec cannot run."""
        if self.draft is None:
            return None, []
        elig = [s for s in decoding if self._spec_eligible(s)]
        if not elig:
            return None, []
        sampled = any(s.request.temperature > 0 for s in elig)
        return ("sampled" if sampled else "greedy"), elig

    # lint: region hot_path
    def _spec_decode_step(self, decoding: list[_Slot],
                          mode: str = "greedy") -> None:
        """One speculative dispatch (see _spec_decode_fn /
        _spec_sampled_fn)."""
        t0 = time.perf_counter()
        S = self.n_slots
        kd = self.n_draft
        # span must fit EVERY decode slot's row (ineligible active slots
        # ride along inactive but still receive verify-window writes
        # beyond their valid prefix)
        room = min(self.max_seq - 1 - s.n_past
                   for s in self.slots if s.state is SlotState.DECODE)
        need = max((s.request.max_tokens - len(s.generated)
                    for s in decoding if s.request is not None),
                   default=1)
        rounds = max(1, min(self.decode_steps // kd,
                            max(room // kd, 1),
                            -(-need // kd)))  # no overshoot rounds
        span = rounds * kd
        if self._paged:
            for s in list(decoding):
                if not self._pool_ensure(s, s.n_past + span):
                    self._finish(s, "length")
                    decoding.remove(s)
            if not decoding:
                return
        elig = {s.idx for s in decoding}
        tokens = np.zeros((S, 1), np.int32)
        pos0 = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        for s in self.slots:
            if s.idx in elig:
                tokens[s.idx, 0] = (s.generated[-1] if s.generated
                                    else s.request.prompt_ids[-1])
                pos0[s.idx] = s.n_past
                active[s.idx] = True
            elif s.state is SlotState.DECODE:
                # active-but-ineligible: rides inactive (advances in the
                # normal dispatch after this one); its valid prefix must
                # NOT be trimmed — the span fit is guaranteed by `room`
                pos0[s.idx] = s.n_past
            else:
                # parked rows must not run off the row end mid-scan.
                # Paged rows never write back (trash wb), so only the
                # in-dispatch position clamps; the prefix survives.
                limit = max(self.max_seq - 1 - span, 0)
                if s.n_past > limit and not self._paged:
                    s.n_past = limit
                    s.cache_tokens = s.cache_tokens[:limit]
                pos0[s.idx] = min(s.n_past, limit)
        payload = {
            "kd": kd, "rounds": rounds, "tokens": tokens, "pos0": pos0,
            "active": active,
        }
        if self._paged:
            payload["pt"] = self._phys_rows(list(range(S)), self.max_seq)
            payload["wb"] = self._wb_rows(
                [(s.idx, ((s.n_past, s.n_past + span)
                          if s.idx in elig else None))
                 for s in self.slots], self.max_seq)
        self._note_ragged_rows("verify", len(decoding))
        D, Mt, J = self._run("spec_s" if mode == "sampled" else "spec",
                             payload)
        # lint: ignore[hot-path-sync] spec verify is a deliberately blocking dispatch: emission needs J/D/Mt on host before the next spec round is sized
        D = np.asarray(D)  # [rounds, S, kd-1] draft candidates
        # lint: ignore[hot-path-sync] same blocking spec harvest (see D above)
        Mt = np.asarray(Mt)  # [rounds, S, kd] main tokens (greedy verify
        # choices, or rejection-resample/bonus tokens on the sampled path)
        # lint: ignore[hot-path-sync] same blocking spec harvest (see D above)
        J = np.asarray(J)  # [rounds, S] emitted counts
        dt_ms = (time.perf_counter() - t0) * 1e3
        emitted_total = 0
        for s in decoding:
            s.t_decode_ms += dt_ms
            prev_last = int(tokens[s.idx, 0])
            for r in range(rounds):
                if s.state is not SlotState.DECODE:
                    break
                j = int(J[r, s.idx])
                emitted = [int(t) for t in D[r, s.idx, : j - 1]]
                emitted.append(int(Mt[r, s.idx, j - 1]))
                for tok_out in emitted:
                    if s.state is not SlotState.DECODE:
                        break
                    s.cache_tokens.append(prev_last)
                    s.n_past += 1
                    prev_last = tok_out
                    emitted_total += 1
                    self._emit_token(s, tok_out, defer=True)
            if s.state is SlotState.DECODE:
                self._flush_emit(s)
        self.metrics.spec_tokens += emitted_total
        self.metrics.spec_dispatches += 1
        if emitted_total:
            tm.ENGINE_GENERATED_TOKENS.labels(model=self._mlabel).inc(
                emitted_total)
        # spec advanced positions the decodek device-resident carry may
        # still hold stale copies of; a stale inactive-row position would
        # write K/V inside the advanced prefix
        self._dev_rows = {}
        dt = time.perf_counter() - t0
        if dt > 0 and emitted_total:
            self._note_tokens_per_second(emitted_total, dt)
        tm.ENGINE_MIXED_DISPATCH.labels(
            model=self._mlabel, composition="decode_only").inc()
        self._note_decode_advance(t0)
        self.metrics.slots_busy = sum(1 for s in self.slots if s.active)
    # lint: endregion hot_path

    def _decode_k_fn(self, k: int, window: int):
        """Jitted k-step decode: ``lax.scan`` over k forward+sample steps so
        one host dispatch yields k tokens per active slot. This hides
        host<->device dispatch latency (SURVEY.md §7 hard part #2:
        per-token host sync kills throughput).

        ``window`` (static) slices the KV cache to the live-context bucket
        for the whole scan: per-step attention traffic scales with actual
        context use, not max_seq — the XLA stand-in for ragged paged
        attention. The slice/write-back happens once per dispatch, inside
        the jit, so XLA keeps it in place on the donated buffer."""
        fn = self._decode_k_fns.get(("decode", k, window))
        if fn is not None:
            return fn
        spec = self.spec
        route = self._route

        @partial(jax.jit, donate_argnums=(2, 5))
        def dispatch_decodek(params, tokens, cache, pos0, slot_ids,
                             sampling, active, *tables):
            view = route.open(cache, tables, window)
            kw = route.forward_kw(
                tables, jnp.ones(tokens.shape[:1], jnp.int32), decode=True)

            def step(carry, _):
                tokens, pos, view, sampling = carry
                logits, view, experts = _forward_step(
                    spec, params, tokens, pos, view, active, kw)
                toks, sampling = _sample_masked(
                    sampling, slot_ids, logits[:, -1, :], active, None)
                pos = jnp.where(active, pos + 1, pos)
                return (toks[:, None], pos, view, sampling), (toks, experts)

            (tok_next, pos_next, view, sampling), (toks_seq, experts) = \
                lax.scan(step, (tokens, pos0, view, sampling), None,
                         length=k)
            # tok_next/pos_next are returned so the next dispatch can
            # chain on device state without a host round trip
            return (toks_seq.T, tok_next, pos_next,
                    route.close(cache, view, tables), sampling,
                    jnp.sum(experts, axis=0))  # toks [S, k]

        self._decode_k_fns[("decode", k, window)] = dispatch_decodek
        return dispatch_decodek

    # ------------------------------------------- multihost dispatch funnel

    def _run(self, kind: str, payload: dict) -> Any:
        """Publish-then-execute: every device dispatch flows through here
        so a multihost leader's followers can replay the identical XLA
        program (parallel/multihost.py). Payloads carry only small host
        inputs; device state advances in place on every host."""
        if faultinject.ACTIVE:
            # chaos surface: a fault here behaves exactly like a device
            # dispatch blowing up — _loop's catch fails active slots
            # with one terminal error event each, scheduler survives.
            # The scope binds the wave's request ids so a delivered
            # fault lands as a span event on each affected trace
            with fault_scope(s.request.id for s in self.slots
                             if s.request is not None):
                faultinject.fire("engine.device_step")
        # cost-model accounting key: non-flight kinds account here, right
        # after the dispatch enqueues (flight kinds account at harvest,
        # where the span is known). Host-side dict math only — no syncs.
        cm = self._costmodel
        ckey = (costmodel.dispatch_key(kind, payload)
                if cm is not None and kind not in costmodel.FLIGHT_KINDS
                else None)
        ch = self.channel
        if ch is not None and not self.follower:
            # dense masks are bit-packed for the wire only; the local exec
            # keeps the raw ndarray (solo mode never pays the pack cost)
            wire = payload
            if isinstance(payload.get("masks"), np.ndarray):
                wire = {**payload, "masks": _pack_masks(payload["masks"])}
            # publish + device-enqueue under ONE critical section: the
            # follower replays records in published order, so the leader's
            # own XLA dispatch order must match it exactly or the
            # cross-host collectives inside the programs deadlock.
            # The envelope carries the wave's distributed trace ids
            # (OUTSIDE "data" — the codec whitelist governs replayed
            # payload fields only) so follower replays emit entries
            # joined to the leader's traces
            trace = sorted({s.request.trace_id for s in self.slots
                            if s.request is not None
                            and s.request.trace_id})
            with ch.order_lock:
                ch.publish(kind, {"model": self.tag, "data": wire,
                                  "trace": trace})
                out = self._exec_traced(kind, payload)
            if ckey is not None:
                cm.on_dispatch(kind, ckey)
            return out
        out = self._exec_traced(kind, payload)
        if ckey is not None:
            cm.on_dispatch(kind, ckey)
        return out

    def _exec_traced(self, kind: str, payload: dict) -> Any:
        """``_dev_exec`` under its span and its load watch: the
        ``sched:enqueue:<kind>`` phase (payload -> device arrays ->
        launch; a no-op off the scheduler thread) and the binding that
        lets a program load name this dispatch's full variant key."""
        vkey = costmodel.variant_key(kind, payload)
        with self._phases.span("sched:enqueue:" + kind, {"key": vkey}), \
                self._loads.watch(kind, vkey, self._in_warmup):
            return self._dev_exec(kind, payload)

    def _dev_exec(self, kind: str, p: dict) -> Any:
        """Device-only work for one dispatch record. MUST be fully
        determined by (kind, payload) + engine construction — no reads of
        leader-side scheduler state — so follower replay stays lockstep."""
        # paged dispatches carry their page-table snapshots in the
        # payload ("pt"/"wb" int32 index arrays), so follower replay
        # needs no allocator state; a dense cache has no tables
        def tabs():
            if not self._paged:
                return ()
            return (jnp.asarray(p["pt"]), jnp.asarray(p["wb"]))

        def carry_in(x):
            # host-fed decode tokens / positions, committed like the
            # device carry they stand in for (see __init__)
            return jax.device_put(x, self._device)

        def cap(fn, *args, **kw):
            # for the load watch, by reference: the jitted function
            # (its cache size is the fallback evidence of a load) and
            # the call's arguments (a load's arg_sig is made from them)
            note_jit(fn, args, kw)
            # warmup capture hook: AOT-compile this exact variant and
            # record its XLA cost row (no-op outside capture mode —
            # the serving hot path pays one attribute check)
            cm = self._costmodel
            if cm is not None and cm.capturing:
                cm.capture(kind, costmodel.dispatch_key(kind, p),
                           fn, args, kw)

        if kind == "prefill":
            toks = jnp.asarray(p["toks"])
            pos0 = jnp.asarray(p["pos0"])
            sids = jnp.asarray(p["slot_ids"])
            fn = self._prefill_fn(p.get("window", self.max_seq))
            tables = tabs()
            cap(fn, self.params, toks, self.cache, pos0, sids, *tables)
            self.cache = fn(self.params, toks, self.cache, pos0, sids,
                            *tables)
            if self.draft is not None:
                self.draft_cache = self._draft_prefill_fn()(
                    self.draft[1], toks, self.draft_cache, pos0, sids,
                    *tables, q_lens=jnp.full(
                        toks.shape[:1], toks.shape[1], jnp.int32))
            return None
        if kind == "mixed":
            # a pure device op with a scalar payload (token ids + per-
            # row index vectors only), so multihost followers replay it
            # like any other record; "carry": the decode group's
            # tokens and positions are the device-resident carry of the
            # dispatch before it, as on the decodek path
            S = self.n_slots
            toks = jnp.asarray(p["toks"])
            pos0 = jnp.asarray(p["pos0"])
            sids = jnp.asarray(p["slot_ids"])
            n_chunk = jnp.asarray(p["n_chunk"])
            masks = _unpack_masks(p["masks"])
            soft = self._soft_dense(p.get("soft"), *p["toks"].shape)
            reset = tuple(jnp.asarray(p["reset"][k]) for k in (
                "temperature", "top_k", "top_p", "min_p",
                "repeat_penalty", "freq_penalty", "presence_penalty",
                "repeat_last_n", "seeds", "has_seed",
                "typical_p", "mirostat", "mirostat_tau", "mirostat_eta"))
            if p["carry"] and self._dev_tokens is not None:
                dtoks, dpos = self._dev_tokens, self._dev_pos
            else:
                dtoks, dpos = carry_in(p["dtoks"]), carry_in(p["dpos"])
            tables = tabs()
            args = [self.params, self.cache, self.sampling, dtoks, dpos,
                    jnp.asarray(p["active"]), toks, pos0, sids, n_chunk,
                    jnp.asarray(p["final"]), jnp.asarray(p["tails"]),
                    jnp.asarray(p["tail_lens"]),
                    None if masks is None else masks[:S],
                    None if masks is None else masks[S:], reset, *tables]
            fn = self._mixed_fn(p.get("window", self.max_seq))
            cap(fn, *args, soft=soft)
            (toks_out, self._dev_tokens, self._dev_pos, self.cache,
             self.sampling, experts) = fn(*args, soft=soft)
            self._expert_out = [experts]
            if self.draft is not None:
                # the prompt rows mirror into the draft cache (decode
                # rows advance without draft writes, exactly as on the
                # decodek path): the prompt group's own tables
                self.draft_cache = self._draft_prefill_fn()(
                    self.draft[1], toks, self.draft_cache, pos0, sids,
                    *(t[S:] for t in tables), q_lens=n_chunk)
            return toks_out
        if kind == "decode1":
            masks = _unpack_masks(p["masks"])
            args = [self.params, jnp.asarray(p["tokens"]), self.cache,
                    jnp.asarray(p["pos0"]), self._all_slot_ids,
                    self.sampling, jnp.asarray(p["active"]), masks,
                    *tabs()]
            cap(self._decode_fn, *args)
            toks, self.cache, self.sampling, experts = self._decode_fn(
                *args)
            self._expert_out = [experts]
            return toks
        if kind == "decodek":
            fn = self._decode_k_fn(p["k"], p["window"])
            if p["carry"] and self._dev_tokens is not None:
                tok_dev, pos_dev = self._dev_tokens, self._dev_pos
            else:
                tok_dev = carry_in(p["tokens"])
                pos_dev = carry_in(p["pos0"])
            act_dev = jnp.asarray(p["active"])
            extra = tabs()
            cap(fn, self.params, tok_dev, self.cache, pos_dev,
                self._all_slot_ids, self.sampling, act_dev, *extra)
            batches, self._expert_out = [], []
            for _ in range(p["depth"]):
                (toks, tok_dev, pos_dev, self.cache, self.sampling,
                 experts) = fn(
                    self.params, tok_dev, self.cache, pos_dev,
                    self._all_slot_ids, self.sampling, act_dev, *extra,
                )
                batches.append(toks)
                self._expert_out.append(experts)
            self._dev_tokens, self._dev_pos = tok_dev, pos_dev
            return batches
        if kind == "spec":
            fn = self._spec_decode_fn(p["kd"], p["rounds"])
            D, Mt, J, _, _, self.cache, self.draft_cache = fn(
                self.params, self.draft[1], self.cache, self.draft_cache,
                jnp.asarray(p["tokens"]), jnp.asarray(p["pos0"]),
                jnp.asarray(p["active"]), *tabs(),
            )
            return D, Mt, J
        if kind == "spec_s":
            import dataclasses

            fn = self._spec_sampled_fn(p["kd"], p["rounds"])
            D, Fin, J, rng, self.cache, self.draft_cache = fn(
                self.params, self.draft[1], self.sampling, self.cache,
                self.draft_cache, jnp.asarray(p["tokens"]),
                jnp.asarray(p["pos0"]), jnp.asarray(p["active"]),
                *tabs(),
            )
            self.sampling = dataclasses.replace(self.sampling, rng=rng)
            return D, Fin, J
        if kind == "kvcopy":
            # cross-slot prefix copy: pure device op with a scalar
            # payload, so it broadcasts to multihost followers like any
            # other dispatch record (no KV bytes cross the wire)
            src = jnp.asarray(p["src"], jnp.int32)
            dst = jnp.asarray(p["dst"], jnp.int32)
            fn = self._kv_copy_fn(p["n"], self.draft is not None)
            if self.draft is not None:
                cap(fn, self.cache, self.draft_cache, src, dst)
                self.cache, self.draft_cache = fn(
                    self.cache, self.draft_cache, src, dst)
            else:
                cap(fn, self.cache, src, dst)
                self.cache = fn(self.cache, src, dst)
            return None
        if kind == "embed":
            # a long prompt goes through in chunks on its throwaway
            # cache: one [1, bucket] pass would hold a [heads, bucket,
            # bucket] f32 score tensor beside the model (2 GB at 4096)
            bucket = p["bucket"]
            cache = KVCache.create(self.spec, 1, bucket,
                                   self.cache.k.dtype)
            toks = jnp.asarray(p["toks"])
            step = min(bucket, self._EMBED_CHUNK)
            zeros = jnp.zeros((1,), jnp.int32)
            parts = []
            for off in range(0, bucket, step):
                hidden, cache = self._hidden_fn(
                    self.params, toks[:, off:off + step], cache,
                    zeros + off, zeros)
                parts.append(hidden)
            return (parts[0] if len(parts) == 1
                    else jnp.concatenate(parts, axis=1))
        raise ValueError(f"unknown dispatch record kind: {kind!r}")

    # ------------------------------------------------------------------ API

    def start(self) -> None:
        if self.follower:
            return  # replay-only: the follower loop drives _dev_exec
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="llm-engine", daemon=True
            )
            self._thread.start()

    def close(self) -> None:
        with self._lock:
            self._stop = True
            self._lock.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        # a closed engine must not leave stale occupancy on /metrics
        tm.ENGINE_SLOTS_BUSY.labels(model=self._mlabel).set(0)
        tm.ENGINE_QUEUE_DEPTH.labels(model=self._mlabel).set(0)
        tm.ENGINE_KV_UTIL.labels(model=self._mlabel).set(0.0)
        tm.ENGINE_KV_RESIDENT_PREFIX.labels(model=self._mlabel).set(0.0)
        tm.ENGINE_MESH_DEVICES.labels(model=self._mlabel).set(0)
        if self._paged:
            tm.ENGINE_KV_PAGES_IN_USE.labels(model=self._mlabel).set(0)
            tm.ENGINE_KV_PAGES_SHARED.labels(model=self._mlabel).set(0)
        if self._tier is not None:
            # land every in-flight tier transfer (pins release, staged
            # fetches abandon) so pool/tier leak checks stay clean
            self._tier.close()
            for tname in ("hbm", "host", "disk"):
                tm.ENGINE_KV_TIER_PAGES.labels(
                    model=self._mlabel, tier=tname).set(0)
        if self._pager is not None:
            # abort any in-flight page move, release the host mirror,
            # deregister from the cross-engine LRU
            self._pager.close()
            for tname in ("hot", "warm"):
                tm.ENGINE_WEIGHT_PAGES.labels(
                    model=self._mlabel, tier=tname).set(0)
        tm.ENGINE_MFU.labels(model=self._mlabel).set(0.0)
        if self._ledger is not None:
            self._ledger.reset_gauges()
        if self.mesh is not None:
            # release the process-wide meshed gate so a later unmeshed
            # engine regains the fused int8 kernel (single-owner rule)
            from ..models import quant

            quant.set_meshed_serving(False)

    def _active_exemplar(self) -> Optional[dict]:
        """Exemplar labels for a batch-level latency sample: the first
        active slot's trace id (a batch observation has no single
        owner; one representative trace is what OM exemplars carry)."""
        for s in self.slots:
            if (s.active and s.request is not None
                    and s.request.trace_id):
                return {"trace_id": s.request.trace_id}
        return None

    def cost_stats(self) -> Optional[dict]:
        """Cost-model summary (MFU, per-kind roofline) for
        /backend/monitor; None when LOCALAI_COSTMODEL=off."""
        return (self._costmodel.stats()
                if self._costmodel is not None else None)

    def hbm_stats(self) -> Optional[dict]:
        """HBM-ledger snapshot for /backend/monitor; None when
        LOCALAI_HBM_LEDGER=off."""
        return (self._ledger.snapshot()
                if self._ledger is not None else None)

    def predicted_drain_s(self) -> Optional[float]:
        """Public, any-thread view of the cost-model queue-drain
        prediction (telemetry/digest.py reads it for the fleet
        heartbeat); None when cost scheduling is off or the predictor
        has no rates yet."""
        with self._lock:
            return self._predicted_drain_s()

    def prefix_summary(self) -> list:
        """Scheduler-cached top-k prefix-hash summary (see
        PrefixIndex.summary) — an atomic tuple swap away from the
        scheduler thread, safe to read from any thread."""
        return [[h, n] for h, n in self._prefix_summary]

    def _warmup_signature(self) -> str:
        """Fingerprint of everything the warmup variant set depends on:
        model geometry, engine shape knobs, backend/device kind. Two
        engines with equal signatures compile the identical HLO set."""
        import hashlib

        mesh_desc = (tuple(sorted(self.mesh.shape.items()))
                     if self.mesh is not None else None)
        dev = jax.devices()[0]
        blob = repr((
            repr(self.spec), self.n_slots, self.max_seq,
            tuple(self.prefill_buckets),
            str(jnp.dtype(self.cache.k.dtype)), self.decode_steps,
            self.latency_target_ms, self.sampling.window,
            self._use_kernel, mesh_desc, jax.default_backend(),
            getattr(dev, "device_kind", ""), jax.__version__,
            # the paged pool changes every variant's cache geometry
            self._paged, self._page, self.kv_pages,
        ))
        return hashlib.sha256(blob.encode()).hexdigest()[:20]

    def _warmup_marker_path(self) -> Optional[str]:
        """Marker file recording a COMPLETED warmup of this signature in
        the persistent compilation cache dir (None when no persistent
        cache is in use — skipping warmup is only safe when a
        mid-request 'compile' would be a fast cache load, not a real
        compile)."""
        import os

        cache_dir = jax.config.jax_compilation_cache_dir
        if not cache_dir or not jax.config.jax_enable_compilation_cache:
            return None
        return os.path.join(
            cache_dir, f"warmup-{self._warmup_signature()}.ok")

    def warmup(self) -> None:
        """Compile the serving dispatch-variant set up front.

        At 8B scale one jit variant costs ~13s to compile; a cold
        variant landing mid-request is a 13-second TTFT outlier
        (measured through the HTTP bench: ragged arrivals hit group
        sizes the first admission wave never used). All-pad dispatches
        — every row pointing at the out-of-bounds sentinel slot id, or
        an all-inactive scan — exercise the identical jit shapes
        without touching engine state, so this is safe before serving.
        With the persistent compilation cache the cost after a code
        change is one cold pass; afterwards seconds.

        Even cache-hit warmups are not free at 8B scale: every variant
        still TRACES its python graph and round-trips the cache
        (seconds apiece across dozens of variants — load wall time the
        r5 bench measured but could not attribute). When a previous
        load of the IDENTICAL signature completed a warmup into the
        configured persistent cache (marker file), the whole pass is
        skipped: any variant a request later touches jit-compiles as a
        fast persistent-cache load instead of a cold compile. Kill
        switch: LOCALAI_WARMUP_REUSE=off (e.g. after pruning the cache
        dir without removing the warmup markers)."""
        import os

        t0 = time.perf_counter()
        marker = self._warmup_marker_path()
        reuse_ok = knobs.flag("LOCALAI_WARMUP_REUSE")
        if marker is not None and reuse_ok and os.path.exists(marker):
            # the capture pass rode the skipped warmup, so reload the
            # cost rows the original warmup exported — same signature,
            # same HLO set, same XLA cost rows. A marker without its
            # sidecar (written before sidecars existed, or pruned) would
            # leave the predictor blind for the whole process, so fall
            # through to a full pass ONCE — under the populated compile
            # cache that pass is trace + cache loads, and completing it
            # rewrites marker + sidecar.
            cm = self._costmodel
            restored = -1
            if cm is not None:
                try:
                    with open(marker + ".cost.json") as f:
                        restored = cm.import_rows(json.load(f))
                except (OSError, ValueError):
                    restored = -1
            if cm is None or restored >= 0:
                self.warmup_reused = True
                if restored > 0:
                    log.info("warmup reuse: %d cost rows restored",
                             restored)
                tm.ENGINE_WARMUP_SECONDS.labels(
                    model=self._mlabel, mode="reuse").set(
                    time.perf_counter() - t0)
                log.info("warmup skipped: variant set %s already in "
                         "the persistent compile cache",
                         os.path.basename(marker))
                return
            log.info("warmup reuse declined: cost sidecar missing for "
                     "%s — re-capturing", os.path.basename(marker))
        n_variants = 0

        def _warm(kind, payload):
            # every warmup dispatch compiles exactly one (fn, shape)
            # jit variant; the count is the series the ragged unification
            # collapses (engine_dispatch_compile_variants_count).
            # Capture mode rides the pass: _dev_exec records each
            # variant's XLA cost row (telemetry/costmodel.py) while the
            # pad dispatch itself stays unaccounted (it is not traffic)
            # The pass's program loads are counted like any other,
            # marked in_warmup: "what serving reached that warmup did
            # not cover" is then one query over one family
            nonlocal n_variants
            n_variants += 1
            cm = self._costmodel
            self._in_warmup = True
            if cm is not None:
                cm.capturing = True
            try:
                return self._run(kind, payload)
            finally:
                self._in_warmup = False
                if cm is not None:
                    cm.capturing = False

        W = self.sampling.window
        S = self.n_slots
        pad_reset = self._reset_columns([], 1)
        # every window below comes from the route's ladder: full-width
        # page tables have NO ladder (one variant per token-budget
        # shape), a dense cache has its power-of-two rungs
        ladder = self._route.ladder
        inactive = {
            "tokens": np.zeros((S, 1), np.int32),
            "pos0": np.zeros((S,), np.int32),
            "active": np.zeros((S,), bool),
        }
        # the admission step: exactly the (rows, bucket, window) shapes
        # _enqueue_mixed can ask for. All-pad prompt rows (sentinel
        # slot ids) and an all-inactive decode group exercise the
        # identical jit shapes without touching engine state.
        for R, bucket, w in self._mixed_variants():
            payload = {
                "toks": np.zeros((R, bucket), np.int32),
                "pos0": np.zeros((R,), np.int32),
                "slot_ids": np.full((R,), S, np.int32),
                "n_chunk": np.ones((R,), np.int32),
                "final": np.zeros((R,), bool),
                "tails": np.zeros((R, W), np.int32),
                "tail_lens": np.zeros((R,), np.int32),
                "masks": None, "soft": None,
                "reset": {k: np.repeat(v, R, axis=0)
                          for k, v in pad_reset.items()},
                "window": w, "carry": False,
                "dtoks": inactive["tokens"], "dpos": inactive["pos0"],
                "active": inactive["active"],
            }
            if self._paged:
                # all-trash tables: garbage reads are masked,
                # writebacks drop — engine state stays untouched
                wp = w // self._page
                payload["pt"] = np.zeros((S + R, wp), np.int32)
                payload["wb"] = np.zeros((S + R, wp), np.int32)
            _warm("mixed", payload)
        if self._ring_axis():
            # a long prompt's first chunk on a seq-sharded mesh rides
            # ring attention at live-context window buckets — compile
            # those too, or the first long prompt stalls on a
            # mid-request jit. Chunk dispatches are always full-bucket
            # wide, so their windows start at the bucket's own window
            # bucket (window >= n_past + bucket).
            for w in ladder("prefill", self.prefill_buckets[-1]):
                _warm("prefill", {
                    "toks": np.zeros((1, self.prefill_buckets[-1]),
                                     np.int32),
                    "pos0": np.zeros((1,), np.int32),
                    "slot_ids": np.full((1,), S, np.int32),
                    "window": w,
                })
        if self._prefix_enabled:
            # cross-slot KV copy variants (cheap compiles — pure DUS,
            # no matmuls — but a mid-admission stall is still a stall);
            # src == dst == 0 is a self-copy no-op on device state
            if self._paged:
                # paged copies are always whole-page: ONE variant
                _warm("kvcopy", {"src": 0, "dst": 0, "n": self._page})
            else:
                for w in ladder("kvcopy"):
                    _warm("kvcopy", {"src": 0, "dst": 0, "n": w})
        ks = self._warm_ks
        for k in sorted(ks):
            if k > 1:
                for w in ladder("decode"):
                    payload = {
                        "k": k, "window": w, "depth": 1, "carry": False,
                        **inactive,
                    }
                    if self._paged:
                        wp = w // self._page
                        payload["pt"] = np.zeros((S, wp), np.int32)
                        payload["wb"] = np.zeros((S, wp), np.int32)
                    _warm("decodek", payload)
        payload = {**inactive, "masks": None}
        if self._paged:
            wp = self.max_seq // self._page
            payload["pt"] = np.zeros((S, wp), np.int32)
            payload["wb"] = np.zeros((S, wp), np.int32)
        _warm("decode1", payload)
        self._dev_rows = {}  # warmup carries are not serving state
        # block until every warmup compile retires so the first real
        # request measures serving, not the compiler
        jax.block_until_ready(self.cache.k)
        # the variant-explosion kill made visible: each warmup dispatch
        # compiled exactly one (fn, shape) variant, so this count IS the
        # jit-cache population the ragged unification collapses
        self.warmup_variants = n_variants
        tm.ENGINE_DISPATCH_VARIANTS.labels(model=self._mlabel).set(
            n_variants)
        tm.ENGINE_WARMUP_SECONDS.labels(
            model=self._mlabel, mode="cold").set(time.perf_counter() - t0)
        if marker is not None:
            # record the completed variant set so the next load of this
            # exact signature skips the whole pass (best effort: losing
            # the marker only costs the speedup)
            try:
                # cost rows first: a marker without its sidecar would
                # reuse-skip future warmups with no way to restore the
                # predictor's cost table
                cm = self._costmodel
                if cm is not None:
                    rows = cm.export_rows()
                    if rows:
                        with open(marker + ".cost.json", "w") as f:
                            json.dump(rows, f)
                with open(marker, "w") as f:
                    f.write("ok")
            except OSError:
                pass

    def submit(self, req: GenRequest) -> queue.SimpleQueue:
        """Queue a request; returns the event stream queue."""
        return self.submit_many([req])[0]

    def submit_many(
        self, reqs: list[GenRequest],
        outs: Optional[list[queue.SimpleQueue]] = None,
    ) -> list[queue.SimpleQueue]:
        """Queue a burst of requests under ONE lock acquisition, so the
        scheduler admits them as a single wave. Beyond fairness, this
        makes the batched final-prefill group size deterministic (the
        per-request submit path can race admission into odd-sized groups,
        each a fresh jit shape). ``outs`` lets a caller supply the event
        queues (the DisaggRouter resubmits a migrated request onto the
        client's ORIGINAL stream queue — no forwarding hop per token)."""
        if outs is None:
            outs = [queue.SimpleQueue() for _ in reqs]
        ok: list[tuple[GenRequest, queue.SimpleQueue]] = []
        for req, out in zip(reqs, outs):
            if len(req.prompt_ids) >= self.max_seq:
                out.put(StreamEvent(
                    done=True, finish_reason="error",
                    error=f"prompt ({len(req.prompt_ids)} tokens) exceeds "
                          f"context size {self.max_seq}"))
                # terminal-at-submit requests still get a complete trace
                # entry: the HTTP layer may have opened one at receive
                TRACER.event(req.id, "done", model=self._mlabel)
                TRACER.annotate(req.id, "terminal", outcome="error",
                                detail="prompt exceeds context")
                TRACER.finish(req.id, status="error")
            elif not req.prompt_ids:
                out.put(StreamEvent(done=True, finish_reason="error",
                                    error="empty prompt"))
                TRACER.event(req.id, "done", model=self._mlabel)
                TRACER.annotate(req.id, "terminal", outcome="error",
                                detail="empty prompt")
                TRACER.finish(req.id, status="error")
            else:
                ok.append((req, out))
        if ok:
            # arrival bookkeeping only for ADMITTED work: a stream of
            # rejected requests (empty/over-context prompts) must not
            # engage the burst clamp or the prefill-formation hold —
            # they contribute nothing a prefill could serve (ADVICE
            # r5 #4)
            now = time.perf_counter()
            for req, _ in ok:
                if req.disagg is not None and req.t_submit:
                    # migrated resubmit: the request keeps the t_submit/
                    # deadline the router stamped at ORIGINAL arrival, so
                    # TTFT and deadline enforcement stay end-to-end
                    # across the prefill→migrate→decode relay
                    if req.deadline:
                        self._deadlines_armed = True
                    continue
                req.t_submit = now
                budget = req.timeout_s or self._default_deadline_s
                if budget > 0:
                    req.deadline = now + budget
                    self._deadlines_armed = True
            shed: list[tuple[GenRequest, queue.SimpleQueue]] = []
            with self._lock:
                if self.max_queue > 0:
                    # bounded admission: refuse the overflow NOW with a
                    # terminal shed event + backoff hint, instead of
                    # letting queue latency grow without bound. Newest
                    # arrivals shed first — earlier ones were promised
                    # a place the moment they fit
                    room = self.max_queue - len(self._pending)
                    if room < len(ok):
                        ok, shed = ok[:max(0, room)], ok[max(0, room):]
                    if shed:
                        retry_s = self._retry_after_s()
                self._pending.extend(ok)
                if ok:
                    self._last_arrival = now
                depth = len(self._pending)
                self._lock.notify_all()
            for req, out in shed:
                if req.disagg is not None:
                    # a shed migrated resubmit must free its interchange
                    # blocks (idempotent KVHandoff.release)
                    req.disagg.release()
                out.put(StreamEvent(
                    done=True, finish_reason="shed",
                    error=f"admission queue full "
                          f"({self.max_queue} queued); retry later",
                    retry_after_s=retry_s))
                TRACER.event(req.id, "shed", t=now, model=self._mlabel)
                TRACER.annotate(req.id, "terminal", t=now, outcome="shed",
                                retry_after_s=round(retry_s, 3))
                TRACER.finish(req.id, status="shed")
                tm.ENGINE_REQUESTS.labels(model=self._mlabel,
                                          reason="shed").inc()
                tm.ENGINE_REQUESTS_SHED.labels(
                    model=self._mlabel, reason="queue_full").inc()
            for req, _ in ok:
                TRACER.event(req.id, "queue", t=now, model=self._mlabel)
                # adopt the trace's distributed id (minted at the HTTP
                # edge, or just now by the auto-opened trace): dispatch
                # records and follower replays carry it from here on
                if not req.trace_id:
                    req.trace_id = TRACER.trace_id_of(req.id)
            tm.ENGINE_QUEUE_DEPTH.labels(model=self._mlabel).set(depth)
            if self._autostart:
                self.start()
        return outs

    def generate(self, req: GenRequest) -> StreamEvent:
        """Blocking helper: drain the stream, return the final event."""
        q = self.submit(req)
        while True:
            ev = q.get()
            if ev.done:
                return ev

    def cancel(self, request_id: str) -> None:
        """Release a queued or in-flight request (ref: llama.cpp task
        cancel on client disconnect — the slot frees at the next
        scheduler iteration; its stream gets a final "cancelled"
        event). A cancel that RACES AHEAD of submit is retained (with an
        expiry) so the late-arriving request is still dropped."""
        with self._lock:
            self._cancelled[request_id] = time.perf_counter()
            self._lock.notify_all()

    _CANCEL_TTL_S = 300.0  # unmatched cancel ids expire (leak bound)

    def _retry_after_s(self) -> float:
        """Suggested client backoff for a shed request. With cost
        scheduling on, the PREDICTED drain time of the actual queue
        contents (prompt lengths and token budgets the predictor can
        cost) — a hint that tracks what is really queued instead of
        what recently happened. Falls back to the p90 of recently
        observed admission queue waits when the predictor has nothing,
        both clamped to the same sane window. Caller holds self._lock."""
        drain = self._predicted_drain_s()
        if drain is not None:
            return drain
        ws = sorted(self._queue_waits)
        if not ws:
            return 1.0
        p90 = ws[min(len(ws) - 1, int(0.9 * len(ws)))]
        return min(30.0, max(0.5, p90))

    def _predicted_drain_s(self) -> Optional[float]:
        """Predicted seconds until the CURRENT queue drains: per queued
        request, predicted prefill (per-token rate x prompt length)
        plus predicted decode (per-step rate x token budget), spread
        across the slots, clamped to the Retry-After window. None when
        cost scheduling is off or the predictor has no rates yet (the
        caller falls back to historical p90). Caller holds self._lock."""
        if not self._cost_sched_on():
            return None
        cm = self._costmodel
        tok_ms = cm.prefill_token_ms()
        step_ms = (self._step_ms if self._step_ms > 0.0
                   else cm.decode_step_ms())
        if tok_ms is None and step_ms is None:
            return None
        total_ms = 0.0
        for req, _ in self._pending:
            if tok_ms is not None:
                total_ms += tok_ms * len(req.prompt_ids)
            if step_ms is not None:
                total_ms += step_ms * max(0, req.max_tokens)
        return min(30.0, max(0.5, total_ms / 1e3
                             / max(1, self.n_slots)))

    def _purge_expired_cancels(self, now: float) -> int:
        """Drop race-ahead cancel ids older than _CANCEL_TTL_S; returns
        how many expired. Caller holds self._lock. Called from BOTH the
        cancellation sweep and the idle wait in _loop — an idle engine
        never runs step(), so without the idle-path purge a burst of
        unmatched cancels would sit for the engine's lifetime."""
        # lint: holds self._lock
        expired = [r for r, t in self._cancelled.items()
                   if now - t > self._CANCEL_TTL_S]
        for rid in expired:
            del self._cancelled[rid]
        return len(expired)

    def _apply_cancellations(self) -> None:
        with self._lock:
            if not self._cancelled:
                return
            now = time.perf_counter()
            n_expired = self._purge_expired_cancels(now)
            cancelled = self._cancelled
            # queued requests: drop before admission
            still = []
            dropped = []
            for req, out in self._pending:
                if req.id in cancelled:
                    del cancelled[req.id]
                    self._deferred.pop(req.id, None)
                    out.put(StreamEvent(done=True,
                                        finish_reason="cancelled"))
                    dropped.append(req.id)
                else:
                    still.append((req, out))
            self._pending = still
        if n_expired:
            tm.ENGINE_CANCELLATIONS.labels(
                model=self._mlabel, reason="expired").inc(n_expired)
        for rid in dropped:
            TRACER.event(rid, "done")
            TRACER.annotate(rid, "terminal", outcome="cancelled",
                            stage="queued")
            TRACER.finish(rid, status="cancelled")
            tm.ENGINE_REQUESTS.labels(model=self._mlabel,
                                      reason="cancelled").inc()
            tm.ENGINE_CANCELLATIONS.labels(model=self._mlabel,
                                           reason="client").inc()
        hit = [s for s in self.slots
               if s.active and s.request is not None
               and s.request.id in cancelled]
        for s in hit:
            with self._lock:
                cancelled.pop(s.request.id, None)
            self._finish(s, "cancelled")

    def _apply_deadlines(self) -> None:
        """Terminate requests whose deadline has passed: queued ones get
        an immediate terminal event (no slot was ever held), decoding
        ones finish through the normal slot path with whatever partial
        text they produced. With cost scheduling on, queued requests
        whose PREDICTED completion already exceeds their deadline are
        rejected early (stage="queued_predicted") instead of burning
        prefill on work that cannot land in time. Gated on the sticky
        _deadlines_armed flag so deadline-free serving skips the sweep
        entirely."""
        if not self._deadlines_armed:
            return
        now = time.perf_counter()
        expired: list[tuple[str, str]] = []  # (request id, stage)
        # predicted-completion rejection: with cost scheduling on, a
        # queued request whose PREDICTED first token already falls past
        # its deadline is rejected now instead of wasting prefill on it.
        # The prediction is the optimistic bound (prefill alone, as if
        # a slot were free this instant), so a request this rejects
        # could never have produced a token in time.
        tok_ms = (self._costmodel.prefill_token_ms()
                  if self._cost_sched_on() else None)
        with self._lock:
            still = []
            for req, out in self._pending:
                if req.deadline and now >= req.deadline:
                    self._deferred.pop(req.id, None)
                    if req.disagg is not None:
                        req.disagg.release()
                    out.put(StreamEvent(
                        done=True, finish_reason="deadline_exceeded",
                        error="deadline exceeded while queued"))
                    expired.append((req.id, "queued"))
                elif (req.deadline and tok_ms is not None
                      and req.disagg is None
                      and now + tok_ms * len(req.prompt_ids) / 1e3
                      >= req.deadline):
                    # (migrated resubmits are exempt: their prompt is
                    # already in pages — pricing a re-prefill against
                    # the deadline would reject work that needs none)
                    self._deferred.pop(req.id, None)
                    out.put(StreamEvent(
                        done=True, finish_reason="deadline_exceeded",
                        error="predicted completion exceeds deadline "
                              "(prefill alone overruns it)"))
                    expired.append((req.id, "queued_predicted"))
                else:
                    still.append((req, out))
            self._pending = still
        for rid, stage in expired:
            TRACER.event(rid, "done")
            TRACER.annotate(rid, "terminal", outcome="deadline_exceeded",
                            stage=stage)
            TRACER.finish(rid, status="deadline_exceeded")
            tm.ENGINE_REQUESTS.labels(model=self._mlabel,
                                      reason="deadline_exceeded").inc()
            tm.ENGINE_DEADLINE_EXCEEDED.labels(
                model=self._mlabel, stage=stage).inc()
        hit = [s for s in self.slots
               if s.active and s.request is not None
               and s.request.deadline and now >= s.request.deadline]
        for s in hit:
            tm.ENGINE_DEADLINE_EXCEEDED.labels(
                model=self._mlabel, stage=self._deadline_stage).inc()
            self._finish(s, "deadline_exceeded")

    # ------------------------------------------------------------- scheduler

    def _loop(self) -> None:
        # the profiler names a host line after the OS thread: the
        # scheduler's sched:* spans then sit on a line of this name
        name_os_thread("llm-engine")
        while True:
            if not self._has_work():
                # TRUE idle transition: step() will not run again until
                # new work arrives, so publish pending prefix-index
                # changes now — the final harvest of a wave would
                # otherwise never reach the gossiped prefix summary
                # and the member's digest would advertise the
                # PREVIOUS request's residency until the next
                # admission. (Unlocked peek: this thread is the only
                # mutator of slots/flights; a submit racing in merely
                # makes the refresh redundant, never wrong.)
                self._refresh_prefix_summary(force=True)
            with self._lock:
                while not self._stop and not self._has_work():
                    self._lock.wait(timeout=0.5)
                    if self._cancelled:
                        # idle-path purge: step() never runs while idle,
                        # so race-ahead cancels must age out here
                        n = self._purge_expired_cancels(
                            time.perf_counter())
                        if n:
                            tm.ENGINE_CANCELLATIONS.labels(
                                model=self._mlabel,
                                reason="expired").inc(n)
                if self._stop:
                    return
            try:
                self.step()
            except Exception as e:  # engine must survive; fail active slots
                # the traceback goes to the log: the per-request "error"
                # events carry only the message
                log.exception("engine step failed (%s)", self._mlabel)
                self._flights.clear()
                if hbm_ledger.looks_like_oom(e):
                    # device allocation failure: write the forensics
                    # file BEFORE failing the slots, so the autopsy
                    # captures the state that OOMed (best-effort — dump
                    # never raises)
                    hbm_ledger.dump_post_mortem(
                        self.state_dir, self._mlabel, e,
                        ledger=self._ledger,
                        pool_stats=(self._pool.stats()
                                    if self._pool is not None else None),
                        tier_stats=(self._tier.stats()
                                    if self._tier is not None else None),
                        weight_stats=(self._pager.stats()
                                      if self._pager is not None
                                      else None))
                self._fail_all(f"engine step error: {e!r}")

    def _has_work(self) -> bool:
        return (bool(self._pending) or bool(self._flights)
                or any(s.active for s in self.slots))

    def _fail_all(self, msg: str) -> None:
        for s in self.slots:
            if s.active and s.out is not None:
                if s.request is not None:
                    TRACER.event(s.request.id, "done")
                    # the step error (a real device failure or an
                    # injected fault — the message says which) becomes
                    # a span event on every trace it terminated; the
                    # trace commits BEFORE the terminal stream event so
                    # a consumer woken by it observes the final status
                    TRACER.annotate(s.request.id, "terminal",
                                    outcome="error", detail=msg)
                    TRACER.finish(s.request.id, status="error")
                    tm.ENGINE_REQUESTS.labels(model=self._mlabel,
                                              reason="error").inc()
                    tm.ENGINE_PREEMPTIONS.labels(model=self._mlabel).inc()
                s.out.put(StreamEvent(done=True, finish_reason="error",
                                      error=msg))
                self._release(s)

    # lint: region hot_path
    def step(self) -> None:
        """One scheduler iteration (ref: update_slots, grpc-server.cpp:1639).

        Async pipeline shape: every device dispatch is ENQUEUED without
        waiting for its results — JAX dispatch, the device work, and
        the host<->device transfer all pipeline — and results are
        harvested when their device arrays turn ready. Admission
        therefore never waits behind an in-flight prefill's download,
        and a deep burst's prefill groups overlap: TTFT for group N is
        the device compute of groups 1..N plus one transfer, not N
        serialized (compute + transfer) blocks. Flight latency is
        device-queue time, so the pipelining hides QUEUE time, and
        keeping the queue clean around latency-critical dispatches is
        what matters."""
        span = self._phases.span
        with span("sched:guards", root=True):
            self._guards()
        with span("sched:admit", root=True):
            self._admit()
        with span("sched:harvest", root=True):
            harvested = self._harvest()
        with span("sched:dispatch", root=True):
            dispatched = self._dispatch()
        with span("sched:gauges", root=True):
            self._update_gauges()
        if not (harvested or dispatched):
            with span("sched:wait", root=True):
                self._wait_for_event()

    def _guards(self) -> None:
        """Request lifecycle guards, ahead of admission."""
        self._apply_cancellations()
        self._apply_deadlines()

    def _refresh_prefix_summary(self, force: bool = False) -> None:
        """Recompute the gossiped prefix top-k when the refresh
        interval elapsed (or ``force``, on the idle transition).
        Registrations otherwise update only on admission waves, so the
        summary first syncs the index against the live slot tokens;
        the rehash itself is revision-gated, so an unchanged index
        costs only the (vectorized, usually early-out) sync diff."""
        nowp = time.monotonic()
        if not force and nowp - self._prefix_summary_t < knobs.float_(
                "LOCALAI_PREFIX_SUMMARY_S"):
            return
        if self._prefix_enabled:
            self._prefix_index.sync(
                (s.idx, s.cache_tokens) for s in self.slots)
        self._prefix_summary_t = nowp
        if self._prefix_index.revision == self._prefix_summary_rev:
            return
        self._prefix_summary_rev = self._prefix_index.revision
        self._prefix_summary = self._prefix_index.summary(
            knobs.int_("LOCALAI_DIGEST_TOPK"))

    def _update_gauges(self) -> None:
        """Scheduler-state gauges, refreshed once per iteration from
        values the scheduler already holds on the host (no device syncs;
        three lock-guarded stores per ms-scale iteration)."""
        m = self._mlabel
        busy = sum(1 for s in self.slots if s.active)
        tm.ENGINE_SLOTS_BUSY.labels(model=m).set(busy)
        tm.ENGINE_QUEUE_DEPTH.labels(model=m).set(len(self._pending))
        # timeline counter samples: same host scalars, recorded only
        # when a value changed (FLIGHT.sample), so the ring keeps its
        # step:/load:/sched: spans for minutes
        FLIGHT.sample("queue_depth", "scheduler", len(self._pending))
        FLIGHT.sample("slots_busy", "scheduler", busy)
        # phase self times the spans added since the last pass
        pub = self._phase_pub
        for ph, total in self._phases.totals.items():
            d = total - pub[ph]
            if d > 0.0:
                pub[ph] = total
                self._phase_ctr[ph].inc(d)
        used = sum(s.n_past for s in self.slots if s.active)
        tm.ENGINE_KV_UTIL.labels(model=m).set(
            used / float(self.n_slots * self.max_seq))
        # reusable-but-idle KV is real capacity the cross-slot cache can
        # serve: count resident prefix tokens across ALL slots (a free
        # slot's resident prefix is invisible to ENGINE_KV_UTIL)
        live_tokens = sum(len(s.cache_tokens) for s in self.slots)
        tm.ENGINE_KV_RESIDENT_PREFIX.labels(model=m).set(
            float(live_tokens))
        if self._paged:
            st = self._pool.stats()
            tm.ENGINE_KV_PAGES_IN_USE.labels(model=m).set(st.in_use)
            tm.ENGINE_KV_PAGES_SHARED.labels(model=m).set(st.shared)
            FLIGHT.sample("kv_pages_in_use", "scheduler", st.in_use)
            # HBM actually allocated per live (resident) token — the
            # series that shows paging tracking expected instead of
            # worst-case context (dense equivalent: max_seq / mean ctx
            # x this value)
            c = self.cache
            tok_bytes = 2 * c.k.dtype.itemsize * c.k.shape[0] \
                * c.k.shape[-1]
            if c.quantized:
                tok_bytes += 2 * 4 * c.k.shape[0]  # f32 row scales
            tm.ENGINE_KV_HBM_PER_TOKEN.labels(model=m).set(
                float(st.in_use * self._page * tok_bytes)
                / max(live_tokens, 1))
            # allocator outcome counters (fresh/shared/cow) sync from
            # the pool's host tallies; reclaimed/exhausted increment at
            # their call sites
            for outcome, v in self._pool.allocs.items():
                prev = self._alloc_sync.get(outcome, 0)
                if v > prev:
                    tm.ENGINE_KV_PAGE_ALLOC.labels(
                        model=m, outcome=outcome).inc(v - prev)
                    self._alloc_sync[outcome] = v
            if self._tier is not None:
                # tier residency gauges: host scalars the tier already
                # tallies (no device syncs, one store per tier)
                tp = self._tier.tier_pages(st.in_use)
                for tname, v in tp.items():
                    tm.ENGINE_KV_TIER_PAGES.labels(
                        model=m, tier=tname).set(v)
                FLIGHT.sample("kv_host_pages", "scheduler", tp["host"])
        if self._pager is not None:
            # weight-tier residency: host scalars the pager tallies
            # under its own lock (a promotion's hot count climbs with
            # the commit cursor)
            wp = self._pager.tier_pages()
            for tname, v in wp.items():
                tm.ENGINE_WEIGHT_PAGES.labels(model=m, tier=tname).set(v)
        if not any(s.state is SlotState.DECODE for s in self.slots):
            # decode-stall gaps are only meaningful while a slot
            # decodes; reset the clock when the decode set drains
            self._last_decode_adv = 0.0
        # fleet-digest prefix gossip: recompute the top-k summary every
        # LOCALAI_PREFIX_SUMMARY_S on the scheduler thread (the index
        # has no locking); host hashing only, published by atomic
        # tuple swap
        self._refresh_prefix_summary()
        now = time.monotonic()
        if now - self._tick_t >= 1.0:
            # the ~1 Hz tick: what a ms-scale scheduler iteration must
            # never pay — the ring-occupancy gauge, and the ledger
            # reconcile + device/host memory gauges (host dict math and
            # a memory_stats() host call)
            self._tick_t = now
            FLIGHT.update_gauge()
            if self._ledger is not None:
                self._ledger.reconcile()
                from ..utils import sysinfo

                sysinfo.update_memory_gauges()

    def _dispatch(self) -> bool:
        """Enqueue device work for the current slot states. Returns
        whether anything was enqueued.

        A prompt is admitted ONE way: the mixed step (_enqueue_mixed)
        carries the wave's prompt rows and one token for every row that
        decodes, whether or not any does — so an admission never stalls
        active streams for more than the step, and the rows it admits
        join the decode carry on the device. With no prompt waiting the
        pipelined k-step scans run."""
        prefilling = [s for s in self.slots if s.state is SlotState.PREFILL]
        if prefilling:
            ring = self._ring_axis()
            for s in prefilling:
                if (ring and s.n_past == 0 and s.request.soft_embeds is None
                        and s.n_prompt > self.prefill_buckets[-1]):
                    self._prefill_step(s)  # enqueue-only, no result
            # every step of the wave at once, chained on the carry: the
            # host's own tick (admit, the KV tier) never stands between
            # two chunks — but what has landed meanwhile is streamed
            # between them: a 20-chunk prompt's chain takes as long to
            # enqueue as to run, and held every stream's tokens for it
            did = False
            while prefilling:
                if did:
                    with self._phases.span("sched:harvest"):
                        self._harvest()
                if not self._enqueue_mixed(prefilling):
                    break
                did = True
                prefilling = [s for s in self.slots
                              if s.state is SlotState.PREFILL]
            return did
        decoding = self._decode_rows()
        return bool(decoding) and self._dispatch_decode(decoding)

    def _ring_axis(self) -> int:
        """The mesh's "seq" axis when a long prompt's first chunk can
        ride ring attention over it (_prefill_fn), else 0."""
        seq_ax = (self.mesh.shape.get("seq", 1)
                  if self.mesh is not None else 1)
        if (seq_ax > 1 and not self.spec.sliding_window
                and self.max_seq > self.prefill_buckets[-1]
                and self.prefill_buckets[-1] % seq_ax == 0):
            return seq_ax
        return 0

    def _decode_rows(self) -> list[_Slot]:
        """The rows that decode: DECODE slots, and PENDING_FIRST slots
        whose first token the device carry holds (sampled by a mixed
        step still in flight)."""
        return [s for s in self.slots
                if s.state is SlotState.DECODE
                or (s.state is SlotState.PENDING_FIRST
                    and self._dev_rows.get(s.idx) is s.request)]

    def _ahead(self) -> tuple[dict[int, int], dict[int, int]]:
        """How far the device is ahead of the host, per slot index:
        cache positions written and tokens sampled by the dispatches
        still in flight (a final's first token is sampled, its
        positions were counted at enqueue)."""
        pos: dict[int, int] = {}
        gen: dict[int, int] = {}
        for fl in self._flights:
            for s, req, p, g in fl.meta["ahead"]:
                if s.request is req:
                    pos[s.idx] = pos.get(s.idx, 0) + p
                    gen[s.idx] = gen.get(s.idx, 0) + g
        return pos, gen

    def _carry_for(self, decoding: list[_Slot]) -> Optional[bool]:
        """Whether a decode-advancing dispatch for these rows takes its
        tokens and positions from the device carry. True: the carry
        holds every row's next token (each was advanced, or admitted,
        by the dispatch that made it). False: the host's are current.
        None: neither — dispatches are in flight and some row's token
        is only on the device, or (grammar / logit-bias rows) the
        host's own state has to catch up first — wait for the harvest."""
        if not decoding:
            return False  # parked rows: the host knows where they stand
        held = self._dev_tokens is not None and all(
            self._dev_rows.get(s.idx) is s.request for s in decoding)
        if not self._flights:
            return held
        if held and not any(
                s.request.constraint or s.request.logit_bias
                for s in decoding):
            return True
        return None

    def _decode_inputs(self, advancing: list[_Slot], ahead: dict,
                       window: int) -> tuple:
        """The decode group as the host knows it: (tokens [S, 1],
        pos0 [S], active [S]), advancing rows at their next position
        and every other row parked where its K/V write does no harm."""
        S = self.n_slots
        tokens = np.zeros((S, 1), np.int32)
        pos0 = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        adv = {s.idx for s in advancing}
        for s in self.slots:
            if s.idx in adv:
                tokens[s.idx, 0] = (s.generated[-1] if s.generated
                                    else s.request.prompt_ids[-1])
                pos0[s.idx] = s.n_past + ahead.get(s.idx, 0)
                active[s.idx] = True
            elif s.active:
                # spec-advanced, first-token-pending or prefilling
                # slots ride inactive at their own tail; the window
                # covers it, so no trimming
                pos0[s.idx] = s.n_past
            else:
                # park inactive rows at their own tail: K/V write lands past
                # the valid prefix, preserving it for prefix reuse. In the
                # windowed path, a row whose prefix out-sizes the window
                # gets clamped: its reusable prefix is truncated to what
                # the window keeps. Paged rows never write back (their wb
                # pages are trash), so the resident prefix survives at
                # full length — only the in-dispatch position is clamped.
                if s.n_past >= window and not self._paged:
                    s.n_past = window - 1
                    s.cache_tokens = s.cache_tokens[: window - 1]
                pos0[s.idx] = min(s.n_past, window - 1, self.max_seq - 1)
        return tokens, pos0, active

    def _wait_for_event(self) -> None:
        """Nothing to enqueue and nothing ready: block until the oldest
        flight's arrays land, an ADMITTABLE request arrives (pending
        alone is not an event — with every slot busy a queued request
        can't be dispatched, and returning on it would hot-spin the
        scheduler for the length of every in-flight scan), or a cancel
        fires."""
        while True:
            with self._lock:
                if self._stop or self._cancelled:
                    return
                if self._pending and any(not s.active for s in self.slots):
                    return
            if not self._flights:
                return
            if self._flights[0].ready():
                return
            time.sleep(5e-4)

    def _harvest(self) -> bool:
        """Complete ready flights in FIFO order (device execution is
        serialized by the donated state buffers, so readiness is
        monotone along the queue)."""
        did = False
        while self._flights and self._flights[0].ready():
            fl = self._flights.popleft()
            # flight-recorder sample: enqueue→ready wall time, stamped
            # from host clocks AFTER ready() returned true — the sample
            # never blocks on the device (hot-path-sync stays clean)
            dur = time.perf_counter() - fl.t_enqueue
            tm.ENGINE_DEVICE_STEP.labels(
                model=self._mlabel, kind=fl.kind).observe(dur)
            rec = fl.meta.get("rec")
            pred = fl.meta.get("pred_ms")
            if pred is not None and rec is not None:
                # predicted-vs-measured rides the timeline span, so
                # Perfetto shows calibration error per dispatch
                rec = dict(rec, predicted_ms=round(pred, 3),
                           measured_ms=round(dur * 1e3, 3))
            FLIGHT.span("step:" + fl.kind, "device", fl.t_enqueue, dur,
                        rec)
            if self._costmodel is not None:
                # cost accounting + MFU sample + predictor calibration
                # against the flight's span — host dict math on
                # already-harvested scalars
                self._costmodel.on_harvest(
                    fl.kind, fl.meta.get("cost"), dur, predicted_ms=pred)
            with self._phases.span("sched:emit"):
                if fl.kind == "mixed":
                    self._complete_mixed(fl)
                else:
                    self._complete_decodek(fl)
                self._note_expert_stats(fl.kind, *fl.meta["experts"])
            did = True
        return did

    # lint: endregion hot_path

    # admission + prefix reuse (ref: grpc-server.cpp:1749-1900; extended
    # to a GLOBAL prefix cache: radix index over every slot's resident
    # prefix + on-device cross-slot row copies)
    def _admit(self) -> None:
        if self._pager is not None:
            # weight-pager hook: work arriving while a demotion's D2H
            # stream is aloft flips its abort flag — never blocks
            self._pager.tick()
        if self._tier is not None:
            # tier policy tick rides the admission pass: harvest landed
            # spill/fetch DMAs, apply background IO results, expire
            # stale stages, run the watermark demotion scan. Entirely
            # non-blocking (TransferWindow.reap + is_ready polling).
            self._tier.tick()
        with self._lock:
            pending, self._pending = self._pending, []
        if not pending:
            return
        if self._pager is not None and not self._pager.poll_admission():
            # weights not device-resident: the poll kicked the warm->hot
            # promotion (layer-streamed, on its own thread); requeue the
            # wave untouched and retry next pass. The brief sleep keeps
            # this retry loop from busy-spinning the scheduler while the
            # stream lands — promotion completion notifies _lock.
            with self._lock:
                self._pending[:0] = pending
            time.sleep(0.002)
            return
        if self._prefix_enabled:
            # lazy re-register: decode appends / window clamps since the
            # last wave are diffed in (extension is the common case)
            self._prefix_index.sync(
                (s.idx, s.cache_tokens) for s in self.slots)
        # prompts admitted but whose prefill has NOT yet dispatched:
        # their KV is uncommitted, so the index cannot serve them yet —
        # same-wave sharers defer one iteration behind them instead
        # (one prefix prefill + N copies serves the whole wave)
        forming = [s.request.prompt_ids for s in self.slots
                   if s.state is SlotState.PREFILL
                   and s.request is not None
                   and s.request.soft_embeds is None]
        requeue: list[tuple[GenRequest, queue.SimpleQueue]] = []
        now = time.perf_counter()
        for req, out in pending:
            with self._lock:
                cancelled = req.id in self._cancelled
                if cancelled:  # cancel raced ahead
                    del self._cancelled[req.id]
                    self._deferred.pop(req.id, None)
                    if req.disagg is not None:
                        req.disagg.release()
                    out.put(StreamEvent(done=True,
                                        finish_reason="cancelled"))
            if cancelled:
                # this terminal previously bypassed the trace recorder
                # entirely, stranding the request's trace in the active
                # table until cap eviction — every terminal must land a
                # complete entry in the ring
                TRACER.event(req.id, "done")
                TRACER.annotate(req.id, "terminal", outcome="cancelled",
                                stage="admit")
                TRACER.finish(req.id, status="cancelled")
                tm.ENGINE_REQUESTS.labels(model=self._mlabel,
                                          reason="cancelled").inc()
                tm.ENGINE_CANCELLATIONS.labels(model=self._mlabel,
                                               reason="client").inc()
                continue
            if req.disagg is None and self._defer_for_prefix(
                    req, forming, now):
                requeue.append((req, out))
                continue
            if (self._tier is not None and req.soft_embeds is None
                    and req.disagg is None
                    and self._tier.plan(req, now)):
                # the session's KV is in the cold tier and its disk
                # load is inside the deadline window: hold admission
                # (overlapped with queue wait) instead of re-prefilling
                requeue.append((req, out))
                continue
            slot = self._pick_slot(req)
            if slot is None:
                requeue.append((req, out))  # no free slot
                continue
            if self._paged and not self._page_headroom(req):
                requeue.append((req, out))  # pool full of ACTIVE state:
                # wait for a release instead of admit-then-kill thrash
                continue
            self._deferred.pop(req.id, None)
            if req.disagg is not None and self._migrator is not None:
                # migrated resubmit: stage the prefill engine's pages
                # into this pool and adopt them by reference — the slot
                # wakes in DECODE with the whole prompt resident and
                # re-prefills ZERO tokens. Spill the slot's resident
                # prefix first (same demote-on-reuse as the tier path:
                # the gather lands before any overwrite in device
                # order). On staging failure (fault injection, pool
                # pressure) the handoff is dropped and the request
                # falls through to _assign below — an ordinary
                # re-prefill, correct just slower.
                if self._tier is not None and req.soft_embeds is None:
                    self._tier.capture(slot, req)
                if self._migrator.assign_migrated(slot, req, out):
                    continue
                req.disagg = None
            if self._tier is not None and req.soft_embeds is None:
                # demote-on-reuse: spill the resident prefix this
                # assignment is about to discard (gather enqueued
                # before any overwrite — device-order keeps it
                # coherent), THEN adopt a staged promotion: the slot's
                # resident prefix becomes the fetched session (share by
                # reference), so _assign's ordinary prefix-reuse path
                # skips those tokens — a prefetch hit re-prefills zero
                self._tier.capture(slot, req)
                self._tier.adopt(slot, req)
            self._assign(slot, req, out)
            if req.soft_embeds is None:
                forming.append(req.prompt_ids)
        if requeue:
            with self._lock:  # preserve arrival order over new arrivals
                self._pending[:0] = requeue

    def _defer_for_prefix(self, req: GenRequest, forming: list,
                          now: float) -> bool:
        """Same-wave prefix grouping: when requests in one admission
        wave share a >= _prefix_defer_min-token prefix the index cannot
        yet serve, the FIRST prefills it and the rest defer until that
        prefill's KV commits (its dispatch extends the donor's
        cache_tokens), then admit as copy + tail-prefill. Bounded by a
        deadline so a stalled/cancelled donor can never strand its
        sharers (they admit normally and re-prefill)."""
        if not self._prefix_enabled or req.soft_embeds is not None:
            return False
        cap = min(len(req.prompt_ids) - 1, self.max_seq - 1)
        state = self._deferred.get(req.id)
        if state is not None:
            deadline, want = state
            if now > deadline:
                self._deferred.pop(req.id, None)
                return False  # donor stalled: admit normally
            have, _ = self._prefix_index.match(req.prompt_ids)
            if min(have, cap) >= want:
                self._deferred.pop(req.id, None)
                return False  # shared prefix committed: admit w/ copy
            if not any(_common_prefix(p, req.prompt_ids) >= want
                       for p in forming):
                self._deferred.pop(req.id, None)
                return False  # donor vanished: admit normally
            return True
        share = max((_common_prefix(p, req.prompt_ids)
                     for p in forming), default=0)
        share = min(share, cap)
        have, _ = self._prefix_index.match(req.prompt_ids)
        have = min(have, cap)
        if share >= have + self._prefix_defer_min:
            self._deferred[req.id] = (now + 0.25, share)
            tm.ENGINE_PREFIX_EVENTS.labels(
                model=self._mlabel, event="deferred").inc()
            return True
        return False

    def _reset_columns(self, group: list[_Slot], pad_to: int) -> dict:
        """Per-slot sampler-reset columns for a mixed step's prompt
        group. The reset rides the admission dispatch (a separate
        reset_batch dispatch costs one extra dispatch per admission
        wave, straight on burst TTFT). Members occupy the leading rows;
        the other rows pad with neutral values — their scatter targets
        the out-of-bounds sentinel slot, so the writes are dropped."""
        W = self.sampling.window
        cols: dict[str, list] = {k: [] for k in (
            "temperature", "top_k", "top_p", "min_p",
            "repeat_penalty", "freq_penalty", "presence_penalty",
            "repeat_last_n", "seeds", "has_seed",
            "typical_p", "mirostat", "mirostat_tau", "mirostat_eta")}
        pad = _PadReq()
        for s in group + [None] * (pad_to - len(group)):
            r = s.request if s is not None else pad
            assert r is not None
            cols["temperature"].append(r.temperature)
            cols["top_k"].append(r.top_k)
            cols["top_p"].append(r.top_p)
            cols["min_p"].append(r.min_p)
            cols["repeat_penalty"].append(r.repeat_penalty)
            cols["freq_penalty"].append(r.frequency_penalty)
            cols["presence_penalty"].append(r.presence_penalty)
            cols["repeat_last_n"].append(
                min(r.repeat_last_n if r.repeat_last_n > 0 else 64, W))
            # wrap to the int32 bit pattern: 64-bit seeds are legal in the
            # API and np.asarray(np.int32) raises on >= 2**31
            seed = (r.seed if r.seed is not None else 0) & 0xFFFFFFFF
            cols["seeds"].append(seed - (1 << 32) if seed >= (1 << 31)
                                 else seed)
            cols["has_seed"].append(r.seed is not None)
            cols["typical_p"].append(r.typical_p)
            cols["mirostat"].append(r.mirostat)
            cols["mirostat_tau"].append(r.mirostat_tau)
            cols["mirostat_eta"].append(r.mirostat_eta)
        return {
            "temperature": np.asarray(cols["temperature"], np.float32),
            "top_k": np.asarray(cols["top_k"], np.int32),
            "top_p": np.asarray(cols["top_p"], np.float32),
            "min_p": np.asarray(cols["min_p"], np.float32),
            "repeat_penalty": np.asarray(cols["repeat_penalty"], np.float32),
            "freq_penalty": np.asarray(cols["freq_penalty"], np.float32),
            "presence_penalty": np.asarray(
                cols["presence_penalty"], np.float32),
            "repeat_last_n": np.asarray(cols["repeat_last_n"], np.int32),
            "seeds": np.asarray(cols["seeds"], np.int32),
            "has_seed": np.asarray(cols["has_seed"], bool),
            "typical_p": np.asarray(cols["typical_p"], np.float32),
            "mirostat": np.asarray(cols["mirostat"], np.int32),
            "mirostat_tau": np.asarray(cols["mirostat_tau"], np.float32),
            "mirostat_eta": np.asarray(cols["mirostat_eta"], np.float32),
        }

    def _pick_slot(self, req: GenRequest) -> Optional[_Slot]:
        free = [s for s in self.slots if not s.active]
        if not free:
            return None
        if not self._prefix_enabled:
            return max(free, key=lambda s: _common_prefix(
                s.cache_tokens, req.prompt_ids))
        # value-destroyed placement: admitting onto a slot overwrites
        # its resident prefix beyond the overlap, so the right victim
        # is the slot whose UNSHARED tail is worth the least (reuse
        # value scaled by the fraction overwritten) — NOT the
        # max-overlap slot. Scoring by overlap alone steers every new
        # conversation that shares a trivial opening with a hot
        # resident (chat-template header, "You are a ..." boilerplate)
        # onto that resident and evicts it while a near-worthless slot
        # sits free; and _maybe_prefix_copy serves the same overlap
        # from ANY donor row, so in-place placement saves only the
        # copy, never the prefill. Ties (e.g. two empty slots) prefer
        # the larger overlap: in-place reuse skips the donor copy.
        now = time.monotonic()

        def cost(s: _Slot) -> tuple:
            overlap = _common_prefix(s.cache_tokens, req.prompt_ids)
            n = self._prefix_index.registered_len(s.idx)
            destroyed = 0.0
            if n:
                keep = min(overlap, n)
                destroyed = (self._prefix_index.value(s.idx, now)
                             * (n - keep) / n)
            return (destroyed, -overlap)

        return min(free, key=cost)

    def _maybe_prefix_copy(self, slot: _Slot, req: GenRequest,
                           common: int) -> tuple[int, int]:
        """Cross-slot prefix reuse: when another slot's committed
        resident prefix beats this slot's by >= _prefix_min_copy
        tokens, enqueue an on-device row-to-row KV copy (donor row ->
        this row) and start prefill from the copied length. The donor
        may be ACTIVE — its committed prefix [0, n_past) is immutable
        (decode/prefill writes land at or beyond n_past, and device
        execution is serialized behind everything already enqueued) —
        so an admitted request reuses the best prefix held by ANY
        slot, not just its own. Returns (new common, tokens gained)."""
        if not self._prefix_enabled:
            return common, 0
        m = self._mlabel
        best, donors = self._prefix_index.match(req.prompt_ids)
        best = min(best, len(req.prompt_ids) - 1, self.max_seq - 1)
        if best >= common + self._prefix_min_copy:
            donors = donors - {slot.idx}
        else:
            donors = set()
        if not donors:
            tm.ENGINE_PREFIX_EVENTS.labels(
                model=m,
                event="hit_resident" if common > 0 else "miss").inc()
            return common, 0
        now = time.monotonic()
        # most-valuable donor: longest registration is implied (all
        # cover >= best); prefer the most recently useful row
        donor = max(donors,
                    key=lambda i: self._prefix_index.value(i, now))
        if self._paged:
            # zero-copy share: the donor's FULL pages covering [0, best)
            # transfer by reference (refcount bump — no device work);
            # only the sub-page tail is row-copied into a fresh private
            # page, so whole-page prefixes admit with ZERO copy
            # dispatches — this supersedes most dense kvcopy traffic.
            P = self._page
            full = best // P
            self._pool.share(slot.idx, donor, full)
            tail = best - full * P
            if tail > 0:
                src_pg = self._pool.table(donor)[full]
                if self._pool_ensure(slot, best):  # the tail page
                    dst_pg = self._pool.table(slot.idx)[full]
                    # whole-page copy (rows past `tail` are rewritten
                    # by prefill or causally invisible): ONE jit variant
                    self._run("kvcopy", {"src": src_pg, "dst": dst_pg,
                                         "n": P})
                    self.metrics.prefix_copies += 1
                    tm.ENGINE_PREFIX_COPIES.labels(model=m).inc()
                else:
                    best = full * P  # no page for the tail: share-only
        else:
            # static-shape length bucket: copying past `best` is
            # harmless (dst positions beyond its valid prefix are
            # rewritten by prefill or causally invisible) and keeps the
            # jit set tiny
            self._run("kvcopy", {"src": donor, "dst": slot.idx,
                                 "n": window_bucket(
                                     best, self.max_seq)})
            self.metrics.prefix_copies += 1
            tm.ENGINE_PREFIX_COPIES.labels(model=m).inc()
        self._prefix_index.touch(donor, now)
        gain = max(0, best - common)
        tm.ENGINE_PREFIX_EVENTS.labels(model=m, event="hit_copy").inc()
        slot.cache_tokens = list(req.prompt_ids[:best])
        slot.n_past = best
        return best, gain

    # ------------------------------------------------- on-disk prompt cache

    def _try_load_prompt_cache(self, slot: _Slot, req: GenRequest) -> str:
        """Restore a saved prompt's KV rows into the slot when the file's
        token prefix beats the slot's resident prefix (ref: llama.cpp
        prompt cache restore via PromptCachePath). Every outcome is
        counted (engine_prompt_cache_restores_total{result=...}) and
        traced, so a corrupt on-disk cache silently re-prefilling every
        request is visible instead of invisible. Returns the result
        string ("unset" when the request carries no cache path)."""
        import os

        path = req.prompt_cache_path
        if not path:
            return "unset"  # the common no-cache case: not counted

        def done(result: str) -> str:
            tm.ENGINE_PROMPT_CACHE_RESTORES.labels(
                model=self._mlabel, result=result).inc()
            TRACER.event(req.id, f"prompt_cache:{result}")
            return result

        if self.channel is not None:
            # multihost: a row restore would need the KV payload
            # broadcast to every follower. CROSS-SLOT copies still work
            # (pure device ops); only the disk path stays off.
            return done("skipped_multihost")
        if self.draft is not None:
            # restored rows would leave the draft cache stale
            return done("skipped_draft")
        if not os.path.exists(path):
            return done("no_file")
        try:
            from .kv_tier import read_cache_file

            data = read_cache_file(path)
            cached_tokens = [int(t) for t in data["tokens"]]
            L, _, _, F = self.cache.k.shape
            k_all, v_all = data["k"], data["v"]
            # a cache written by a different model/dtype config must be
            # ignored, not crash the scheduler or corrupt KV
            if (k_all.shape[0] != L or k_all.shape[2] != F
                    or v_all.shape != k_all.shape):
                return done("shape_mismatch")
            if self.cache.quantized != (k_all.dtype == np.int8):
                return done("dtype_mismatch")
            if self.cache.quantized and "k_scale" not in data:
                return done("dtype_mismatch")
            common = _common_prefix(cached_tokens, req.prompt_ids)
            if common <= _common_prefix(slot.cache_tokens, req.prompt_ids):
                return done("stale")
            n = min(common, len(cached_tokens), self.max_seq - 1,
                    k_all.shape[1])
            if self._paged:
                # replace the slot's rows wholesale: fresh private
                # pages, file rows scattered page by page (the on-disk
                # format stays slot-contiguous [L, n, F], so caches are
                # portable across paged and dense engines)
                self._pool.drop(slot.idx)
                slot.cache_tokens = []
                slot.n_past = 0
                if not self._pool_ensure(slot, n):
                    return done("error")
                P = self._page
                table = self._pool.table(slot.idx)
                npg = len(table)
                pad = npg * P - n

                def paged_rows(a):
                    a = np.asarray(a[:, :n])
                    if pad:
                        a = np.concatenate([a, np.zeros(
                            (a.shape[0], pad) + a.shape[2:], a.dtype)],
                            axis=1)
                    return a.reshape((a.shape[0], npg, P) + a.shape[2:])

                tbl = jnp.asarray(np.asarray(table, np.int32))
                ck = self.cache.k.at[:, tbl].set(
                    jnp.asarray(paged_rows(k_all)).astype(
                        self.cache.k.dtype))
                cv = self.cache.v.at[:, tbl].set(
                    jnp.asarray(paged_rows(v_all)).astype(
                        self.cache.v.dtype))
                ks, vs = self.cache.k_scale, self.cache.v_scale
                if self.cache.quantized:
                    ks = ks.at[:, tbl].set(
                        jnp.asarray(paged_rows(data["k_scale"])))
                    vs = vs.at[:, tbl].set(
                        jnp.asarray(paged_rows(data["v_scale"])))
            else:
                ck = self.cache.k.at[:, slot.idx, :n].set(
                    jnp.asarray(k_all[:, :n]).astype(self.cache.k.dtype))
                cv = self.cache.v.at[:, slot.idx, :n].set(
                    jnp.asarray(v_all[:, :n]).astype(self.cache.v.dtype))
                ks, vs = self.cache.k_scale, self.cache.v_scale
                if self.cache.quantized:
                    ks = ks.at[:, slot.idx, :n].set(
                        jnp.asarray(data["k_scale"][:, :n]))
                    vs = vs.at[:, slot.idx, :n].set(
                        jnp.asarray(data["v_scale"][:, :n]))
        except Exception as e:
            # unreadable/incompatible cache: prefill normally — but
            # say so, a corrupt file re-prefilling forever is a real
            # cost someone is paying
            log.warning("prompt cache %s unusable: %r", path, e)
            return done("error")
        self.cache = KVCache(k=ck, v=cv, k_scale=ks, v_scale=vs)
        slot.cache_tokens = cached_tokens[:n]
        slot.n_past = n
        slot.cache_loaded = (path, n)
        if self._prefix_enabled:
            self._prefix_index.set_tokens(slot.idx, slot.cache_tokens)
        return done("restored")

    def _maybe_save_prompt_cache(self, slot: _Slot) -> None:
        """Persist the slot's prefix rows (ref: llama.cpp prompt cache
        save; PromptCacheAll includes the generation)."""
        req = slot.request
        if req is None or not req.prompt_cache_path or req.prompt_cache_ro \
                or self.channel is not None:
            return
        n = slot.n_past if req.prompt_cache_all else min(
            slot.n_past, slot.n_prompt)
        if n <= 0:
            return
        if slot.cache_loaded == (req.prompt_cache_path, n):
            return  # the file already holds exactly this prefix
        # snapshot the (immutable) device arrays now; the transfer +
        # write happens OFF the scheduler thread so a finishing request
        # never stalls other slots' decoding
        if self._paged:
            # gather the slot's page run into contiguous rows — the
            # on-disk format stays [L, n, F] either way
            P = self._page
            tbl = jnp.asarray(np.asarray(
                self._pool.table(slot.idx)[: -(-n // P)], np.int32))
            L = self.cache.k.shape[0]
            F = self.cache.k.shape[-1]
            k_rows = self.cache.k[:, tbl].reshape(L, -1, F)[:, :n]
            v_rows = self.cache.v[:, tbl].reshape(L, -1, F)[:, :n]
            scales = ((self.cache.k_scale[:, tbl].reshape(L, -1)[:, :n],
                       self.cache.v_scale[:, tbl].reshape(L, -1)[:, :n])
                      if self.cache.quantized else None)
        else:
            k_rows = self.cache.k[:, slot.idx, :n]
            v_rows = self.cache.v[:, slot.idx, :n]
            scales = ((self.cache.k_scale[:, slot.idx, :n],
                       self.cache.v_scale[:, slot.idx, :n])
                      if self.cache.quantized else None)
        tokens = np.asarray(slot.cache_tokens[:n], np.int32)
        path = req.prompt_cache_path

        def persist():
            # the writer is the cold tier's format code (kv_tier.py):
            # np.asarray here blocks on the gathered rows OFF the
            # scheduler thread, then the same atomic savez the tier's
            # background demotion uses
            from .kv_tier import write_cache_file

            try:
                write_cache_file(path, tokens, k_rows, v_rows, scales)
            except OSError:
                pass  # cache persistence is best-effort

        threading.Thread(target=persist, daemon=True,
                         name="prompt-cache-save").start()

    def _assign(self, slot: _Slot, req: GenRequest,
                out: queue.SimpleQueue) -> None:
        now = time.perf_counter()
        TRACER.event(req.id, "admit", t=now, model=self._mlabel)
        if req.t_submit:
            wait = max(0.0, now - req.t_submit)
            tm.ENGINE_QUEUE_WAIT.labels(model=self._mlabel).observe(wait)
            with self._lock:
                self._queue_waits.append(wait)
        slot.cache_loaded = None
        copy_gain = disk_gain = 0
        if req.soft_embeds is not None:
            common = 0  # image-conditioned K/V: no token-id prefix reuse
        else:
            common = _common_prefix(slot.cache_tokens, req.prompt_ids)
            common, copy_gain = self._maybe_prefix_copy(slot, req, common)
            # the on-disk cache can still beat a live resident/copied
            # prefix (it persists across restarts); it checks the
            # slot's CURRENT tokens, so it only applies when longer
            before_disk = common
            if self._try_load_prompt_cache(slot, req) == "restored":
                common = _common_prefix(slot.cache_tokens, req.prompt_ids)
                disk_gain = common - before_disk
            if common == len(req.prompt_ids):
                common -= 1  # reprocess last token for logits (ref :1882-1890)
        if self._paged:
            # the write frontier (position `common`) must be privately
            # writable: a SHARED boundary page (this slot donated its
            # full pages, or the relogit -1 stepped back into a shared
            # page) is copy-on-write swapped for a private copy before
            # any prefill write can land in it
            cow = self._pool.prepare_write(slot.idx, common)
            if cow is not None:
                self._run("kvcopy", {"src": cow[0], "dst": cow[1],
                                     "n": self._page})
        slot.request = req
        slot.out = out
        slot.state = SlotState.PREFILL
        slot.n_past = common
        slot.n_prompt = len(req.prompt_ids)
        slot.cache_tokens = list(req.prompt_ids[:common])
        slot.n_reused = common
        if self._prefix_enabled:
            # eager re-register: the row now holds (only) this truncated
            # prefix — later admissions in the SAME wave must not match
            # the stale longer registration
            self._prefix_index.set_tokens(slot.idx, slot.cache_tokens)
            self._prefix_index.touch(slot.idx)
            self._prefix_index.set_chain(
                slot.idx, req.prefix_chain, len(req.prompt_ids))
        if common > 0:
            # attribute reuse by source; clamp so the three sources sum
            # exactly to `common` even across the relogit -1 adjustment
            disk_gain = min(disk_gain, common)
            copy_gain = min(copy_gain, common - disk_gain)
            resident = common - disk_gain - copy_gain
            m = self._mlabel
            self.metrics.prefix_reused_tokens += common
            for src_name, val in (("resident", resident),
                                  ("copy", copy_gain),
                                  ("disk", disk_gain)):
                if val > 0:
                    tm.ENGINE_PREFIX_REUSED_TOKENS.labels(
                        model=m, source=src_name).inc(val)
        slot.generated = []
        slot.decoder = StreamDecoder(self.tokenizer)
        slot.pending_text = ""
        slot.t_start = now
        slot.t_first = 0.0
        slot.t_prefill_ms = 0.0
        slot.t_prefill_enq_ms = 0.0
        slot.t_prefill_t0 = 0.0
        slot.t_decode_ms = 0.0
        slot.constraint_state = (
            req.constraint.initial_state() if req.constraint else None
        )

    def _bucket(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def _prefill_step(self, slot: _Slot) -> None:
        """The first chunk of a long prompt on a seq-sharded mesh, as
        ring attention over the "seq" axis (_prefill_fn): the chunk
        attends only to itself at pos0 == 0, pad included — there is
        none, the chunk is a whole largest bucket. Enqueue-only; the
        rest of the prompt rides the mixed step."""
        req = slot.request
        assert req is not None
        t0 = time.perf_counter()
        bucket = self.prefill_buckets[-1]
        chunk = req.prompt_ids[:bucket]
        window = self._route.window(bucket, "prefill")
        self._run("prefill", {
            "toks": np.asarray([chunk], np.int32),
            "pos0": np.zeros((1,), np.int32),
            "slot_ids": np.asarray([slot.idx], np.int32),
            "window": window,
        })
        n = len(chunk)
        self._note_dispatch_tokens("prefill", n, bucket, [(0, n)])
        slot.n_past += n
        slot.cache_tokens.extend(chunk)
        if slot.t_prefill_t0 == 0.0:
            slot.t_prefill_t0 = t0
        # _run only ENQUEUES: device time is attributed at harvest of
        # the covering flight (_complete_mixed); the host-side enqueue
        # cost is tracked as its own phase component
        slot.t_prefill_enq_ms += (time.perf_counter() - t0) * 1e3
        tm.ENGINE_MIXED_DISPATCH.labels(
            model=self._mlabel, composition="prefill_only").inc()

    @property
    def _half_k(self) -> int:
        """A half-length scan in warmup()'s decode ks: the largest power
        of two <= decode_steps // 2 (floor 4), a rung for _latency_k and
        the ITL budget to snap to when decode_steps is no power of two."""
        h = max(self.decode_steps // 2, 4)
        while h & (h - 1):
            h &= h - 1
        return h

    @property
    def _warm_ks(self) -> set:
        """Every scan length warmup() precompiles — the ONLY values any
        runtime k decision may produce (a cold k jits ~13 s mid-request
        at 8B scale). The {2,4,8,16} rungs give _latency_k a dense
        ladder to snap to across model scales."""
        return {k for k in (1, 2, 4, 8, 16) if k <= self.decode_steps} | {
            self._half_k, self.decode_steps}

    # the shortest scan worth dispatching: device work per scan should
    # cover the host's dispatch round trip or the device idles between
    # scans. The value was calibrated on hardware that is gone
    # (ROADMAP Queue 1: re-derive from a chip trace).
    _LAT_TARGET_MS = 90.0

    def _latency_k(self, lat_mode: bool = False) -> int:
        """Scan length for open-capacity periods, from the
        harvest-measured per-step EWMA.

        Balanced (lat_mode False): the smallest WARMED k whose device
        time still covers _LAT_TARGET_MS — an unpredicted arrival
        waits behind short scans, and a model whose steps are short
        keeps long scans and its drain throughput.

        Latency mode (lat_mode True: latency_target_ms set, open
        capacity, not a drain tail): the LARGEST warmed k that fits the
        budget — combined with the depth-1 gate in the scan decision,
        total queued decode work stays under the budget, so a steady
        arrival's prefill does not queue behind full-length scans.
        Open-capacity decode deliberately stops covering the dispatch
        round trip: that is the knob. (Not measured on today's code.)"""
        if self._step_ms <= 0.0:
            return self.decode_steps  # no samples yet: don't throttle
        if lat_mode and self.latency_target_ms is not None:
            best = 0
            for k in sorted(self._warm_ks):
                if k > 1 and k * self._step_ms <= self.latency_target_ms:
                    best = k
            return best or min(k for k in self._warm_ks if k > 1)
        for k in sorted(self._warm_ks):
            if k > 1 and k * self._step_ms >= self._LAT_TARGET_MS:
                return k
        return self.decode_steps

    # lint: region hot_path
    def _enqueue_mixed(self, prefilling: list[_Slot]) -> bool:
        """Enqueue ONE mixed step (_mixed_fn) for the waiting prompts
        and every row that decodes; False when it has to wait for a
        harvest first (_carry_for).

        The prompt group is sized to what it carries (_mixed_shape):
        rows padded up the bucket's row ladder with sentinel rows
        pointing at the out-of-bounds slot id ``n_slots`` — JAX drops
        out-of-bounds scatter updates and clamps out-of-bounds gathers,
        so a pad row is pure discarded compute that never touches
        engine state; rows whose remainder exceeds the bucket take a
        bucket-wide non-final chunk and continue next dispatch, as do
        rows beyond the bucket's row cap. Decode rows ride every step,
        one token each, so their inter-token gap is bounded by one
        step's device work (cost scheduling bounds it in ms).

        Prefill bookkeeping (n_past/cache_tokens) advances HERE: device
        execution order equals enqueue order, so anything enqueued
        later (kvcopy from a same-wave prefix sharer included) sees
        this chunk committed. Decode rows advance at harvest
        (_complete_mixed), exactly like the decode scan path; the rows
        whose final chunk rode join the device carry at once, so the
        next scan is enqueued behind this step without waiting for it."""
        t0 = time.perf_counter()
        S = self.n_slots
        W = self.sampling.window
        decoding = self._decode_rows()
        carry = self._carry_for(decoding)
        if carry is None:
            return False
        ahead, _ = self._ahead()
        chunk = self._step_buckets[-1]
        if self._paged:
            # page capacity up front: decode rows append one token,
            # prompt rows at most one bucket-wide chunk
            for s in list(decoding):
                if not self._pool_ensure(
                        s, s.n_past + ahead.get(s.idx, 0) + 1):
                    self._finish(s, "length")
                    decoding.remove(s)
            for s in list(prefilling):
                rem = s.n_prompt - s.n_past
                if not self._pool_ensure(
                        s, s.n_past + min(rem, chunk)):
                    self._finish(s, "length")
                    prefilling.remove(s)
            if not prefilling:
                return True  # composition changed: next pass re-plans
        R, bucket = self._mixed_shape(
            [s.n_prompt - s.n_past for s in prefilling],
            self._itl_budget_ms() if decoding else 0.0,
            lambda b: self._mixed_window(prefilling, decoding, ahead, b))
        riding = prefilling[:R]
        window = self._mixed_window(riding, decoding, ahead, bucket)
        if carry:
            # rows parked on the device keep the position the chain's
            # earlier windows covered: a window never shrinks under them
            window = max(window, self._dev_window)
        toks = np.zeros((R, bucket), np.int32)
        pos0 = np.zeros((R,), np.int32)
        slot_ids = np.full((R,), S, np.int32)  # OOB sentinel
        n_chunk = np.ones((R,), np.int32)
        final = np.zeros((R,), bool)
        tails = np.zeros((R, W), np.int32)
        tail_lens = np.zeros((R,), np.int32)
        finals: list[_Slot] = []
        chunk_tokens = 0
        for r, s in enumerate(riding):
            req = s.request
            rem = s.n_prompt - s.n_past
            chunk = req.prompt_ids[s.n_past: s.n_past + min(rem, bucket)]
            toks[r, : len(chunk)] = chunk
            pos0[r] = s.n_past
            slot_ids[r] = s.idx
            n_chunk[r] = len(chunk)
            chunk_tokens += len(chunk)
            if rem <= bucket:  # final chunk: reset+seed+sample ride
                finals.append(s)
                final[r] = True
                tail = req.prompt_ids[-W:]
                tails[r, : len(tail)] = tail
                tail_lens[r] = len(tail)
        dmask = self._constraint_mask_rows(decoding)
        pmask = self._constraint_mask_rows(riding)
        masks = None
        if dmask is not None or pmask is not None:
            masks = np.ones((S + R, self.spec.vocab_size), bool)
            if dmask is not None:
                masks[[s.idx for s in decoding]] = dmask
            if pmask is not None:
                masks[S: S + len(riding)] = pmask
        dtoks, dpos, active = self._decode_inputs(decoding, ahead, window)
        payload = {
            "toks": toks, "pos0": pos0, "slot_ids": slot_ids,
            "n_chunk": n_chunk, "final": final, "tails": tails,
            "tail_lens": tail_lens, "masks": masks,
            # (a non-final row's reset drops with its sentinel id)
            "reset": self._reset_columns(riding, R),
            "soft": self._soft_payload(riding, pos0, bucket),
            "window": window, "carry": carry,
            "dtoks": dtoks, "dpos": dpos, "active": active,
        }
        if self._paged:
            spans: list = [(i, None) for i in range(S)]
            for s in decoding:
                at = int(dpos[s.idx])
                spans[s.idx] = (s.idx, (at, at + 1))
            spans += [(s.idx, (s.n_past, s.n_past + int(n_chunk[r])))
                      for r, s in enumerate(riding)]
            spans += [(None, None)] * (R - len(riding))
            payload["pt"] = self._phys_rows([si for si, _ in spans],
                                            window)
            payload["wb"] = self._wb_rows(spans, window)
        toks_out = self._run("mixed", payload)
        toks_out.copy_to_host_async()
        experts = self._take_expert_stats()
        t_disp = time.perf_counter()
        enq_ms = (t_disp - t0) * 1e3
        self._dev_rows = {s.idx: s.request for s in decoding + finals}
        self._dev_window = window
        # a decode row reads its whole cache; a chunk its causal sum
        context = [(int(dpos[s.idx]), 1) for s in decoding]
        for r, s in enumerate(riding):
            chunk_len = int(n_chunk[r])
            context.append((s.n_past, chunk_len))
            s.cache_tokens.extend(
                s.request.prompt_ids[s.n_past: s.n_past + chunk_len])
            s.n_past += chunk_len
            if s.t_prefill_t0 == 0.0:
                s.t_prefill_t0 = t0
            s.t_prefill_enq_ms += enq_ms
        for s in finals:
            s.state = SlotState.PENDING_FIRST
            TRACER.event(s.request.id, "prefill_dispatch", t=t_disp)
        tm.ENGINE_MIXED_DISPATCH.labels(
            model=self._mlabel,
            composition="mixed" if decoding else "prefill_only").inc()
        self._note_ragged_rows("decode", len(decoding))
        self._note_ragged_rows("final", len(finals))
        self._note_ragged_rows("prefill", len(riding) - len(finals))
        # a decode row is one real token; the shape is both groups'
        self._note_dispatch_tokens("mixed", len(decoding) + chunk_tokens,
                                   S + R * bucket, context)
        if decoding:
            self._note_decode_advance(t_disp)
        ckey = costmodel.dispatch_key("mixed", payload)
        self._flights.append(_Flight(
            kind="mixed", arrays=[toks_out, *experts],
            meta={
                "experts": (experts, 1),
                # a decode row's consumed token: the host's, or (carry)
                # whatever the flight before this one sampled last
                "decode": [(s, s.request,
                            None if carry else int(dtoks[s.idx, 0]))
                           for s in decoding],
                "prompt": [(s, s.request, bool(final[r]))
                           for r, s in enumerate(riding)],
                "ahead": ([(s, s.request, 1, 1) for s in decoding]
                          + [(s, s.request, 0, 1) for s in finals]),
                "cost": ckey,
                "pred_ms": (self._costmodel.predict_ms("mixed", ckey)
                            if self._costmodel is not None else None),
                # timeline args for the flight recorder's harvest span
                "rec": {"decode": len(decoding),
                        "prefill": len(riding) - len(finals),
                        "finals": len(finals),
                        "chunk_tokens": chunk_tokens}},
            t_enqueue=t0,
        ))
        return True

    def _complete_mixed(self, fl: _Flight) -> None:
        """Harvest a mixed flight: decode rows emit their sampled token
        (and commit the consumed input token, like the scan harvest),
        final-chunk rows emit their first token and join the decode
        set, non-final chunk rows only collect prefill-time
        attribution."""
        # lint: ignore[hot-path-sync] flight ready() verified by _harvest; the transfer already landed
        toks_host = np.asarray(fl.arrays[0])  # [S + R]
        S = self.n_slots
        now = time.perf_counter()
        dt_ms = (now - fl.t_enqueue) * 1e3
        # exemplar BEFORE the emit loop: a finishing slot deactivates
        # below, and its trace id is exactly the one worth linking
        exemplar = self._active_exemplar()
        decode_emitted = first_toks = prompt_toks = 0
        last = self._harvest_last
        for s, req, consumed in fl.meta["decode"]:
            tok = int(toks_host[s.idx])
            if consumed is None:
                consumed = last[s.idx]
            last[s.idx] = tok
            if s.request is not req or s.state is not SlotState.DECODE:
                continue  # finished/cancelled in an earlier flight
            s.cache_tokens.append(consumed)
            s.n_past += 1
            s.t_decode_ms += dt_ms
            decode_emitted += 1
            self._emit_token(s, tok, defer=True)
            if s.state is SlotState.DECODE:
                self._flush_emit(s)
        for r, (s, req, is_final) in enumerate(fl.meta["prompt"]):
            # a non-final chunk's bookkeeping advanced at enqueue; its
            # device time lands at the covering final's harvest
            # (t_prefill_t0)
            if not is_final:
                continue
            last[s.idx] = int(toks_host[S + r])
            if s.request is not req:  # cancelled mid-flight
                continue
            s.t_prefill_ms += (now - (s.t_prefill_t0
                                      or fl.t_enqueue)) * 1e3
            self.metrics.prompt_tokens_processed += s.n_prompt
            # the Prometheus counter reports tokens that actually went
            # THROUGH prefill — reused (resident/copied/restored)
            # tokens are counted in engine_prefix_reused_tokens_total,
            # so reused + prefilled == submitted prompt tokens
            actual = max(0, s.n_prompt - s.n_reused)
            self.metrics.prefill_tokens += actual
            prompt_toks += actual
            first_toks += 1
            s.state = SlotState.DECODE
            s.t_last = now
            self._emit_token(s, last[s.idx])
        m = self._mlabel
        if prompt_toks:
            tm.ENGINE_PROMPT_TOKENS.labels(model=m).inc(prompt_toks)
        if decode_emitted + first_toks:
            tm.ENGINE_GENERATED_TOKENS.labels(model=m).inc(
                decode_emitted + first_toks)
        if decode_emitted:
            tm.ENGINE_INTER_TOKEN.labels(model=m).observe(
                dt_ms / 1e3, exemplar=exemplar)
            self._note_tokens_per_second(decode_emitted, dt_ms / 1e3)
        self.metrics.slots_busy = sum(1 for s in self.slots if s.active)
    # lint: endregion hot_path

    def _note_decode_advance(self, now: float) -> None:
        """Stall accounting: observe the gap between consecutive
        decode-advancing dispatches while >=1 slot decodes
        (engine_decode_stall_seconds — the series the legacy holds
        spiked and the mixed dispatcher bounds). _update_gauges resets
        the clock whenever no slot is decoding."""
        if self._last_decode_adv:
            tm.ENGINE_DECODE_STALL.labels(model=self._mlabel).observe(
                max(0.0, now - self._last_decode_adv))
        self._last_decode_adv = now

    def _context_tokens(self, rows) -> tuple[float, int]:
        """(read, held) context tokens of ``rows`` = (pos, n) pairs: a
        row whose n consecutive queries start at position pos. Held is
        the causal sum n*pos + n(n-1)/2; read is the mean over layers
        of what each layer's sliding window (0: none) lets those
        queries see, min(context, window) a query."""
        read, held = 0, 0
        for pos, n in rows:
            full = n * pos + n * (n - 1) // 2
            held += full
            for w, layers in self._layer_windows.items():
                if w <= 0 or pos + n - 1 <= w:
                    got = full
                elif pos >= w:
                    got = n * w
                else:  # the first w - pos queries still see everything
                    m = w - pos
                    got = m * pos + m * (m - 1) // 2 + (n - m) * w
                read += got * layers
        return read / self.spec.n_layers, held

    def _note_dispatch_tokens(self, kind: str, real: int, padded: int,
                              rows, steps: int = 0) -> None:
        """Counts taken where the work is dispatched, from host scalars
        the enqueue already holds: token positions that carry work
        against those of the program's shape
        (engine_dispatch_tokens_total{part}), the context tokens the
        attention rows (``rows``: _context_tokens') have to read and
        those they hold (engine_attn_context_tokens_total,
        ..._held_tokens_total) and, for decode-only programs, the
        token-steps (engine_decode_steps_total). Children are bound
        once a kind."""
        ctr = self._tok_ctr.get(kind)
        if ctr is None:
            m = self._mlabel
            ctr = self._tok_ctr[kind] = (
                tm.ENGINE_DISPATCH_TOKENS.labels(
                    model=m, kind=kind, part="real"),
                tm.ENGINE_DISPATCH_TOKENS.labels(
                    model=m, kind=kind, part="padded"),
                tm.ENGINE_ATTN_CONTEXT_TOKENS.labels(model=m, kind=kind),
                tm.ENGINE_ATTN_CONTEXT_HELD_TOKENS.labels(
                    model=m, kind=kind),
                tm.ENGINE_DECODE_STEPS.labels(model=m))
        read, held = self._context_tokens(rows)
        ctr[0].inc(real)
        ctr[1].inc(padded)
        ctr[2].inc(read)
        ctr[3].inc(held)
        if steps:
            ctr[4].inc(steps)

    def _take_expert_stats(self) -> list:
        """The expert statistics of the step programs the newest
        dispatch ran (one [E + 1] i32 array a program, _dev_exec left
        them), their copy to the host started; [] for a model without
        experts."""
        out, self._expert_out = self._expert_out, []
        if not self.spec.n_experts:
            return []
        for a in out:
            a.copy_to_host_async()
        return out

    def _note_expert_stats(self, kind: str, stats: list,
                           steps: int) -> None:
        """A harvested dispatch's expert statistics onto the counters:
        tokens by expert, the expert layer-steps it ran (``steps``
        token-steps a program) and the experts those touched."""
        if not stats:
            return
        m, E = self._mlabel, self.spec.n_experts
        ctr = self._expert_ctr.get(kind)
        if ctr is None:
            ctr = self._expert_ctr[kind] = (
                tm.ENGINE_EXPERT_LAYER_STEPS.labels(model=m, kind=kind),
                tm.ENGINE_EXPERTS_TOUCHED.labels(model=m, kind=kind),
                [tm.ENGINE_EXPERT_TOKENS.labels(model=m, expert=str(e))
                 for e in range(E)])
        # lint: ignore[hot-path-sync] the flight these ride was ready()
        total = np.sum([np.asarray(a) for a in stats], axis=0)
        ctr[0].inc(len(stats) * steps * self._n_expert_layers)
        ctr[1].inc(int(total[E]))
        for e in np.nonzero(total[:E])[0]:
            ctr[2][e].inc(int(total[e]))

    def _note_ragged_rows(self, kind: str, n: int) -> None:
        """Rows advanced through the unified ragged path by kind
        (decode / prefill chunk / prefill final / spec verify) —
        engine_ragged_rows_total, the series proving every row kind
        actually flows through the one-kernel dispatch discipline."""
        if self._paged and n > 0:
            tm.ENGINE_RAGGED_ROWS.labels(
                model=self._mlabel, kind=kind).inc(n)

    _TPS_ALPHA = 0.3

    def _note_tokens_per_second(self, emitted: int, dt_s: float) -> None:
        """ONE EWMA for metrics.tokens_per_second across every decode
        flavor (k-scan harvest, blocking single-step, speculative,
        mixed). The previous per-site stores each stomped the value
        with a single-dispatch instantaneous rate, so /backend/monitor
        flapped between k-step and blocking-path numbers."""
        if emitted <= 0 or dt_s <= 0:
            return
        inst = emitted / dt_s
        cur = self.metrics.tokens_per_second
        self.metrics.tokens_per_second = (
            inst if cur <= 0.0
            else (1.0 - self._TPS_ALPHA) * cur + self._TPS_ALPHA * inst)

    def _soft_payload(self, group: list[_Slot], pos0: Any,
                      bucket: int) -> Optional[list]:
        """Compact multimodal rows for a mixed step's prompt group
        (member i is row i): [(batch row, chunk-relative positions,
        embeds [k, D])] for every slot whose soft tokens fall inside
        this chunk; None when text-only (the common case pays
        nothing)."""
        out = []
        for r, s in enumerate(group):
            req = s.request
            if req is None or req.soft_embeds is None:
                continue
            sp = np.asarray(req.soft_positions)
            sel = (sp >= int(pos0[r])) & (sp < int(pos0[r]) + bucket)
            if not sel.any():
                continue
            out.append((r, (sp[sel] - int(pos0[r])).astype(np.int32),
                        np.asarray(req.soft_embeds)[sel]
                        .astype(np.float32)))
        return out or None

    def _soft_dense(self, rows: Optional[list], B: int,
                    T: int) -> Optional[tuple]:
        """Compact soft payload -> padded device arrays (emb [Rp, D],
        brow [Rp], bpos [Rp]) for _soft_expand inside the jitted prefill.
        Rp is the token count rounded to a power of two (bounded jit
        cache); padding rows point at batch row B, which the scatter
        drops."""
        if not rows:
            return None
        R = sum(len(idxs) for _, idxs, _ in rows)
        Rp = 1 << max(R - 1, 0).bit_length()
        D = self.spec.d_model
        emb = np.zeros((Rp, D), np.float32)
        brow = np.full((Rp,), B, np.int32)
        bpos = np.zeros((Rp,), np.int32)
        off = 0
        for r, idxs, vals in rows:
            n = len(idxs)
            emb[off:off + n] = vals
            brow[off:off + n] = r
            bpos[off:off + n] = idxs
            off += n
        return jnp.asarray(emb), jnp.asarray(brow), jnp.asarray(bpos)

    def _constraint_mask_rows(self, slots: list[_Slot]) -> Optional[np.ndarray]:
        """Build [B, V] bool masks for grammar-constrained slots (host-side
        automaton, mask shipped to device — SURVEY.md §7 hard part #3)."""
        rows = []
        any_mask = False
        V = self.spec.vocab_size
        for s in slots:
            req = s.request
            mask = None
            if req is not None and req.constraint is not None:
                raw = np.asarray(
                    req.constraint.next_mask(s.constraint_state), dtype=bool
                )
                if raw.shape[0] != V:  # tokenizer/model vocab mismatch
                    mask = np.zeros(V, bool)
                    mask[: min(raw.shape[0], V)] = raw[:V]
                else:
                    mask = raw
                any_mask = True
            if req is not None and req.logit_bias:
                if mask is None:
                    mask = np.ones(V, bool)
                else:
                    # next_mask returns cached/shared arrays — mutating
                    # in place would ban these tokens for every later
                    # request sharing the constraint
                    mask = mask.copy()
                for tid, bias in req.logit_bias.items():
                    if 0 <= int(tid) < V and bias <= -100:
                        mask[int(tid)] = False
                any_mask = True
            rows.append(mask if mask is not None else np.ones(V, bool))
        if not any_mask:
            return None
        return np.stack(rows)

    def _multi_step_k(
        self, decoding: list[_Slot], ahead: dict, sampled: dict,
    ) -> tuple[int, int, int]:
        """(k, room, need): on-device step count — no grammar/logit-bias
        slot (those need a host-side mask per token), no slot may cross
        the end of its context row mid-scan, and k is capped by ``need``
        (the largest remaining token budget). ``room`` is the shared
        context headroom that also gates pipeline depth. Both count
        what the dispatches in flight already cover (``ahead``
        positions, ``sampled`` tokens per slot)."""
        room = min(self.max_seq - 1 - s.n_past - ahead.get(s.idx, 0)
                   for s in decoding)
        need = 0
        host = False
        for s in decoding:
            req = s.request
            if req is not None and (req.constraint or req.logit_bias):
                host = True
            if req is not None:
                need = max(need, req.max_tokens - len(s.generated)
                           - sampled.get(s.idx, 0))
        if host or self.decode_steps <= 1:
            return 1, room, need
        # cap by the largest remaining budget: a short request must not
        # pay (or make the NEXT request wait behind) a full-length scan
        # of discarded overshoot tokens
        k = min(self.decode_steps, max(room, 1), max(need, 1))
        if k & (k - 1):  # round UP to a power of two (tiny jit cache)
            k = 1 << k.bit_length()
        k = min(k, self.decode_steps, max(room, 1))
        while k & (k - 1):  # room may not be a power of two: round down
            k &= k - 1
        k = max(k, 1)
        # prefer an already-compiled k in [k, room] over cold-compiling
        # the exact smaller variant (same trick as the window buckets:
        # overshoot is discarded host-side anyway)
        compiled = [key[1] for key in self._decode_k_fns
                    if key[0] == "decode" and k < key[1] <= room
                    and key[1] <= self.decode_steps]
        if compiled and ("decode", k) not in {
                (key[0], key[1]) for key in self._decode_k_fns
                if len(key) > 1}:  # 1-tuple keys: ("draft_prefill",)
            k = min(compiled)
        return k, room, need

    # lint: region hot_path
    def _dispatch_decode(self, decoding: list[_Slot]) -> bool:
        """Enqueue (or, for the host-interactive paths, run) decode work
        (ref: grpc-server.cpp:1688-1726 batching ongoing tokens). The
        normal path enqueues one k-step scan as a _Flight and keeps up
        to ``_pipeline_depth`` scans in flight, chained on the
        device-resident carry — the device never idles waiting for a
        download, and downloads never serialize behind each other. A
        scan chains behind a mixed step the same way: the rows that
        step admitted are in the carry it returned.
        Tokens generated past a slot's EOS/stop are discarded host-side
        at harvest (the over-written tail K/V sits beyond the valid
        prefix, so it is never attended to)."""
        spec_mode, spec_slots = self._spec_mode(decoding)
        if spec_mode and not self._flights and min(
                self.max_seq - 1 - s.n_past for s in decoding
        ) >= self.n_draft:
            # near the context wall the kd-token verify forward would
            # clamp its KV writes onto valid rows; normal path instead.
            # Eligible slots advance speculatively; the rest (penalties/
            # grammar/bias/mm) fall through to the normal dispatch below
            # — PER-SLOT eligibility, not whole-batch. Spec decoding is
            # a host-interactive (blocking) path, so it runs only with
            # an empty pipeline.
            self._spec_decode_step(spec_slots, spec_mode)
            decoding = [s for s in decoding
                        if s.state is SlotState.DECODE
                        and s not in spec_slots]
            if not decoding:
                return True
        now = time.perf_counter()
        dflights = [f for f in self._flights if f.kind == "decodek"]
        ahead, sampled = self._ahead()
        k, room, need_tokens = self._multi_step_k(decoding, ahead, sampled)
        if k <= 1:
            # grammar/logit-bias slots need a host mask per token: the
            # blocking single-step path, and it needs the true current
            # tokens — drain the pipeline first
            if self._flights:
                return False
            self._decode1_step(decoding)
            return True
        free = any(not s.active for s in self.slots)
        depth = self._pipeline_depth
        lat_mode = (self.latency_target_ms is not None and free
                    and not self._pending
                    and now - self._last_arrival >= 1.0
                    # a wave's drain tail (every stream within ONE full
                    # scan of its budget) finishes at full k: throttling
                    # it only delays the wall clock, no arrival benefits.
                    # Kept at one scan, not more: continuous short-
                    # generation service must still engage the clamp
                    and need_tokens > self.decode_steps)
        if lat_mode:
            # latency mode at open capacity: ONE short scan in flight at
            # a time, so total queued decode work stays under the
            # budget. The device idles the dispatch RTT between scans —
            # the throughput half of the knob's tradeoff.
            depth = 1
        if len(dflights) >= depth or room < k:
            return False
        if need_tokens <= 0:
            return False  # everything already covered by in-flight scans
        if (self._pending or now - self._last_arrival < 1.0) and free:
            # arrivals active with admissible room: a late request's
            # admission step queues on the device BEHIND this scan —
            # keep it short so burst TTFT is not hostage to a long
            # scan. (A flat k=4 on free slots ALONE throttled the 1B
            # drain to 1/4 throughput; the open-capacity case below
            # sizes k from measured step time instead.)
            k = min(k, 4)
        elif free:
            # open capacity, no arrival in sight: an UNPREDICTED
            # arrival's admission queues behind whatever scans are in
            # flight when it lands, so bound that queue in TIME (see
            # _latency_k for the balanced/latency-mode policies and
            # their measured effect).
            k = min(k, self._latency_k(lat_mode))
        itl_budget = self._itl_budget_ms()
        if itl_budget > 0.0:
            # explicit ms ITL budget: a k-scan's tokens surface only at
            # harvest, so the scan's whole device time IS the stream's
            # inter-token gap — clamp k to the largest warmed length
            # whose predicted time fits. Per-step time comes from the
            # measured EWMA when it has samples, else the cost-model
            # prediction (the fallback-before-warm contract); floor at
            # the smallest warmed multi-step scan: progress beats
            # stalling even over budget.
            step = (self._step_ms if self._step_ms > 0.0
                    else (self._costmodel.decode_step_ms() or 0.0))
            if step > 0.0:
                fits = [kk for kk in self._warm_ks
                        if kk > 1 and kk * step <= itl_budget]
                kb = (max(fits) if fits
                      else min(kk for kk in self._warm_ks if kk > 1))
                k = min(k, kb)
        carry = self._carry_for(decoding)
        if carry is None:
            # dispatches in flight and some row's next token is only on
            # the device, outside the carry (a slot woken in DECODE by a
            # migration, a blocking step's rows): wait for the harvest
            return False

        S = self.n_slots
        # the window must cover EVERY non-free slot position plus the
        # tokens already in flight
        window = self._route.window(
            max(s.n_past + ahead.get(s.idx, 0) for s in self.slots
                if s.state in (SlotState.DECODE, SlotState.PENDING_FIRST))
            + k + 1, "decode",
            (key[2] for key in self._decode_k_fns
             if key[0] == "decode" and key[1] == k))
        if carry:
            # rows parked on the device keep the position the chain's
            # earlier windows covered: a window never shrinks under them
            window = max(window, self._dev_window)

        if self._paged:
            # page capacity for the scan's write span ([n_past +
            # ahead, + k) per advancing row) BEFORE the table
            # snapshots below
            for s in list(decoding):
                if not self._pool_ensure(
                        s, s.n_past + ahead.get(s.idx, 0) + k):
                    self._finish(s, "length")
                    decoding.remove(s)
            if not decoding:
                return True
        advancing = {s.idx for s in decoding}
        tokens, pos0, active = self._decode_inputs(decoding, ahead, window)
        payload = {
            "k": k, "window": window, "depth": 1, "carry": carry,
            "tokens": tokens, "pos0": pos0, "active": active,
        }
        if self._paged:
            payload["pt"] = self._phys_rows(list(range(S)), window)
            payload["wb"] = self._wb_rows(
                [(i, ((int(pos0[i]), int(pos0[i]) + k)
                      if i in advancing else None)) for i in range(S)],
                window)
        self._note_ragged_rows("decode", len(decoding))
        # step j of the scan reads the row's cache as it stands then:
        # n_past + the tokens of dispatches still in flight + j
        self._note_dispatch_tokens(
            "decodek", len(decoding) * k, S * k,
            [(int(pos0[s.idx]), k) for s in decoding], steps=k)
        batches = self._run("decodek", payload)
        toks = batches[0]
        toks.copy_to_host_async()
        experts = self._take_expert_stats()
        self._dev_rows = {s.idx: s.request for s in decoding}
        self._dev_window = window
        dckey = costmodel.dispatch_key("decodek", payload)
        chained = bool(self._flights)
        self._flights.append(_Flight(
            kind="decodek", arrays=[toks, *experts],
            meta={
                "k": k,
                "experts": (experts, k),
                "cost": dckey,
                "pred_ms": (self._costmodel.predict_ms("decodek", dckey)
                            if self._costmodel is not None else None),
                "pairs": [(s, s.request) for s in decoding],
                "ahead": [(s, s.request, k, k) for s in decoding],
                # None for a chained scan: what the flight before it
                # sampled last is unknown until that flight harvests
                # (_harvest_last)
                "prev_last": (None if chained else
                              {s.idx: int(tokens[s.idx, 0])
                               for s in decoding}),
                # enqueued behind another DECODE scan: its harvest-to-
                # harvest gap measures decode device time (the step
                # EWMA's input). A scan enqueued onto an idle device
                # measures device time + dispatch RTT, and one behind a
                # mixed step measures that step's time too
                # (_last_harvest_t only advances on decode harvests) —
                # neither may pollute the EWMA, so a mixed step
                # anywhere in the pipeline disqualifies the sample even
                # when another decode scan is also in flight (ADVICE r5
                # #1: the 8x outlier guard alone let prefill-inflated
                # samples through and mis-sized the k clamps)
                "saturated": bool(dflights) and not any(
                    f.kind == "mixed" for f in self._flights),
                # timeline args for the flight recorder's harvest span
                "rec": {"rows": len(decoding), "k": k, "window": window},
            },
            t_enqueue=time.perf_counter(),
        ))
        tm.ENGINE_MIXED_DISPATCH.labels(
            model=self._mlabel, composition="decode_only").inc()
        self._note_decode_advance(time.perf_counter())
        return True

    def _complete_decodek(self, fl: _Flight) -> None:
        """Harvest one k-step scan: emit tokens per slot, discarding
        overshoot past a finish (EOS/stop/limit)."""
        k = fl.meta["k"]
        # lint: ignore[hot-path-sync] flight ready() verified by _harvest; the transfer already landed
        toks_host = np.asarray(fl.arrays[0])  # [S, k]
        now = time.perf_counter()
        dt_ms = (now - max(fl.t_enqueue, self._last_harvest_t)) * 1e3
        self._last_harvest_t = now
        step = dt_ms / k
        if (fl.meta.get("saturated") and 0.0 < step
                and (self._step_ms == 0.0
                     or step < 8.0 * self._step_ms)):
            # EWMA per-step device time, from SATURATED samples only: a
            # scan enqueued onto an idle device (latency mode's depth-1
            # cadence) measures step + RTT, and feeding that back into
            # _latency_k collapses k to the floor and then mis-sizes
            # the balanced clamp too. Saturated samples keep flowing
            # whenever all slots are busy (full k, depth 2), which is
            # exactly when step time is cleanly observable. The 8x
            # outlier guard drops compile/transfer stalls.
            self._step_ms = (step if self._step_ms == 0.0
                             else 0.8 * self._step_ms + 0.2 * step)
            tm.ENGINE_DECODE_STEP.labels(model=self._mlabel).observe(
                step / 1e3)
        prev_last = fl.meta["prev_last"]
        if prev_last is None:
            prev_last = self._harvest_last
        # exemplar BEFORE the emit loop: a finishing slot deactivates
        # below, and its trace id is exactly the one worth linking
        exemplar = self._active_exemplar()
        emitted = 0
        next_last: dict[int, int] = {}
        for s, req in fl.meta["pairs"]:
            next_last[s.idx] = int(toks_host[s.idx, k - 1])
            if s.request is not req or s.state is not SlotState.DECODE:
                continue  # finished/cancelled in an earlier flight
            consumed = [prev_last[s.idx]] + [
                int(t) for t in toks_host[s.idx, : k - 1]
            ]
            s.t_decode_ms += dt_ms
            for j in range(k):
                if s.state is not SlotState.DECODE:
                    break  # finished: discard overshoot tokens
                s.cache_tokens.append(consumed[j])
                s.n_past += 1
                emitted += 1
                self._emit_token(s, int(toks_host[s.idx, j]),
                                 defer=True)
            if s.state is SlotState.DECODE:
                self._flush_emit(s)  # one event per slot per harvest
        self._harvest_last.update(next_last)
        if dt_ms > 0 and emitted:
            self._note_tokens_per_second(emitted, dt_ms / 1e3)
            tm.ENGINE_GENERATED_TOKENS.labels(model=self._mlabel).inc(
                emitted)
            tm.ENGINE_INTER_TOKEN.labels(model=self._mlabel).observe(
                dt_ms / 1e3 / k, exemplar=exemplar)
        self.metrics.slots_busy = sum(1 for s in self.slots if s.active)

    def _decode1_step(self, decoding: list[_Slot]) -> None:
        """Blocking single-step decode for host-interactive slots
        (grammar masks / logit_bias need fresh host work every token)."""
        t0 = time.perf_counter()
        S = self.n_slots
        if self._paged:
            for s in list(decoding):
                if not self._pool_ensure(s, s.n_past + 1):
                    self._finish(s, "length")
                    decoding.remove(s)
            if not decoding:
                return
        tokens = np.zeros((S, 1), np.int32)
        pos0 = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        for s in self.slots:
            if s.state is SlotState.DECODE:
                tokens[s.idx, 0] = (s.generated[-1] if s.generated
                                    else s.request.prompt_ids[-1])
                pos0[s.idx] = s.n_past
                active[s.idx] = True
            else:
                pos0[s.idx] = min(s.n_past, self.max_seq - 1)
        masks = self._constraint_mask_rows(self.slots)
        payload = {
            "tokens": tokens, "pos0": pos0, "active": active,
            "masks": masks,
        }
        if self._paged:
            payload["pt"] = self._phys_rows(list(range(S)), self.max_seq)
            payload["wb"] = self._wb_rows(
                [(s.idx, ((s.n_past, s.n_past + 1)
                          if s.state is SlotState.DECODE else None))
                 for s in self.slots], self.max_seq)
        self._note_dispatch_tokens(
            "decode1", len(decoding), S,
            [(s.n_past, 1) for s in decoding], steps=1)
        toks = self._run("decode1", payload)
        experts = self._take_expert_stats()
        # lint: ignore[hot-path-sync] decode1 IS the blocking path: grammar masks / logit bias need every token on host before the next dispatch
        toks_host = np.asarray(toks)
        self._note_expert_stats("decode1", experts, 1)
        dt_ms = (time.perf_counter() - t0) * 1e3
        emitted = 0
        for s in decoding:
            s.cache_tokens.append(int(tokens[s.idx, 0]))
            s.n_past += 1
            s.t_decode_ms += dt_ms
            emitted += 1
            self._emit_token(s, int(toks_host[s.idx]))
        self._dev_rows = {}  # device carry (if any) is now stale
        if dt_ms > 0 and emitted:
            self._note_tokens_per_second(emitted, dt_ms / 1e3)
            tm.ENGINE_GENERATED_TOKENS.labels(model=self._mlabel).inc(
                emitted)
        tm.ENGINE_MIXED_DISPATCH.labels(
            model=self._mlabel, composition="decode_only").inc()
        self._note_ragged_rows("decode", len(decoding))
        self._note_decode_advance(t0)
        self.metrics.slots_busy = sum(1 for s in self.slots if s.active)

    # lint: endregion hot_path

    # ---------------------------------------------------- token → stream

    def _emit_token(self, slot: _Slot, token_id: int,
                    defer: bool = False) -> None:
        """Per-sampled-token bookkeeping (ref: process_token,
        grpc-server.cpp:1069-1160: stop words, EOS, limits).

        ``defer=True`` (harvest loops): per-token semantics (stops, EOS,
        limits, grammar advance) run exactly as before, but the text
        spans buffer on the slot and flush as ONE StreamEvent per
        harvest (_flush_emit) — per-token queue puts woke 64 consumer
        threads 1024 times per k=16 scan, a measured multi-hundred-ms
        GIL pile-up at burst time."""
        req = slot.request
        assert req is not None and slot.decoder is not None
        if req.constraint is not None:
            slot.constraint_state = req.constraint.advance(
                slot.constraint_state, token_id
            )
        if not slot.generated:
            # first token of the request: TTFT and prefill attribution
            # (host timestamps only; guarded so the per-token path pays
            # one list check)
            slot.t_first = time.perf_counter()
            TRACER.event(req.id, "first_token", t=slot.t_first)
            if req.t_submit:
                # OpenMetrics exemplar: the trace id links this bucket
                # sample to its /debug/traces entry
                tm.ENGINE_TTFT.labels(model=self._mlabel).observe(
                    slot.t_first - req.t_submit,
                    exemplar=({"trace_id": req.trace_id}
                              if req.trace_id else None))
            tm.ENGINE_PREFILL.labels(model=self._mlabel).observe(
                slot.t_prefill_ms / 1e3)
        slot.generated.append(token_id)
        self.metrics.tokens_generated += 1

        if (not req.ignore_eos) and token_id in self.tokenizer.eos_ids:
            self._finish(slot, "stop")
            return

        text = slot.decoder.push(token_id)
        slot.pending_text += text

        # stop-string scan with partial-match withholding
        emit, stop_hit = _scan_stops(slot.pending_text, req.stop)
        if stop_hit:
            if slot.out is not None:
                self._flush_emit(slot)
                slot.out.put(StreamEvent(text=emit, token_id=token_id))
            slot.pending_text = ""
            self._finish(slot, "stop")
            return
        if defer:
            if emit:
                slot.emit_buf.append(emit)
            if slot.emit_tok is None:
                slot.emit_tok = token_id
        elif slot.out is not None:
            slot.out.put(StreamEvent(text=emit, token_id=token_id))
        if emit:
            slot.pending_text = slot.pending_text[len(emit):]

        if len(slot.generated) >= req.max_tokens:
            self._finish(slot, "length")
        elif slot.n_past + 1 >= self.max_seq:
            # context exhausted: end generation (ref: grpc-server.cpp
            # :1673-1683 — no context shift)
            self._finish(slot, "length")

    def _flush_emit(self, slot: _Slot) -> None:
        """Put the buffered text spans as one stream event. A harvest
        whose text was fully withheld (partial stop-string match /
        multi-byte tail) puts NOTHING — an empty event would wake the
        consumer thread for a no-op, re-creating the wakeup storm this
        buffering removes."""
        if not slot.emit_buf:
            slot.emit_tok = None
            return
        if slot.out is not None:
            slot.out.put(StreamEvent(text="".join(slot.emit_buf),
                                     token_id=slot.emit_tok))
        slot.emit_buf = []
        slot.emit_tok = None

    def _finish(self, slot: _Slot, reason: str) -> None:
        req = slot.request
        self._flush_emit(slot)  # buffered text precedes the done event
        self._maybe_save_prompt_cache(slot)
        if self._migrator is not None and req is not None:
            # disaggregated prefill side: a finishing prefill-probe
            # slot's pages are captured into the migration bus HERE,
            # before release can recycle them (the gather lands first
            # in device order, so later overwrites are safe). No-op
            # for ordinary requests.
            self._migrator.on_finish(slot, reason)
        full = slot.decoder.text if slot.decoder else ""
        if req is not None and req.stop:
            for st in req.stop:
                i = full.find(st)
                if i >= 0:
                    full = full[:i]
        # strip trailing eos token artifacts is tokenizer-dependent; decoder
        # already excludes eos because we finish before pushing it
        if slot.pending_text and reason != "stop":
            if slot.out is not None and slot.pending_text:
                slot.out.put(StreamEvent(text=slot.pending_text))
        dt_decode = slot.t_decode_ms
        now = time.perf_counter()
        queue_ms = ttft_ms = 0.0
        if req is not None and req.t_submit:
            queue_ms = max(0.0, (slot.t_start - req.t_submit) * 1e3)
            if slot.t_first:
                ttft_ms = (slot.t_first - req.t_submit) * 1e3
        if req is not None and req.disagg is not None:
            # migrated request: queue time is what the request spent
            # QUEUED on either engine (original wait on the prefill
            # side + re-admission wait here), not the whole relay —
            # prefill device time and migration wall already live in
            # timing_prompt_processing_ms (stamped at adoption)
            h = req.disagg
            queue_ms = h.queued_ms + max(
                0.0, (slot.t_start - h.t_resubmit) * 1e3)
            tm.ENGINE_DISAGG_STAGE.labels(
                model=self._mlabel, stage="decode").observe(
                max(0.0, now - h.t_resubmit))
        ev = StreamEvent(
            done=True,
            finish_reason=reason,
            full_text=full,
            prompt_tokens=slot.n_prompt,
            completion_tokens=len(slot.generated),
            timing_prompt_processing_ms=slot.t_prefill_ms,
            timing_token_generation_ms=dt_decode,
            timing_queue_ms=queue_ms,
            timing_first_token_ms=ttft_ms,
            timing_prefill_enqueue_ms=slot.t_prefill_enq_ms,
        )
        if slot.out is not None:
            slot.out.put(ev)
        self.metrics.requests_completed += 1
        tm.ENGINE_REQUESTS.labels(model=self._mlabel, reason=reason).inc()
        if reason == "cancelled":
            tm.ENGINE_CANCELLATIONS.labels(model=self._mlabel,
                                           reason="client").inc()
        if req is not None:
            TRACER.event(req.id, "done", t=now)
            TRACER.annotate(req.id, "terminal", t=now, outcome=reason)
            TRACER.finish(req.id, status=reason)
        self._release(slot)

    def _release(self, slot: _Slot) -> None:
        # cache_tokens stay: they describe this row's reusable prefix.
        # Exception: multimodal rows — soft tokens share one id across
        # DIFFERENT images, so their K/V must never be prefix-matched
        if slot.request is not None and slot.request.soft_embeds is not None:
            slot.cache_tokens = []
            slot.n_past = 0
            if self._paged:
                self._pool.drop(slot.idx)
        slot.state = SlotState.FREE
        slot.request = None
        slot.out = None
        slot.decoder = None
        slot.pending_text = ""
        slot.emit_buf = []
        slot.emit_tok = None
        slot.constraint_state = None

    # ------------------------------------------------------------- extras

    def tokenize(self, text: str) -> list[int]:
        return self.tokenizer.encode(text)

    def embed(self, text: str) -> np.ndarray:
        """Mean-pooled final hidden state (ref: transformers backend
        mean-pool embeddings, backend/python/transformers/backend.py
        :286-324; served via /v1/embeddings). Uses a throwaway 1-slot cache;
        does not touch the serving slots."""
        ids = self.tokenizer.encode(text, add_bos=True) or [0]
        ids = ids[: self.max_seq]
        bucket = self._bucket(len(ids))
        if len(ids) > bucket:  # past the last bucket: whole chunks
            bucket = -(-len(ids) // self._EMBED_CHUNK) * self._EMBED_CHUNK
        toks = np.zeros((1, bucket), np.int32)
        toks[0, : len(ids)] = ids
        hidden = self._run("embed", {"toks": toks, "bucket": bucket})
        h = np.asarray(hidden[0, : len(ids)], dtype=np.float32)
        return h.mean(axis=0)


def _scan_stops(pending: str, stops: list[str]) -> tuple[str, bool]:
    """Return (text safe to emit, hit). Withholds any tail that is a prefix
    of a stop string (ref: stop-word partial matching in process_token)."""
    if not stops:
        return pending, False
    for st in stops:
        i = pending.find(st)
        if i >= 0:
            return pending[:i], True
    # find longest suffix of pending that is a prefix of some stop
    hold = 0
    for st in stops:
        for k in range(min(len(st) - 1, len(pending)), 0, -1):
            if pending.endswith(st[:k]):
                hold = max(hold, k)
                break
    return pending[: len(pending) - hold] if hold else pending, False
