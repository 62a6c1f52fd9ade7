"""Canonical metric families, instrumented across the serving layers.

Every family registered here MUST:
- be snake_case with a unit suffix (counters end in ``_total``;
  time/size series end in ``_seconds``/``_bytes``; dimensionless gauges
  end in ``_count``/``_ratio``), and
- appear in the README.md "Observability" table.

``tools/check_metrics.py`` statically enforces both (wired into the
test suite), so metric drift fails fast instead of rotting dashboards.

Layer map (where each family is recorded):
- HTTP         server/app.py telemetry middleware
- engine       engine/engine.py scheduler (host-held values only — no
               device syncs ride a metric sample)
- loader       engine/loader.py ModelLoader (reuses the per-phase
               breakdown from models/load_timing.py)
- workers      engine/loader.py busy/idle accounting + WatchDog
"""

from __future__ import annotations

from .registry import REGISTRY

# sub-millisecond ladder for per-token / per-step series; the default
# ladder (1ms..60s) fits request-scale latencies
_STEP_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
)

# ------------------------------------------------------------------ HTTP

# successor of the reference's api_call histogram (core/services/
# metrics.go) — re-keyed by matched ROUTE TEMPLATE, not the raw path:
# unmatched/404 paths bucket as "other" and the label-set cap collapses
# any residual explosion into path="other"
API_CALL = REGISTRY.histogram(
    "api_call_seconds",
    "HTTP API call latency by method and matched route template",
    labels=("method", "path"),
    max_label_sets=128,
    overflow={"path": "other"},
)

# ---------------------------------------------------------------- engine

ENGINE_QUEUE_WAIT = REGISTRY.histogram(
    "engine_queue_wait_seconds",
    "Time a request spent queued before slot admission",
    labels=("model",),
)
ENGINE_TTFT = REGISTRY.histogram(
    "engine_ttft_seconds",
    "Submit-to-first-token latency per request",
    labels=("model",),
)
ENGINE_PREFILL = REGISTRY.histogram(
    "engine_prefill_seconds",
    "Prompt-processing (prefill) time per request",
    labels=("model",),
)
ENGINE_INTER_TOKEN = REGISTRY.histogram(
    "engine_inter_token_seconds",
    "Mean inter-token latency per harvested decode scan",
    labels=("model",), buckets=_STEP_BUCKETS,
)
ENGINE_DECODE_STEP = REGISTRY.histogram(
    "engine_decode_step_seconds",
    "Device time per decode step (saturated-pipeline samples only)",
    labels=("model",), buckets=_STEP_BUCKETS,
)
ENGINE_QUEUE_DEPTH = REGISTRY.gauge(
    "engine_queue_depth_count",
    "Requests queued awaiting a slot",
    labels=("model",),
)
ENGINE_SLOTS_BUSY = REGISTRY.gauge(
    "engine_slots_busy_count",
    "Slots occupied by an active request (batch occupancy)",
    labels=("model",),
)
ENGINE_KV_UTIL = REGISTRY.gauge(
    "engine_kv_slot_utilization_ratio",
    "Fraction of KV-cache positions held by active slots",
    labels=("model",),
)
ENGINE_REQUESTS = REGISTRY.counter(
    "engine_requests_total",
    "Completed engine requests by finish reason",
    labels=("model", "reason"),
)
ENGINE_CANCELLATIONS = REGISTRY.counter(
    "engine_cancellations_total",
    "Cancellation records by outcome (client = a request was cancelled "
    "while queued or in flight, expired = a race-ahead cancel id aged "
    "out of the pending-cancel set without ever matching a request)",
    labels=("model", "reason"),
)
ENGINE_PREEMPTIONS = REGISTRY.counter(
    "engine_preemptions_total",
    "Active requests force-failed by the engine (scheduler error paths)",
    labels=("model",),
)
ENGINE_PROMPT_TOKENS = REGISTRY.counter(
    "engine_prompt_tokens_total",
    "Prompt tokens processed through prefill",
    labels=("model",),
)
ENGINE_GENERATED_TOKENS = REGISTRY.counter(
    "engine_generated_tokens_total",
    "Tokens sampled and emitted to streams",
    labels=("model",),
)
# cross-slot prefix cache (engine/prefix_index.py + kvcopy dispatch)
ENGINE_PREFIX_REUSED_TOKENS = REGISTRY.counter(
    "engine_prefix_reused_tokens_total",
    "Prompt tokens served from KV-resident prefixes instead of prefill "
    "(source: resident = destination slot already held them, copy = "
    "row-to-row on-device copy from another slot, disk = on-disk "
    "prompt cache restore)",
    labels=("model", "source"),
)
ENGINE_PREFIX_COPIES = REGISTRY.counter(
    "engine_prefix_copies_total",
    "On-device cross-slot KV prefix row copies dispatched",
    labels=("model",),
)
ENGINE_PREFIX_EVENTS = REGISTRY.counter(
    "engine_prefix_cache_events_total",
    "Cross-slot prefix cache admission outcomes "
    "(hit_copy/hit_resident/miss/deferred/off)",
    labels=("model", "event"),
)
ENGINE_PROMPT_CACHE_RESTORES = REGISTRY.counter(
    "engine_prompt_cache_restores_total",
    "On-disk prompt cache restore attempts by result (restored/stale/"
    "shape_mismatch/dtype_mismatch/error/skipped_multihost/"
    "skipped_draft/no_file)",
    labels=("model", "result"),
)
ENGINE_KV_RESIDENT_PREFIX = REGISTRY.gauge(
    "engine_kv_resident_prefix_tokens_count",
    "KV-resident reusable prefix tokens across ALL slots (free and "
    "active) — the cross-slot cache's working set",
    labels=("model",),
)
# paged KV pool (engine/kv_pool.py + the paged dispatch paths)
ENGINE_KV_PAGES_IN_USE = REGISTRY.gauge(
    "engine_kv_pages_in_use_count",
    "Distinct KV pool pages currently allocated (arena occupancy; the "
    "trash page is excluded)",
    labels=("model",),
)
ENGINE_KV_PAGES_SHARED = REGISTRY.gauge(
    "engine_kv_pages_shared_count",
    "KV pool pages referenced by more than one slot's page table "
    "(zero-copy prefix shares currently live)",
    labels=("model",),
)
ENGINE_KV_PAGE_ALLOC = REGISTRY.counter(
    "engine_kv_page_alloc_total",
    "KV pool page-allocation events by outcome (fresh = new private "
    "page, shared = table entry added by zero-copy prefix share, cow = "
    "copy-on-write privatization of a shared boundary page, reclaimed "
    "= a free slot's resident prefix dropped under pool pressure, "
    "exhausted = allocation failed even after reclaim)",
    labels=("model", "outcome"),
)
ENGINE_KV_HBM_PER_TOKEN = REGISTRY.gauge(
    "engine_kv_hbm_per_live_token_bytes",
    "KV HBM allocated per live (resident) token — pool pages in use x "
    "page x per-token row bytes / resident tokens; the dense cache "
    "pins this at max_seq/mean_context x the ideal",
    labels=("model",),
)
ENGINE_KV_ROW_BYTES = REGISTRY.gauge(
    "engine_kv_row_bytes",
    "Bytes ONE cached token holds over all layers as stored: the "
    "arena's bytes (row scales, a latent row's zero lanes) over its "
    "token capacity",
    labels=("model",),
)
# tiered KV memory (engine/kv_tier.py): hot HBM pages, warm host-RAM
# pages, cold on-disk sessions
ENGINE_KV_TIER_PAGES = REGISTRY.gauge(
    "engine_kv_tier_pages_count",
    "KV pages resident per tier (hbm = pool pages allocated, host = "
    "spilled pages held in host RAM, disk = pages of cold sessions in "
    "the on-disk prompt-cache format)",
    labels=("model", "tier"),
)
ENGINE_KV_TIER_MOVES = REGISTRY.counter(
    "engine_kv_tier_moves_total",
    "Tier transitions by direction (spill = HBM->host, fetch = "
    "host->HBM, save = host->disk, load = disk->host) and outcome "
    "(ok, dedup = shared page already spilled once, fault = injected/"
    "real DMA failure, aborted = session state changed mid-transfer)",
    labels=("model", "direction", "outcome"),
)
ENGINE_KV_TIER_PREFETCH = REGISTRY.counter(
    "engine_kv_tier_prefetch_total",
    "Returning-session promotion attempts at admission (hit = pages "
    "back in HBM before the prefill slot opened — zero re-prefill, "
    "late = the transfer missed its admission deadline and the request "
    "re-prefilled, miss = no tier entry covered the prompt, expired = "
    "a staged fetch was abandoned before adoption)",
    labels=("model", "result"),
)
ENGINE_KV_TIER_BYTES = REGISTRY.counter(
    "engine_kv_tier_bytes_moved_total",
    "Bytes moved between KV tiers by direction (spill/fetch/save/load; "
    "scale planes included for int8 caches)",
    labels=("model", "direction"),
)
# layer-granular weight paging (engine/weight_pager.py): HBM-hot
# device tree, host-RAM warm pages, cross-engine LRU
ENGINE_WEIGHT_PAGES = REGISTRY.gauge(
    "engine_weight_pages_count",
    "Weight pages resident per tier (hot = on-device layer pages, "
    "warm = host-RAM layer pages; a page counts in both tiers while "
    "the retained host copy backs a promoted device tree)",
    labels=("model", "tier"),
)
ENGINE_WEIGHT_PAGE_MOVES = REGISTRY.counter(
    "engine_weight_page_moves_total",
    "Weight page tier transitions by direction (demote = HBM->host, "
    "promote = host->HBM) and outcome (ok, seed = demote served from "
    "the retained/artifact host copy with zero DMA, fault = injected/"
    "real transfer failure, aborted = new work arrived mid-demotion "
    "and the device tree was kept)",
    labels=("model", "direction", "outcome"),
)
ENGINE_WEIGHT_PREFETCH = REGISTRY.counter(
    "engine_weight_prefetch_total",
    "Warm-model promotion attempts at admission (warm = layer-streamed "
    "prefetch-ahead assembly served the wake-up, cold = the stream "
    "faulted and the blocking full-tree fallback load served it, "
    "fault = a streamed page transfer failed)",
    labels=("model", "result"),
)
ENGINE_MODEL_RESIDENCY = REGISTRY.gauge(
    "engine_model_residency_count",
    "Live engines per weight-residency state across the process (hot "
    "= weights on device, warm = weights paged to host RAM, "
    "transitioning = a demotion or promotion is in flight)",
    labels=("state",),
)
# disaggregated prefill/decode serving (engine/kv_migrate.py)
ENGINE_DISAGG_REQUESTS = REGISTRY.counter(
    "engine_disagg_requests_total",
    "Requests by disaggregation path (disagg = prefilled on the "
    "prefill engine and migrated, local = stayed on the decode engine, "
    "fallback = migration failed and the request re-prefilled on the "
    "decode engine)",
    labels=("model", "path"),
)
ENGINE_KV_MIGRATED_PAGES = REGISTRY.counter(
    "engine_kv_migrated_pages_total",
    "KV pages moved through the prefill->decode migration interchange "
    "by outcome (migrated = adopted by reference on the decode engine, "
    "fault = an injected/real capture or adopt failure, dropped = "
    "captured but abandoned before adoption)",
    labels=("model", "outcome"),
)
ENGINE_KV_MIGRATION = REGISTRY.histogram(
    "engine_kv_migration_seconds",
    "Wall time of the migrate stage per disaggregated request: prefill "
    "terminal to handoff collected on the router thread (D2H gather "
    "landing + content-addressed host publish)",
    labels=("model",),
)
ENGINE_DISAGG_STAGE = REGISTRY.histogram(
    "engine_disagg_stage_seconds",
    "Per-stage wall time of disaggregated requests (queued/prefill on "
    "the prefill engine, migrate on the router, decode from resubmit "
    "to terminal on the decode engine)",
    labels=("model", "stage"),
)
# stall-free mixed prefill+decode dispatch (engine._enqueue_mixed)
ENGINE_MIXED_DISPATCH = REGISTRY.counter(
    "engine_mixed_dispatch_total",
    "Engine-advancing device dispatches by composition (mixed = one "
    "fused step advanced prefill chunks AND decode rows; "
    "prefill_only/decode_only = the dispatch advanced a single phase)",
    labels=("model", "composition"),
)
ENGINE_DECODE_STALL = REGISTRY.histogram(
    "engine_decode_stall_seconds",
    "Gap between consecutive decode-advancing dispatches while at "
    "least one slot was decoding — the scheduler stall the mixed "
    "dispatcher bounds by its token budget",
    labels=("model",), buckets=_STEP_BUCKETS,
)
# ragged paged attention (ops/ragged_paged_attention.py + the
# full-width dispatch discipline in engine.py)
ENGINE_DISPATCH_VARIANTS = REGISTRY.gauge(
    "engine_dispatch_compile_variants_count",
    "Jit dispatch variants precompiled by the last completed engine "
    "warmup pass (one per (fn, shape) pair) — the compile-variant "
    "explosion the ragged paged-attention unification collapses to one "
    "variant per token-budget shape; 0 until warmup runs or when it "
    "was skipped via the persistent-cache marker",
    labels=("model",),
)
ENGINE_RAGGED_ROWS = REGISTRY.counter(
    "engine_ragged_rows_total",
    "Rows advanced through the unified ragged-attention dispatch path "
    "by kind (decode = decode rows, prefill = non-final prompt chunk "
    "rows, final = final prompt chunk rows, verify = spec-decode "
    "verify rows)",
    labels=("model", "kind"),
)

# --------------------------------------------------- pod-scale serving

ENGINE_MESH_DEVICES = REGISTRY.gauge(
    "engine_mesh_devices_count",
    "Devices in the engine's serving mesh (1 for unsharded engines; "
    "data x seq x model axis product otherwise) — the replica's "
    "tensor-parallel footprint, reset to 0 on close",
    labels=("model",),
)
ENGINE_WARMUP_SECONDS = REGISTRY.gauge(
    "engine_warmup_seconds",
    "Wall seconds of the last engine warmup pass by mode (cold = the "
    "dispatch-variant set was compiled, reuse = an identical variant "
    "set was already in the persistent compile cache and the pass was "
    "marker-skipped) — the replica-boot cost tools/profile_boot.py "
    "measures",
    labels=("model", "mode"),
)

# ------------------------------------------------------------ resilience

ENGINE_REQUESTS_SHED = REGISTRY.counter(
    "engine_requests_shed_total",
    "Requests refused at admission by the bounded queue "
    "(queue_full = LOCALAI_MAX_QUEUE exceeded at submit)",
    labels=("model", "reason"),
)
ENGINE_DEADLINE_EXCEEDED = REGISTRY.counter(
    "engine_deadline_exceeded_total",
    "Requests terminated by their deadline, by the stage they were in "
    "when it expired (queued = still in _pending, decode = already "
    "holding a slot)",
    labels=("model", "stage"),
)
FEDERATION_NODE_STATE = REGISTRY.gauge(
    "federation_node_state_count",
    "Registered federation nodes by circuit-breaker state "
    "(closed/open/half_open)",
    labels=("state",),
)
FEDERATION_RETRIES = REGISTRY.counter(
    "federation_retries_total",
    "Federated proxy connect-failure retries by outcome (rerouted = a "
    "later node accepted the request, exhausted = every eligible node "
    "failed before any bytes streamed, midstream = upstream died after "
    "bytes streamed so no retry was possible)",
    labels=("outcome",),
)
FEDERATION_DIGEST_ERRORS = REGISTRY.counter(
    "federation_digest_errors_total",
    "Per-node telemetry digests the balancer rejected, by reason "
    "(fetch = probe GET failed, oversize = body past "
    "LOCALAI_DIGEST_MAX_BYTES, version = unknown DIGEST_VERSION, "
    "malformed = schema violation) — the node's last GOOD digest is "
    "kept with its age; /fleet/metrics and routing never break on a "
    "bad digest",
    labels=("reason",),
)
FEDERATION_ROUTE_LOCALITY = REGISTRY.counter(
    "federation_route_locality_total",
    "Prefix-locality routing decisions by result (hit = picked node "
    "holds the request's fingerprinted prefix per a fresh digest, "
    "miss = no eligible node matched, stale = matches existed only on "
    "stale digests so routing decayed to load-only, off = non-prefix "
    "strategy or no fingerprint chain in the body)",
    labels=("result",),
)
FEDERATION_PREFIX_MATCHED = REGISTRY.counter(
    "federation_prefix_matched_tokens_total",
    "Prefix tokens the balancer routed onto a node already holding "
    "them (gossiped-digest estimate at pick time; the cross-replica "
    "KV reuse the locality strategy buys)",
)
FAULTS_INJECTED = REGISTRY.counter(
    "faults_injected_total",
    "Faults actually delivered by armed LOCALAI_FAULTS injection points "
    "(utils/faultinject.py) — zero outside chaos runs",
    labels=("point",),
)

# -------------------------------------------------------- observability

ENGINE_DEVICE_STEP = REGISTRY.histogram(
    "engine_device_step_seconds",
    "Enqueue-to-ready wall time per harvested device flight by dispatch "
    "kind (mixed/decodek) — host-timed at harvest, when "
    "the flight's arrays are already ready, so the sample costs no "
    "device sync",
    labels=("model", "kind"), buckets=_STEP_BUCKETS,
)
TRACE_SPANS_DROPPED = REGISTRY.counter(
    "trace_spans_dropped_total",
    "Trace entries or span events dropped by the bounded recorder "
    "(active_overflow = still-active trace evicted at active_cap, "
    "ring_evict = finished trace pushed out of the ring, note_cap = "
    "span event past the per-trace annotation cap)",
    labels=("reason",),
)
TIMELINE_RING_EVENTS = REGISTRY.gauge(
    "timeline_ring_events_count",
    "Events currently held by the flight-recorder timeline ring "
    "(telemetry/flightrec.py; exported as Chrome-trace JSON via "
    "GET /debug/timeline)",
)
# the scheduler says what it was doing (telemetry/flightrec.py
# PhaseClock / LoadWatch + the dispatch sites in engine.py)
ENGINE_SCHED_PHASE = REGISTRY.counter(
    "engine_sched_phase_seconds_total",
    "Scheduler-thread SELF time per phase (guards = cancellations + "
    "deadlines, admit = the admission pass with its sched:admit:<part> "
    "sub-spans, harvest, emit = token emission inside harvest, "
    "dispatch = payload building, enqueue = payload -> device arrays "
    "-> launch, state = a recurrent state's snapshot taken or "
    "restored, gauges, wait = nothing ready and nothing to enqueue) — "
    "the phases tile the scheduler's wall time while it has work, so "
    "host time per phase reads over any window with no capture",
    labels=("model", "phase"),
)
ENGINE_SCHED_SPAN = REGISTRY.counter(
    "engine_sched_span_seconds_total",
    "Scheduler-thread SELF time per span NAME (sched:<phase>; "
    "sched:admit:tier = weight pager + KV tier tick, :prefix = prefix "
    "index sync, :place = deferral, tier plan, slot choice, page "
    "headroom, :spill = the tier's capture + adopt, :assign; "
    "sched:enqueue:<kind>) — the same seconds as the phase counter, "
    "split by the full name: summed over a phase's spans they give "
    "that phase",
    labels=("model", "span"),
)
ENGINE_SCHED_STALLS = REGISTRY.counter(
    "engine_sched_stalls_total",
    "Decode stalls — a gap between two decode-advancing dispatches of "
    ">= 0.25 s and >= 3 x the time-weighted running mean gap — by "
    "cause: the span "
    "that held most of the gap (a phase, admit:<part>), load when "
    "program loads took half of it or more, unnamed when no span did",
    labels=("model", "cause"),
)
ENGINE_SCHED_STALL_SECONDS = REGISTRY.counter(
    "engine_sched_stall_seconds_total",
    "Seconds of the gaps counted by engine_sched_stalls_total, by the "
    "same cause",
    labels=("model", "cause"),
)
ENGINE_DEVICE_STARVED = REGISTRY.counter(
    "engine_device_starved_seconds_total",
    "Seconds the device had NO step queued while the engine had work: "
    "from the harvest that emptied the flight queue to the next "
    "enqueue — a lower bound of device idle time, read over any window "
    "with no capture",
    labels=("model",),
)
ENGINE_PROGRAM_LOADS = REGISTRY.counter(
    "engine_program_loads_total",
    "Programs loaded on first use — the first execution in this "
    "process of a (program, input signature): traced, lowered, and "
    "compiled (source = compile), fetched from the persistent compile "
    "cache (cache), or only re-traced (trace) — while the calling "
    "thread, for a dispatch the scheduler and every stream, stood "
    "still. Warmup's loads count here too (in_warmup on "
    "/backend/monitor tells them apart)",
    labels=("model", "kind", "source"),
)
ENGINE_PROGRAM_LOAD_SECONDS = REGISTRY.histogram(
    "engine_program_load_seconds",
    "Wall time the dispatching thread was blocked by one dispatch "
    "that loaded a program (trace + lower + compile or cache fetch + "
    "the launch itself)",
    labels=("model", "kind"),
)
ENGINE_DISPATCH_TOKENS = REGISTRY.counter(
    "engine_dispatch_tokens_total",
    "Token positions dispatched, by kind and part (real = positions "
    "that carry work: decode rows x steps + prompt-chunk tokens; "
    "padded = positions of the program's shape: n_slots + group rows "
    "x bucket for mixed, n_slots x k x depth for decodek) — real / "
    "padded is how full a dispatch was",
    labels=("model", "kind", "part"),
)
ENGINE_ATTN_CONTEXT_TOKENS = REGISTRY.counter(
    "engine_attn_context_tokens_total",
    "Context tokens the attention rows of a dispatch had to read from "
    "the KV cache, summed over rows (a decode row at context c in a "
    "k-step scan adds c + (c+1) + ... + (c+k-1); a prompt chunk of n "
    "tokens at position p adds its causal sum n*p + n(n-1)/2; where "
    "layers have sliding windows, the mean over layers of what each "
    "layer's window lets a query read) — with "
    "engine_decode_steps_total the mean context per decode step",
    labels=("model", "kind"),
)
ENGINE_ATTN_CONTEXT_HELD_TOKENS = REGISTRY.counter(
    "engine_attn_context_held_tokens_total",
    "engine_attn_context_tokens_total with every layer's window "
    "ignored: the context the rows HELD. Equal to it for a model "
    "without sliding windows; their ratio is the share of the held "
    "context that the windows let the attention read",
    labels=("model", "kind"),
)
ENGINE_LATENT_PROMPT_TOKENS = REGISTRY.counter(
    "engine_latent_prompt_tokens_total",
    "Prompt tokens of a latent-attention model dispatched, by the form "
    "their step's program attends them in: expanded (cached rows "
    "up-projected through W_kvb; the flash kernel "
    "ops/latent_flash_attention.py on the kernel route, XLA elsewhere) "
    "or absorbed (W_kvb folded into query and output, "
    "ops/ragged_paged_attention.py) — the rule is "
    "latent_flash_attention.latent_prompt_form, by the row's bucket",
    labels=("model", "form"),
)
ENGINE_EXPERT_TOKENS = REGISTRY.counter(
    "engine_expert_tokens_total",
    "Tokens routed to each expert, summed over the expert layers of "
    "the step programs harvested (decode rows that decode, prompt "
    "positions within a row's chunk; experts_per_token for each)",
    labels=("model", "expert"),
    # a label set an expert: the widest published layers have 512, and
    # the default cap (64) would fold most of 128 into expert="other"
    max_label_sets=2048,
    overflow={"expert": "other"},
)
ENGINE_EXPERT_LAYER_STEPS = REGISTRY.counter(
    "engine_expert_layer_steps_total",
    "Expert layers run by the step programs harvested: the model's "
    "expert layers x the token-steps of a program (k of a k-step scan, "
    "1 of a mixed or single step), by program kind",
    labels=("model", "kind"),
)
ENGINE_EXPERTS_TOUCHED = REGISTRY.counter(
    "engine_experts_touched_total",
    "Experts that had at least one token, summed over the expert "
    "layer-steps of engine_expert_layer_steps_total: the expert "
    "weights a step had to read, in experts",
    labels=("model", "kind"),
)
ENGINE_EXPERT_ASSIGNMENTS = REGISTRY.counter(
    "engine_expert_assignments_total",
    "(token, expert) assignments of a model whose layers hold a SHARE "
    "of the published experts, summed over the expert layers of the "
    "step programs harvested: where=held went to an expert on this "
    "chip (and were computed), where=absent to one held elsewhere "
    "(read nothing, added nothing). held / (held + absent) is the part "
    "of the deployment's routed load this chip carries",
    labels=("model", "where"),
)
ENGINE_EXPERT_DISPATCH_ROWS = REGISTRY.counter(
    "engine_expert_dispatch_rows_total",
    "Sorted (token, expert) rows around the grouped matmul, summed "
    "over the expert layers of the step programs harvested: "
    "kind=slots the rows a step's arrays hold (token rows x "
    "experts_per_token, padding rows too), kind=moved the rows the "
    "dispatch gathered and combined — the held assignments where a "
    "layer that holds a share takes ops/expert_rows.py, every slot "
    "where XLA's gather, mask and un-sort run",
    labels=("model", "kind"),
)
ENGINE_DECODE_STEPS = REGISTRY.counter(
    "engine_decode_steps_total",
    "Decode token-steps dispatched by decode-only programs (k x depth "
    "per k-step scan, 1 per single-step dispatch)",
    labels=("model",),
)
# recurrent state of linear-attention layers (engine/engine.py: the
# state arena rides the cache route; ops/gated_delta.py)
ENGINE_LINEAR_STATE_STEPS = REGISTRY.counter(
    "engine_linear_state_steps_total",
    "Linear-attention layer-steps dispatched by decode-only programs: "
    "the model's linear layers x the token-steps of a program, counted "
    "where engine_decode_steps_total is",
    labels=("model", "kind"),
)
ENGINE_LINEAR_STATE_ROWS = REGISTRY.counter(
    "engine_linear_state_rows_total",
    "Live rows whose recurrent state those layer-steps updated (rows x "
    "layer-steps): / engine_linear_state_steps_total = rows a step",
    labels=("model", "kind"),
)
ENGINE_STATE_SNAPSHOTS = REGISTRY.counter(
    "engine_state_snapshots_total",
    "Recurrent-state snapshots kept for prefix reuse, by op: taken (a "
    "prompt's deepest page boundary), restored (an admission that "
    "reused pages up to one), evicted (its pages went)",
    labels=("model", "op"),
)
ENGINE_STATE_BYTES = REGISTRY.gauge(
    "engine_state_bytes",
    "Bytes of recurrent state held on the device: the arena of every "
    "slot and linear layer plus the snapshot store",
    labels=("model",),
)
ENGINE_DEVICE_FLOPS = REGISTRY.counter(
    "engine_device_flops_total",
    "Device FLOPs accounted per dispatch kind from the warmup-captured "
    "XLA cost model (telemetry/costmodel.py) — accumulated host-side at "
    "dispatch/harvest, zero hot-path syncs",
    labels=("model", "kind"),
)
ENGINE_DEVICE_BYTES = REGISTRY.counter(
    "engine_device_bytes_total",
    "Device bytes accessed (HBM traffic) accounted per dispatch kind "
    "from the warmup-captured XLA cost model",
    labels=("model", "kind"),
)
ENGINE_MFU = REGISTRY.gauge(
    "engine_mfu_ratio",
    "EWMA model-FLOPs-utilization: cost-model FLOPs per harvested "
    "flight divided by (device-step span x peak FLOPs across the mesh)",
    labels=("model",),
)
ENGINE_DISPATCH_PREDICTED = REGISTRY.histogram(
    "engine_dispatch_predicted_seconds",
    "Predicted device time per dispatch from the cost-model device-"
    "time predictor (telemetry/costmodel.py predict_ms) — observed at "
    "harvest next to engine_device_step_seconds, so the two "
    "distributions overlay on one dashboard",
    labels=("model", "kind"), buckets=_STEP_BUCKETS,
)
ENGINE_DISPATCH_PREDICTED_RATIO = REGISTRY.histogram(
    "engine_dispatch_predicted_ratio",
    "Predicted / measured device time per harvested dispatch — the "
    "predictor's live calibration error (1.0 = perfect; drift away "
    "from 1 means the per-kind calibration EWMA is stale)",
    labels=("model", "kind"),
    buckets=(0.125, 0.25, 0.5, 0.8, 1.0, 1.25, 2.0, 4.0, 8.0),
)
ENGINE_HBM_BYTES = REGISTRY.gauge(
    "engine_hbm_bytes",
    "Component-level HBM ledger (telemetry/hbm_ledger.py): bytes "
    "attributed to weights / kv_arena / kv_scales / draft_cache / "
    "staging / sampler, plus an 'unattributed' drift row reconciled "
    "against device.memory_stats()",
    labels=("model", "component"),
)
DEVICE_HBM_USED = REGISTRY.gauge(
    "device_hbm_used_bytes",
    "Per-device bytes_in_use from device.memory_stats(), synced "
    "periodically by utils/sysinfo.update_memory_gauges()",
    labels=("device",), max_label_sets=256,
)
PROCESS_RSS = REGISTRY.gauge(
    "process_rss_bytes",
    "Resident set size of the serving process (host RAM pressure; "
    "includes the KV host-spill tier)",
)

# ---------------------------------------------------------------- loader

MODEL_LOADS = REGISTRY.counter(
    "model_loads_total",
    "Backend model loads by outcome",
    labels=("model", "result"),
)
MODEL_LOAD_PHASE = REGISTRY.counter(
    "model_load_phase_seconds_total",
    "Cumulative load wall time by phase (models/load_timing.py)",
    labels=("phase",),
)
MODEL_EVICTIONS = REGISTRY.counter(
    "model_evictions_total",
    "Model unloads by reason (api/watchdog/single_active/shutdown)",
    labels=("reason",),
)
MODELS_LOADED = REGISTRY.gauge(
    "models_loaded_count",
    "Live loaded backends",
)

# --------------------------------------------------------------- workers

MODELS_BUSY = REGISTRY.gauge(
    "models_busy_count",
    "Loaded backends currently serving at least one request",
)
WATCHDOG_KILLS = REGISTRY.counter(
    "watchdog_kills_total",
    "Models killed by the busy/idle watchdog",
    labels=("kind",),
)

# ------------------------------------------------------------- error hygiene

RECOVERED_ERRORS = REGISTRY.counter(
    "recovered_errors_total",
    "Recoverable failures that were caught and absorbed on a degraded "
    "path (labelled by site). Before graftlint's except-swallow rule "
    "these were silent `except Exception` swallows; now every recovery "
    "is at least counted, so a spike is visible on /metrics instead of "
    "surfacing as mystery behavior",
    labels=("site",),
    max_label_sets=64,
)
