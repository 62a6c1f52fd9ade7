"""Declarative registry for every ``LOCALAI_*`` environment knob.

Every env knob the framework reads is declared HERE — name, default,
parser kind and a one-line doc — and read through the typed accessors
(:func:`flag` / :func:`int_` / :func:`float_` / :func:`str_` /
:func:`raw` / :func:`present`). The graftlint ``env-knob-registry``
rule forbids raw ``os.environ["LOCALAI_..."]`` access anywhere else in
the package and cross-checks this registry against the README
"Configuration knobs" table, so a knob cannot ship undocumented and a
typo'd knob name cannot silently read its default forever.

Accessors read ``os.environ`` at CALL time (no import-time caching):
tests and operators mutate the environment between engine constructions
and every layer must observe the current value.

The ``ApplicationConfig`` layer (``config/app_config.py``) is the one
deliberate exception: it maps computed CLI-flag names onto
``LOCALAI_<FLAG>`` aliases generically and stays outside this registry
(and outside the lint rule's scope, which exempts ``config/``).
"""

from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = [
    "Knob", "REGISTRY", "flag", "int_", "float_", "str_", "raw",
    "present", "markdown_rows",
]


@dataclass(frozen=True)
class Knob:
    name: str
    default: str  # raw env-string default, shown verbatim in the README
    kind: str  # "flag" | "int" | "float" | "str"
    doc: str


REGISTRY: dict[str, Knob] = {}


def _knob(name: str, default: str, kind: str, doc: str) -> None:
    if name in REGISTRY:
        raise ValueError(f"duplicate knob registration: {name}")
    REGISTRY[name] = Knob(name, default, kind, doc)


# --------------------------------------------------------------- engine
_knob("LOCALAI_PAGED_KV", "on", "flag",
      "Paged KV arena (vs the dense per-slot cache).")
_knob("LOCALAI_KV_PAGE", "0", "int",
      "KV page-size override: power of two >= 8 dividing max_seq "
      "(0 = auto, largest <= 256).")
_knob("LOCALAI_KV_PAGES", "0", "int",
      "Physical page-count override (0 = n_slots * pages_per_slot + 1).")
_knob("LOCALAI_PREFIX_CACHE", "on", "flag",
      "Cross-request prefix KV reuse (copy a resident shared prefix "
      "instead of re-prefilling).")
_knob("LOCALAI_PREFIX_CACHE_MIN", "8", "int",
      "Minimum token GAIN over the destination's own resident prefix "
      "before a prefix copy dispatches.")
_knob("LOCALAI_PREFIX_CACHE_DEFER_MIN", "64", "int",
      "Minimum shared-prefix length before a same-wave request defers "
      "behind a wave-mate's prefill.")
_knob("LOCALAI_REQUEST_DEADLINE_S", "0", "float",
      "Default per-request deadline in seconds (0 = off; a request's "
      "own timeout_s overrides).")
_knob("LOCALAI_MAX_QUEUE", "0", "int",
      "Admission queue cap — submit_many sheds beyond it with a "
      "terminal \"shed\" event (0 = unbounded).")
_knob("LOCALAI_KV_TIER", "on", "flag",
      "Tiered KV memory: async host-RAM spill + prefetch for resident "
      "sessions (single-host paged engines).")
_knob("LOCALAI_DECODE_KERNEL", "auto", "str",
      "Fused Pallas decode kernel: auto (on where mosaic compiles), "
      "0/off to force XLA, 1/on to force the kernel.")
_knob("LOCALAI_WARMUP_REUSE", "on", "flag",
      "Skip the warmup pass when the persistent compile-cache marker "
      "for the variant set exists.")
_knob("LOCALAI_PREFIX_SUMMARY_S", "1", "float",
      "Scheduler refresh interval for the prefix-index top-k summary "
      "gossiped in telemetry digests, in seconds.")

# -------------------------------------------------------------- kv tier
_knob("LOCALAI_KV_TIER_HOST_MB", "256", "float",
      "Host-RAM budget for spilled KV pages, in MiB.")
_knob("LOCALAI_KV_TIER_WATERMARK", "0.85", "float",
      "Host-tier fill fraction that triggers cold-tier demotion "
      "(clamped to [0.05, 1.0]).")
_knob("LOCALAI_KV_TIER_IDLE_S", "1", "float",
      "Session idle seconds before its pages become spill candidates.")
_knob("LOCALAI_KV_TIER_COLD_S", "30", "float",
      "Host-tier residency seconds before a spilled page may demote "
      "to the cold dir.")
_knob("LOCALAI_KV_TIER_FETCH_DEADLINE_S", "2", "float",
      "Deadline for a staged prefetch before the request falls back "
      "to re-prefill.")
_knob("LOCALAI_KV_TIER_DIR", "", "str",
      "Cold-tier spill directory ('' disables the disk tier).")
_knob("LOCALAI_KV_TIER_INFLIGHT_MB", "64", "float",
      "In-flight spill transfer window, in MiB.")

# --------------------------------------------------------- weight paging
_knob("LOCALAI_WEIGHT_PAGING", "on", "flag",
      "Layer-granular weight paging: idle models demote their weights "
      "to host RAM and promote back on demand, so dozens of gallery "
      "models share one chip (single-chip engines; meshed/follower/"
      "draft/disagg engines force it off). off is byte-identical to "
      "the fully-resident path.")
_knob("LOCALAI_WEIGHT_HBM_MB", "0", "float",
      "Cross-engine HBM budget for hot (device-resident) weights, in "
      "MiB — the process-wide LRU demotes the least-recently-used "
      "model's weights to host RAM when the hot set exceeds it "
      "(0 = unlimited: models only demote via the watchdog or an "
      "explicit demote_weights call).")
_knob("LOCALAI_WEIGHT_PREFETCH_AHEAD", "2", "int",
      "Layer pages kept in flight ahead of the promotion commit "
      "cursor (double-buffer depth of the warm->hot layer stream).")
_knob("LOCALAI_WEIGHT_INFLIGHT_MB", "256", "float",
      "In-flight device->host transfer window during weight demotion, "
      "in MiB.")
_knob("LOCALAI_WATCHDOG_DEMOTE", "off", "flag",
      "Watchdog idle handling demotes a model's weights to host RAM "
      "(keeping registry/tokenizer/engine state) instead of shutting "
      "the model down — the next request pays a warm promotion, not a "
      "cold load.")

# ------------------------------------------------- disaggregated serving
_knob("LOCALAI_DISAGG", "off", "flag",
      "Disaggregated prefill/decode serving: a second prefill-tuned "
      "engine runs long prompts and migrates finished KV pages to the "
      "decode engine (engine/kv_migrate.py); off is byte-identical "
      "single-engine serving.")
_knob("LOCALAI_DISAGG_MIN_PROMPT", "256", "int",
      "Minimum prompt tokens before a request takes the disaggregated "
      "path; shorter prompts stay on the decode engine.")
_knob("LOCALAI_DISAGG_MIN_MS", "0", "float",
      "Minimum PREDICTED prefill milliseconds (cost-model "
      "prefill_token_ms x prompt tokens) before disaggregating; 0 "
      "routes on prompt length alone.")
_knob("LOCALAI_DISAGG_MIGRATE_DEADLINE_S", "5", "float",
      "Budget for the migrate stage (prefill terminal to adopted "
      "handoff) before the request falls back to re-prefill on the "
      "decode engine.")
_knob("LOCALAI_DISAGG_PREFILL_SLOTS", "2", "int",
      "Slot count for the prefill-side engine (it holds at most this "
      "many prompts in flight; each finishes at its first token).")

# ------------------------------------------------------------ dispatch
_knob("LOCALAI_PREFILL_GROUP_TOKENS", "8192", "int",
      "Token budget per fused prefill/mixed dispatch — bounds the "
      "[B, H, T, window] score materialization so big-bucket groups "
      "cannot OOM at compile.")
_knob("LOCALAI_COST_SCHED", "on", "flag",
      "Cost-model-driven scheduling: predicted device time packs "
      "dispatches and drives admission/deadline decisions; off "
      "restores the pure token-budget scheduler.")
_knob("LOCALAI_ITL_BUDGET_MS", "0", "float",
      "Explicit inter-token-latency budget in ms: mixed/decode "
      "dispatches are sized so their PREDICTED device time fits it "
      "(0 = token-budget sizing only).")
_knob("LOCALAI_WARMUP", "on", "flag",
      "Precompile the dispatch-variant set at model load (leader/"
      "single-host roles only).")
_knob("LOCALAI_NATIVE", "on", "flag",
      "Build the native hot-path libraries (grammar/store) at startup.")
_knob("LOCALAI_NATIVE_GBNF", "on", "flag",
      "Use the native GBNF grammar library when built.")
_knob("LOCALAI_NATIVE_STORE", "on", "flag",
      "Use the native vector store when built.")

# ---------------------------------------------------------------- quant
_knob("LOCALAI_QUANT_ARTIFACTS", "on", "flag",
      "Persist/reuse int8 quantization artifacts on disk.")
_knob("LOCALAI_QUANT_CACHE_DIR", "", "str",
      "Quant-artifact cache root ('' = $XDG_CACHE_HOME/localai_tpu/"
      "quant).")
_knob("LOCALAI_QUANT_CACHE_MAX_GB", "50", "float",
      "Quant-artifact cache size budget in GB (LRU-pruned).")
_knob("LOCALAI_COMMIT_INFLIGHT_MB", "1024", "int",
      "In-flight host->device transfer window during weight commit, "
      "in MiB.")

# ------------------------------------------------------------ telemetry
_knob("LOCALAI_TIMELINE", "on", "flag",
      "Flight-recorder timeline event capture.")
_knob("LOCALAI_TIMELINE_EVENTS", "8192", "int",
      "Flight-recorder ring capacity in events (min 64).")
_knob("LOCALAI_COSTMODEL", "on", "flag",
      "Warmup-captured XLA cost model: per-dispatch FLOPs/bytes "
      "accounting and the MFU gauge (telemetry/costmodel.py).")
_knob("LOCALAI_HBM_LEDGER", "on", "flag",
      "Component-level HBM byte ledger with memory_stats "
      "reconciliation and OOM post-mortems (telemetry/hbm_ledger.py).")
_knob("LOCALAI_PROFILER", "off", "flag",
      "Enable the on-demand GET /debug/profile jax.profiler capture "
      "endpoint.")
_knob("LOCALAI_PROFILER_MAX_S", "30", "float",
      "Upper bound on a single /debug/profile capture duration, in "
      "seconds.")
_knob("LOCALAI_PEAK_FLOPS", "0", "float",
      "Per-device peak FLOP/s for MFU/roofline accounting (0 = "
      "built-in per-platform table).")
_knob("LOCALAI_PEAK_HBM_GBS", "0", "float",
      "Per-device peak memory bandwidth in GB/s for roofline "
      "classification (0 = built-in per-platform table).")

# ------------------------------------------------------- multihost/fleet
_knob("LOCALAI_COORDINATOR", "", "str",
      "jax.distributed coordinator address (alias of "
      "JAX_COORDINATOR_ADDRESS).")
_knob("LOCALAI_NUM_HOSTS", "", "int",
      "jax.distributed process count (presence-gated: unset/empty "
      "defers to JAX).")
_knob("LOCALAI_HOST_ID", "", "int",
      "jax.distributed process id (presence-gated: unset/empty defers "
      "to JAX; 0 is meaningful).")
_knob("LOCALAI_FED_BREAKER_FAILS", "3", "int",
      "Consecutive upstream failures that open a federation circuit "
      "breaker.")
_knob("LOCALAI_FED_BREAKER_BASE_S", "1", "float",
      "Federation breaker backoff base seconds.")
_knob("LOCALAI_FED_BREAKER_CAP_S", "30", "float",
      "Federation breaker backoff cap seconds.")
_knob("LOCALAI_FED_PROBE_S", "5", "float",
      "Federation half-open probe interval seconds.")
_knob("LOCALAI_P2P_TOKEN", "", "str",
      "Federation join token (falls back to TOKEN).")
_knob("LOCALAI_DIGEST_MAX_BYTES", "4096", "int",
      "Encoded-size cap for per-node telemetry digests "
      "(telemetry/digest.py): builders shed prefix/model detail to "
      "fit, the balancer rejects larger bodies as oversize.")
_knob("LOCALAI_DIGEST_TOPK", "16", "int",
      "Prefix-hash entries carried in the digest's top-k summary "
      "(0 disables prefix gossip).")
_knob("LOCALAI_DIGEST_STALE_S", "60", "float",
      "Age past which a node's digest counts as stale on /fleet/* "
      "(fleet_digest_stale_count; the data still serves with its "
      "age attached).")
_knob("LOCALAI_SLO_TTFT_P95_MS", "2000", "float",
      "Fleet SLO: 95% of requests must see first token under this "
      "many ms (burn-rate monitored on /fleet/slo).")
_knob("LOCALAI_SLO_ITL_P99_MS", "200", "float",
      "Fleet SLO: 99% of inter-token gaps must be under this many ms.")
_knob("LOCALAI_SLO_AVAILABILITY", "0.99", "float",
      "Fleet SLO: target fraction of registered nodes serving "
      "(online, no outstanding probe failure).")
_knob("LOCALAI_SLO_FAST_WINDOW_S", "300", "float",
      "Fast burn-rate window seconds for the fleet SLO monitor.")
_knob("LOCALAI_SLO_SLOW_WINDOW_S", "3600", "float",
      "Slow burn-rate window seconds for the fleet SLO monitor.")
_knob("LOCALAI_SLO_BURN_WARN", "6", "float",
      "Burn rate (error rate / budget) at which BOTH windows flip an "
      "objective to warning.")
_knob("LOCALAI_SLO_BURN_CRIT", "14.4", "float",
      "Burn rate at which BOTH windows flip an objective to critical "
      "(the classic 30-day-budget-in-2-days threshold).")
_knob("LOCALAI_FED_STRATEGY", "prefix", "str",
      "Default federated pick strategy: prefix (locality-scored), "
      "least-used (byte-identical legacy pick), or random.")
_knob("LOCALAI_ROUTE_ALPHA", "0.01", "float",
      "Routing score weight per matched prefix token (the locality "
      "term of score = a*match - b*drain - g*pressure).")
_knob("LOCALAI_ROUTE_BETA", "1", "float",
      "Routing score weight per predicted drain second.")
_knob("LOCALAI_ROUTE_GAMMA", "1", "float",
      "Routing score weight per unit queue pressure (in_flight plus "
      "digest-reported queue depth over slots).")
_knob("LOCALAI_SCALE_UP_QW_MS", "500", "float",
      "Autoscaler scale-up trigger: windowed fleet queue-wait p90 "
      "above this many ms (0 disables scale-up).")
_knob("LOCALAI_SCALE_MIN", "1", "int",
      "Autoscaler lower bound on serving replicas.")
_knob("LOCALAI_SCALE_MAX", "8", "int",
      "Autoscaler upper bound on serving replicas.")
_knob("LOCALAI_SCALE_TICK_S", "0", "float",
      "Autoscaler evaluation interval seconds (0 = the federation "
      "probe interval).")
_knob("LOCALAI_SCALE_COOLDOWN_S", "30", "float",
      "Cooldown seconds after any scale action (or failed attempt) "
      "before the autoscaler acts again.")
_knob("LOCALAI_SCALE_HYSTERESIS", "2", "int",
      "Consecutive autoscaler ticks a scale signal must persist "
      "before acting.")
_knob("LOCALAI_SCALE_DOWN_MFU", "0.05", "float",
      "Fleet mean MFU below which (with occupancy also under floor) "
      "scale-down is considered.")
_knob("LOCALAI_SCALE_DOWN_OCC", "0.25", "float",
      "Fleet busy-slot fraction below which scale-down is considered.")
_knob("LOCALAI_SCALE_DRAIN_TIMEOUT_S", "60", "float",
      "Max seconds to wait for a draining scale-down victim to empty "
      "before the kill proceeds anyway.")
_knob("LOCALAI_GALLERIES", "", "str",
      "JSON gallery list (falls back to GALLERIES).")

# -------------------------------------------------------------- workers
_knob("LOCALAI_TINY_DIFFUSION", "off", "flag",
      "Force the tiny random-init diffusion pipeline (tests/smoke).")
_knob("LOCALAI_KEEP_FRAMES", "off", "flag",
      "Keep intermediate PNG frames after ffmpeg video assembly.")

# ------------------------------------------------------------ debugging
_knob("LOCALAI_FAULTS", "", "str",
      "Deterministic fault-injection spec, e.g. "
      "\"engine.device_step:fail@3\" (utils/faultinject.py).")
_knob("LOCALAI_SAN", "off", "flag",
      "Arm graftsan, the lockdep-style runtime sanitizer "
      "(tools/lint/sanitizer.py).")


_TRUE = frozenset({"1", "true", "on", "yes"})
_FALSE = frozenset({"", "0", "false", "off", "no"})


def raw(name: str) -> str:
    """The raw env string, or the registered default when unset."""
    return os.environ.get(name, REGISTRY[name].default)


def present(name: str) -> bool:
    """True when the knob is set to a non-empty string (for knobs where
    an explicit 0 differs from unset, e.g. LOCALAI_HOST_ID)."""
    REGISTRY[name]  # typo guard
    return bool(os.environ.get(name))


def flag(name: str) -> bool:
    v = raw(name).strip().lower()
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    return REGISTRY[name].default.strip().lower() in _TRUE


def int_(name: str) -> int:
    k = REGISTRY[name]
    try:
        return int(raw(name) or k.default or 0)
    except ValueError:
        try:
            return int(k.default or 0)
        except ValueError:
            return 0


def float_(name: str) -> float:
    k = REGISTRY[name]
    try:
        return float(raw(name) or k.default or 0.0)
    except ValueError:
        try:
            return float(k.default or 0.0)
        except ValueError:
            return 0.0


def str_(name: str) -> str:
    return raw(name)


def markdown_rows() -> list[str]:
    """One README table row per knob (the env-knob-registry lint rule
    checks each knob appears in the README; tests regenerate the table
    from here)."""
    out = []
    for k in sorted(REGISTRY.values(), key=lambda k: k.name):
        default = k.default if k.default != "" else "*(unset)*"
        out.append(f"| `{k.name}` | {k.kind} | `{default}` | {k.doc} |")
    return out
