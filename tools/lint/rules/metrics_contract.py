"""metrics-contract: metric naming + README coverage + required set.

The lint-framework port of ``tools/check_metrics.py`` (whose CLI now
wraps this rule). Every literal registry registration
(``REGISTRY.counter("...")`` / ``.gauge`` / ``.histogram``) must

- be snake_case,
- carry a unit suffix (counters ``_total``; histograms ``_seconds`` /
  ``_bytes``/``_ratio``; gauges ``_seconds``/``_bytes``/``_count``/
  ``_ratio``/``_info``, or a ``<unit>_per_<x>`` rate),
- appear as `` `name` `` in the README Observability table, and
- a computed (non-literal) name is itself a finding: it can be neither
  linted nor documented.

``REQUIRED_FAMILIES`` must all stay registered — deleting one silently
breaks dashboards and the bench's extra blocks. Repo-wide checks
(required set, empty-scan guard, README coverage without an explicit
readme) only run on full-package scans so fixture tests stay hermetic.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator

from ..core import Context, Finding

_SNAKE = re.compile(r"^[a-z][a-z0-9]*(_[a-z0-9]+)*$")

SUFFIXES = {
    "counter": ("_total",),
    "histogram": ("_seconds", "_bytes", "_ratio"),
    "gauge": ("_seconds", "_bytes", "_count", "_ratio", "_info"),
}

# rate/intensity gauges: unit suffix + `_per_<x>` qualifier
# (Prometheus bytes_per_second convention) is also valid
_PER_GAUGE = re.compile(r"_(seconds|bytes|count)_per_[a-z0-9_]+$")

# families that MUST exist (removing one silently breaks dashboards
# and the bench's extra blocks)
REQUIRED_FAMILIES = {
    "engine_kv_pages_in_use_count",
    "engine_kv_pages_shared_count",
    "engine_kv_page_alloc_total",
    "engine_kv_hbm_per_live_token_bytes",
    "engine_kv_tier_pages_count",
    "engine_kv_tier_moves_total",
    "engine_kv_tier_prefetch_total",
    "engine_kv_tier_bytes_moved_total",
    "engine_weight_pages_count",
    "engine_weight_page_moves_total",
    "engine_weight_prefetch_total",
    "engine_model_residency_count",
    "engine_disagg_requests_total",
    "engine_kv_migrated_pages_total",
    "engine_kv_migration_seconds",
    "engine_disagg_stage_seconds",
    "engine_dispatch_compile_variants_count",
    "engine_ragged_rows_total",
    "engine_mesh_devices_count",
    "engine_warmup_seconds",
    "engine_requests_shed_total",
    "engine_deadline_exceeded_total",
    "federation_node_state_count",
    "federation_retries_total",
    "federation_digest_errors_total",
    "federation_route_locality_total",
    "federation_prefix_matched_tokens_total",
    "fleet_replicas_desired_count",
    "fleet_scale_events_total",
    "fleet_ttft_seconds",
    "fleet_itl_seconds",
    "fleet_queue_wait_seconds",
    "fleet_node_queue_depth_count",
    "fleet_node_slots_busy_count",
    "fleet_node_mfu_ratio",
    "fleet_node_hbm_bytes",
    "fleet_node_predicted_drain_seconds",
    "fleet_digest_age_seconds",
    "fleet_digest_stale_count",
    "fleet_slo_burn_rate_ratio",
    "fleet_slo_state_info",
    "faults_injected_total",
    "engine_device_step_seconds",
    "trace_spans_dropped_total",
    "timeline_ring_events_count",
    "engine_device_flops_total",
    "engine_device_bytes_total",
    "engine_mfu_ratio",
    "engine_dispatch_predicted_seconds",
    "engine_dispatch_predicted_ratio",
    "engine_hbm_bytes",
    "device_hbm_used_bytes",
    "process_rss_bytes",
    "engine_sched_phase_seconds_total",
    "engine_sched_span_seconds_total",
    "engine_sched_stalls_total",
    "engine_sched_stall_seconds_total",
    "engine_device_starved_seconds_total",
    "engine_program_loads_total",
    "engine_program_load_seconds",
    "engine_dispatch_tokens_total",
    "engine_attn_context_tokens_total",
    "engine_decode_steps_total",
}

_METRICS_MODULE = "localai_tfp_tpu/telemetry/metrics.py"


def find_registrations(ctx: Context):
    """(kind, name, module, line) for every literal registration, plus
    (module, line) for computed names."""
    regs, computed = [], []
    for m in ctx.modules:
        for node in ast.walk(m.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in SUFFIXES):
                continue
            # skip unrelated attr calls with no args (e.g. obj.gauge())
            if not node.args:
                continue
            name = node.args[0]
            if isinstance(name, ast.Constant) \
                    and isinstance(name.value, str):
                regs.append((node.func.attr, name.value, m, node.lineno))
            else:
                computed.append((node.func.attr, m, node.lineno))
    return regs, computed


class MetricsContract:
    id = "metrics-contract"
    doc = ("metric registration violates the naming/README contract "
           "(snake_case, unit suffix, Observability table row)")

    def check(self, ctx: Context) -> Iterator[Finding]:
        regs, computed = find_registrations(ctx)
        full = ctx.module(_METRICS_MODULE) is not None
        for kind, m, line in computed:
            yield m.finding(
                self.id, line,
                f".{kind}() registration with a computed name — literal "
                "names only (a computed name cannot be linted or "
                "documented)")
        readme = ctx.readme_text
        for kind, name, m, line in regs:
            if not _SNAKE.match(name):
                yield m.finding(self.id, line,
                                f"metric '{name}' is not snake_case")
            if not name.endswith(SUFFIXES[kind]) and not (
                    kind == "gauge" and _PER_GAUGE.search(name)):
                yield m.finding(
                    self.id, line,
                    f"{kind} '{name}' lacks a unit suffix (one of "
                    f"{', '.join(SUFFIXES[kind])})")
            if (readme or full) and f"`{name}`" not in readme:
                yield m.finding(
                    self.id, line,
                    f"metric '{name}' is not documented in the "
                    f"README.md Observability table (add a `{name}` "
                    "row)")
        if full:
            main = ctx.module(_METRICS_MODULE)
            if not regs:
                yield main.finding(
                    self.id, 1,
                    "no metric registrations found under "
                    "localai_tfp_tpu/ — scanner or layout broke")
            missing = REQUIRED_FAMILIES - {n for _, n, _, _ in regs}
            for name in sorted(missing):
                yield main.finding(
                    self.id, 1,
                    f"required metric family '{name}' is not "
                    "registered anywhere under localai_tfp_tpu/")
