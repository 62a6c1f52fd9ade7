"""Expert layer: the expert weights its grouped matmuls had to read in
the decode-only programs, over peak HBM bytes per second, over the
device time those matmuls took, in percent. Bound named: memory (at a
decode step's row count each expert sees one or two rows).

Bytes per expert layer-step: the experts that had a token, COUNTED by
the program as it harvests each step — Δengine_experts_touched_total ÷
Δengine_expert_layer_steps_total of the decode-only kinds between the
two scrapes that bracket the capture most tightly (as
``attn_kernel_roofline_counted`` brackets it) — times the bytes of one
expert as served (the model file's ``expert_bytes``). Layer-steps in
the capture: its decode token-steps x the configuration's expert
layers. Time: the self time of the ops named by the model file's
``EXPERT_KERNELS`` inside the decode-only programs. A program without
the counters, or a model file without experts, has nothing to read."""
from benchmark.lib import models, prom
from benchmark.lib import trace as T

TOUCHED = "engine_experts_touched_total"
LAYER_STEPS = "engine_expert_layer_steps_total"
DECODE_KINDS = {"kind": ["decodek", "decode1"]}


def reduce(trace, run):
    prof = run.get("profile")
    if trace is None or not prof or not run.get("peaks"):
        return None
    mod = models.of(run["config"])
    kernels = tuple(getattr(mod, "EXPERT_KERNELS", ()))
    if not kernels or not hasattr(mod, "expert_bytes"):
        return None
    before, after = prof.get("before"), prof.get("after")
    ends = prof.get("t_before", 0.0) + prof.get("duration", 0.0)
    if run.get("metrics_after") is not None \
            and 0.0 <= run.get("seconds", -1.0) - ends <= 2.0:
        after = run["metrics_after"]
    if before is None or after is None or TOUCHED not in after:
        return None
    layer_steps = prom.delta(before, after, LAYER_STEPS, DECODE_KINDS)
    if layer_steps <= 0:
        return None
    touched = prom.delta(before, after, TOUCHED, DECODE_KINDS) / layer_steps
    mods = T.module_events(trace, T.DECODE)
    steps, _seconds = T.decode_steps(trace, run["config"])
    inside = T.ops_inside(
        trace, mods, lambda name: T.own_name(name).startswith(kernels))
    ns = sum(self_ns for _e, self_ns in T.self_times(inside))
    if not steps or ns == 0:
        return None
    nbytes = touched * mod.expert_bytes(run["config"])
    floor_s = steps * mod.expert_layers(run["config"]) * nbytes \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ns / 1e9)
