"""Expert layer: device self time of the routed dispatch of
models/transformer.py ``_moe_mlp`` over device busy time, in percent.

The capture names an op by its whole HLO line and carries no name
stack, so the ops are found by what only the expert layer has:
  the grouped matmuls   ops whose own name starts with one of the model
                        file's ``EXPERT_KERNELS`` (``lax.ragged_dot`` is
                        XLA's ``ragged-dot*`` kernel and its set-up op)
  the combine           ops with a value shaped [tokens, k, hidden] —
                        the k expert outputs of a token brought back to
                        token order and summed under their weights
The router, the sort and the gather of the rows into expert order are
small and carry no shape of their own, so they are left out and the
share reads low rather than high. A model file without
``EXPERT_KERNELS`` (a type with no experts) has nothing to read."""
import re

from benchmark.lib import models
from benchmark.lib import trace as T


def matcher(config):
    """-> f(event name) for the ops counted, or None."""
    kernels = tuple(getattr(models.of(config), "EXPERT_KERNELS", ()))
    if not kernels:
        return None
    combine = re.compile(rf"\[\d+,{config['num_experts_per_tok']},"
                         rf"{config['hidden_size']}\]")

    def match(name):
        own = T.own_name(name)
        return not own.startswith("while") and (
            own.startswith(kernels) or bool(combine.search(name)))
    return match


def reduce(trace, run):
    if trace is None:
        return None
    match = matcher(run["config"])
    if match is None:
        return None
    pl = T.chip_planes(trace)[0]
    ns = sum(self_ns for e, self_ns in T.self_times(T.events(pl, T.OPS))
             if match(e[0]))
    if ns == 0:
        return None
    busy, _ = T.busy_and_window(trace)
    return 100.0 * ns / 1e9 / busy
