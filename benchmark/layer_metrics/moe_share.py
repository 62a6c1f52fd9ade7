"""Expert layer: device self time of the ops of models/transformer.py
``_moe_mlp`` over device busy time, in percent.

The capture names an op by its whole HLO line and carries no name
stack, so the ops are found by the shapes only the expert layer has —
E experts x F intermediate x D hidden from the configuration:
  expert weight stacks      [E,D,F] / [E,F,D]       (the einsums
                            ``btd,edf->btef`` and ``btef,efd->bted``)
  per-expert activations    [..,E,F]                (act(g) * u)
  per-expert outputs        [..,E,D] as a 4-D value (``bted,bte->btd``)
An op counts when its result or an operand has one of those shapes.
The router (``btd,de->bte``, [..,E]) is too small to matter and is left
out, so the share reads low rather than high."""
import re

from benchmark.lib import trace as T


def pattern(config):
    e, f, d = (config["num_local_experts"], config["intermediate_size"],
               config["hidden_size"])
    return re.compile(
        rf"\[(?:\d+,)*{e},{d},{f}\]|\[(?:\d+,)*{e},{f},{d}\]"
        rf"|\[\d+(?:,\d+)*,{e},{f}\]|\[\d+,\d+,{e},{d}\]")


def reduce(trace, run):
    if trace is None or not run["config"].get("num_local_experts"):
        return None
    pat = pattern(run["config"])
    pl = T.chip_planes(trace)[0]
    ns = sum(self_ns for e, self_ns in T.self_times(T.events(pl, T.OPS))
             if pat.search(e[0]) and not T.own_name(e[0]).startswith("while"))
    if ns == 0:
        return None
    busy, _ = T.busy_and_window(trace)
    return 100.0 * ns / 1e9 / busy
