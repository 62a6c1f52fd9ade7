"""Dispatch kinds: device time of the decode-only programs
(jit_dispatch_decodek, jit_dispatch_decode1) per token-step they ran.
A decodek program runs k steps; the steps are counted from the trace
itself — attention-kernel calls inside those programs / layers."""
from benchmark.lib import trace as T


def reduce(trace, run):
    if trace is None:
        return None
    steps, seconds = T.decode_steps(trace, run["config"])
    return None if not steps else seconds * 1e3 / steps
