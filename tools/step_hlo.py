"""What one step program's layer loop does, op by op, as the TPU's
compiler wrote it — no chip, no weights.

  python tools/step_hlo.py --config benchmark/configs/<name>.json \
      --kind decodek|mixed [--k 8] [--rows 1] [--bucket N] \
      [--min-mb 1] [--all] [--dump FILE | --hlo FILE] [--attached]

builds the engine's own ``dispatch_<kind>`` program for one
configuration of the benchmark (its ``serving`` block: quantization,
KV dtype, slots, context; the default page pool) over ABSTRACT arrays,
compiles it for a described (not attached) v5e chip, and prints every
op of every ``while`` body of the optimized HLO with its output shape,
output bytes and the compiler's own cycle estimate — loop bodies
outermost first, each with the parameter leaves it reads. Ops
that write at least ``--min-mb`` are listed; ``--all`` lists every op.
Ops that ``offenders`` names (below) are marked ``<<``: an op that is
neither a dot / convolution fusion nor a Pallas call and writes a
weight-sized array of a weight's dtype — a layer's matrix sliced out
of its stack, or copied into another layout, on every layer of every
step (PERF.md §5, PR 44). ``tests/test_step_hlo.py`` holds the three
configurations' programs to an empty list.

Nothing here is a time: cycles are the compiler's estimate for a
described device. A time comes from a chip run (``benchmark/run.py
--trace 1``, ``breakdown.device_ops``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
from typing import NamedTuple, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KINDS = ("decodek", "mixed")
MIN_BYTES = 1 << 20


def describe_v5e():
    """One described v5e chip as a sharding. Only one process may hold
    libtpu: call this from a script's main or a test's fixture, never
    at import."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs to /tmp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _attached():
    import jax
    from jax.sharding import SingleDeviceSharding

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"--attached: no TPU here ({dev.platform})")
    return SingleDeviceSharding(dev)


# ---------------------------------------------------------------------------
# the program, as the engine builds it, over abstract arrays
# ---------------------------------------------------------------------------

def _abstract(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def serving_shapes(config: dict, sharding) -> dict:
    """The configuration as the worker would serve it (workers/llm.py),
    every array abstract: spec, params, cache, sampling, geometry."""
    import jax
    import jax.numpy as jnp

    from localai_tfp_tpu.models.llm_spec import spec_from_hf_config
    from localai_tfp_tpu.models.quant import quantize_params
    from localai_tfp_tpu.models.transformer import KVCache, init_params
    from localai_tfp_tpu.ops.sampling import SamplingState

    spec = spec_from_hf_config(config)
    serving = config.get("serving", {})
    quant = (serving.get("quantization") or "none").lower()
    n_slots = int(serving.get("max_batch_slots", 16))
    max_seq = int(serving.get("context_size", 4096))
    kv_dtype = getattr(
        jnp, (serving.get("kv_cache_dtype") or "bfloat16").lower())
    page = 256  # engine.py: the largest power of two <= 256 dividing
    assert max_seq % page == 0, max_seq  # max_seq
    kv_pages = n_slots * (max_seq // page) + 1  # + the trash page

    def params():
        p = init_params(jax.random.PRNGKey(0), spec, jnp.bfloat16)
        if quant in ("int8", "int8_full"):
            p = quantize_params(p, embeddings=quant == "int8_full")
        return p

    return {
        "spec": spec, "n_slots": n_slots, "max_seq": max_seq, "page": page,
        "params": _abstract(jax.eval_shape(params), sharding),
        "cache": _abstract(jax.eval_shape(lambda: KVCache.create(
            spec, kv_pages, page, kv_dtype, state_slots=n_slots)), sharding),
        "sampling": _abstract(jax.eval_shape(lambda: SamplingState.create(
            n_slots, spec.vocab_size, window=256)), sharding),
    }


def _shell_engine(s: dict):
    """An ``LLMEngine`` that holds only what its program builders read
    (``_decode_k_fn``, ``_mixed_fn``): constructing one allocates the
    weights' worth of cache and state. The ragged route is the one a
    TPU engine of these shapes takes (``_kernel_ineligible``)."""
    from localai_tfp_tpu.engine.cache_route import choose_route
    from localai_tfp_tpu.engine.engine import LLMEngine

    eng = object.__new__(LLMEngine)
    eng.spec, eng.n_slots, eng.max_seq = s["spec"], s["n_slots"], s["max_seq"]
    eng.sampling = s["sampling"]
    eng._paged, eng._page = True, s["page"]
    eng._route = choose_route(paged=True, kernel=True, max_seq=s["max_seq"],
                              page=s["page"], mesh=None,
                              latent=bool(s["spec"].kv_lora_rank))
    eng._decode_k_fns = {}
    return eng


@contextlib.contextmanager
def _compile_for_tpu():
    """While open, the Pallas kernels lower for Mosaic although the
    process's backend is the CPU (``ops.decode_attention._interpret``
    asks the backend; the program is compiled for a described chip)."""
    import importlib

    mods = [importlib.import_module(f"localai_tfp_tpu.ops.{name}")
            for name in ("decode_attention", "ragged_paged_attention",
                         "gated_delta", "grouped_matmul",
                         "latent_flash_attention", "expert_rows")]
    saved = [m._interpret for m in mods]
    try:
        for m in mods:
            m._interpret = lambda: False
        yield
    finally:
        for m, fn in zip(mods, saved):
            m._interpret = fn


def lower_program(config: dict, kind: str, sharding, *, k: int = 8,
                  rows: int = 1, bucket: Optional[int] = None):
    """``jit_dispatch_<kind>`` of the configuration, lowered for the
    sharding's device: the arguments ``_dev_exec`` passes, abstract.
    ``rows`` x ``bucket`` is the mixed step's prompt group (default:
    one prompt row of the engine's step size)."""
    import jax
    import jax.numpy as jnp
    s = serving_shapes(config, sharding)
    eng = _shell_engine(s)
    S, W = s["n_slots"], s["max_seq"]

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    i32, wp = jnp.int32, W // s["page"]
    if kind == "decodek":
        fn = eng._decode_k_fn(k, W)
        args = (s["params"], arr((S, 1), i32), s["cache"], arr((S,), i32),
                arr((S,), i32), s["sampling"], arr((S,), bool),
                arr((S, wp), i32), arr((S, wp), i32))
    elif kind == "mixed":
        R, T = rows, bucket or eng._step_tokens
        reset = tuple(arr(v.shape, v.dtype)
                      for v in eng._reset_columns([], R).values())
        fn = eng._mixed_fn(W)
        args = (s["params"], s["cache"], s["sampling"], arr((S, 1), i32),
                arr((S,), i32), arr((S,), bool), arr((R, T), i32),
                arr((R,), i32), arr((R,), i32), arr((R,), i32),
                arr((R,), bool), arr((R, s["sampling"].window), i32),
                arr((R,), i32), None, None, reset,
                arr((S + R, wp), i32), arr((S + R, wp), i32))
    else:
        raise ValueError(f"kind {kind!r}: one of {KINDS}")
    with _compile_for_tpu():
        return fn.lower(*args)


# ---------------------------------------------------------------------------
# the optimized HLO's loop bodies
# ---------------------------------------------------------------------------

_ITEM = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(_ITEM) + r")\[([0-9,]*)\]")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<shape>\(.*?\)|\S+)\s+"
    r"(?P<op>[\w\-]+)\((?P<rest>.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")


class Op(NamedTuple):
    name: str
    opcode: str  # fusion, copy, custom-call, while, ...
    kind: str  # a fusion's kind (kLoop, kOutput, kInput, ...), else ""
    shapes: tuple  # ((dtype, dims), ...): one entry an output array
    bytes: int  # of all outputs
    cycles: Optional[int]  # the compiler's estimate, where it gives one
    calls: tuple  # computations it calls (a fusion's, a while's body)
    operands: tuple  # names of its operands
    line: str


def _arrays(shape: str) -> tuple:
    return tuple((dt, tuple(int(d) for d in dims.split(",") if d))
                 for dt, dims in _ARRAY.findall(shape))


def _nbytes(shapes: tuple) -> int:
    total = 0
    for dt, dims in shapes:
        n = _ITEM[dt]
        for d in dims:
            n *= d
        total += n
    return total


def parse_hlo(text: str) -> dict:
    """{computation name: [Op, ...]} of an HLO module's text."""
    comps: dict = {}
    cur = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP.match(line)
            if m and "=" not in line.split("(")[0]:
                cur = comps.setdefault(m.group("name"), [])
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        shapes = _arrays(m.group("shape"))
        rest = m.group("rest")
        kind = re.search(r"\bkind=(\w+)", rest)
        cyc = re.search(r'"estimated_cycles":"?(\d+)', rest)
        calls = re.findall(
            r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)", rest)
        cur.append(Op(m.group("name"), m.group("op"),
                      kind.group(1) if kind else "", shapes,
                      _nbytes(shapes), int(cyc.group(1)) if cyc else None,
                      tuple(calls), _operands(rest), line.strip()))
    return comps


def _operands(rest: str) -> tuple:
    """Operand names of an instruction, from the text after its
    opening parenthesis."""
    depth = 1
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return tuple(re.findall(r"%([\w.\-]+)", rest[:i]))
    return ()


def origin(comps: dict, comp: str, name: str, _hops: int = 0) -> str:
    """Where a value read in computation ``comp`` comes from: followed
    through loop carries, copies and bitcasts up to an entry parameter
    (``params['wk'].q``) or the op that computes it."""
    ops = {o.name: o for o in comps.get(comp, ())}
    op = ops.get(name)
    if op is None or _hops > 64:
        return name
    if op.opcode in ("copy", "bitcast") and op.operands:
        return origin(comps, comp, op.operands[0], _hops + 1)
    if op.opcode == "parameter":
        m = re.search(r'op_name="([^"]*)"', op.line)
        return m.group(1).replace("\\'", "'") if m else name
    if op.opcode != "get-tuple-element" or not op.operands:
        return name
    src = ops.get(op.operands[0])
    idx = re.search(r"index=(\d+)", op.line)
    if src is None or src.opcode != "parameter" or not idx:
        return name
    for parent, pops in comps.items():  # the loop that runs this body
        for w in pops:
            if w.opcode == "while" and comp in w.calls and w.operands:
                tup = next((o for o in pops if o.name == w.operands[0]),
                           None)
                if tup is not None and tup.opcode == "tuple":
                    return origin(comps, parent,
                                  tup.operands[int(idx.group(1))],
                                  _hops + 1)
    return name


def _fused_opcodes(comps: dict, op: Op) -> set:
    """Opcodes inside a fusion's computation (and those it calls)."""
    seen, todo, out = set(), list(op.calls), set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for o in comps[c]:
            out.add(o.opcode)
            todo.extend(o.calls)
    return out


class Body(NamedTuple):
    name: str  # the while body's computation
    depth: int  # 0: a loop of the entry computation
    ops: tuple  # its Ops (a nested loop's ops are in its own Body)


def loop_bodies(text: str) -> tuple:
    """(every ``while`` body reachable from the entry computation,
    outermost first; the module's computations)."""
    comps = parse_hlo(text)
    entry = next((n for n in comps if re.search(
        r"^ENTRY\s+%?" + re.escape(n) + r"\b", text, re.M)), None)
    out, seen = [], set()

    def walk(comp: str, depth: int):
        for op in comps.get(comp, ()):
            if op.opcode == "while":
                body = next((c for c in op.calls if re.search(
                    r"body=%?" + re.escape(c) + r"\b", op.line)), None)
                if body and body not in seen:
                    seen.add(body)
                    out.append(Body(body, depth,
                                    tuple(comps.get(body, ()))))
                    walk(body, depth + 1)
            elif op.opcode in ("call", "conditional"):
                for c in op.calls:
                    walk(c, depth)

    if entry:
        walk(entry, 0)
    return out, comps


# what may move a weight-sized array in a loop: the matmul that reads
# the weight (XLA's fusion of a dot carries one of these opcodes
# inside) and a Pallas kernel
_MATMUL_OPCODES = {"dot", "convolution", "ragged-dot"}
_PLUMBING = {"parameter", "tuple", "get-tuple-element", "bitcast",
             "constant", "while", "call", "conditional"}


def _is_async(op: Op) -> bool:
    # copy-start / slice-start ... -done: the compiler's own prefetch
    # of an operand into fast memory (memory-space assignment), a DMA
    # beside the loop's compute that keeps the layout — not a relayout
    return op.opcode.endswith(("-start", "-done"))


def offenders(text: str, weight_dtypes: tuple,
              min_bytes: int = MIN_BYTES) -> list:
    """(body name, Op, the leaf it moves) for every op of a loop body
    that is neither a dot / convolution (fusion) nor a custom call,
    writes an array of a weight's dtype of at least ``min_bytes`` and
    reads a parameter leaf (``params[...]``) — straight from its stack,
    or through copies, bitcasts and ops already named here: a layer's
    matrix sliced out of the stack, or copied into another layout.
    Activations and the KV pool read no parameter leaf and are not
    looked at; nor are the compiler's asynchronous prefetches
    (``_is_async``), which the listing still shows."""
    bodies, comps = loop_bodies(text)
    out = []
    for b in bodies:
        named: dict = {}  # op name -> the leaf it moved
        for op in b.ops:
            if op.opcode in _PLUMBING or op.opcode == "custom-call" \
                    or _is_async(op):
                continue
            inner = _fused_opcodes(comps, op) if op.opcode == "fusion" \
                else {op.opcode}
            if inner & _MATMUL_OPCODES or "custom-call" in inner:
                continue
            if not any(dt in weight_dtypes
                       and _nbytes(((dt, dims),)) >= min_bytes
                       for dt, dims in op.shapes):
                continue
            src = [named.get(o, o) for o in
                   (origin(comps, b.name, x) for x in op.operands)]
            leaves = [o for o in src if o.startswith("params[")]
            if leaves:
                named[op.name] = leaves[0]
                out.append((b.name, op, leaves[0]))
    return out


def weight_dtypes(config: dict) -> tuple:
    """HLO names of the dtypes the configuration's matrices are served
    in."""
    quant = (config.get("serving", {}).get("quantization") or "none").lower()
    return ("s8", "bf16") if quant.startswith("int8") else ("bf16",)


def offenders_of(text: str, config: dict,
                 min_bytes: int = MIN_BYTES) -> list:
    return offenders(text, weight_dtypes(config), min_bytes)


def _fmt_shapes(shapes: tuple) -> str:
    return " ".join(f"{dt}[{','.join(map(str, dims))}]"
                    for dt, dims in shapes) or "()"


def report(text: str, config: dict, *, min_bytes: int = MIN_BYTES,
           every: bool = False) -> dict:
    """The printed listing, as data."""
    bad = offenders_of(text, config, min_bytes)
    marked = {(b, op.name) for b, op, _ in bad}
    bodies, comps = loop_bodies(text)
    return {
        "weight_dtypes": list(weight_dtypes(config)),
        "bodies": [{
            "body": b.name, "depth": b.depth,
            "reads": sorted({o for op in b.ops for o in (
                origin(comps, b.name, x) for x in op.operands)
                if o.startswith("params[")}),
            "ops": [{"name": op.name,
                     "op": op.opcode + (f":{op.kind}" if op.kind else ""),
                     "out": _fmt_shapes(op.shapes), "bytes": op.bytes,
                     "cycles": op.cycles,
                     "offender": (b.name, op.name) in marked}
                    for op in b.ops if op.opcode not in _PLUMBING
                    and (every or op.bytes >= min_bytes)]}
            for b in bodies],
        "offenders": [{"body": b, "name": op.name, "op": op.opcode,
                       "out": _fmt_shapes(op.shapes), "bytes": op.bytes,
                       "cycles": op.cycles, "leaf": leaf}
                      for b, op, leaf in bad]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="benchmark/configs/<name>.json")
    ap.add_argument("--kind", choices=KINDS, default="decodek")
    ap.add_argument("--k", type=int, default=8, help="decodek's steps")
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--bucket", type=int, default=None)
    ap.add_argument("--min-mb", type=float, default=1.0)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--dump", help="write the optimized HLO text here")
    ap.add_argument("--hlo", help="read this dump instead of compiling")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--attached", action="store_true",
                    help="compile for the chip this process holds "
                    "(through the chip tool) instead of a described one")
    a = ap.parse_args(argv)
    if not a.attached:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with open(a.config) as f:
        config = json.load(f)
    if a.hlo:
        with open(a.hlo) as f:
            text = f.read()
    else:
        text = lower_program(
            config, a.kind, _attached() if a.attached else describe_v5e(),
            k=a.k, rows=a.rows, bucket=a.bucket).compile().as_text()
    if a.dump:
        with open(a.dump, "w") as f:
            f.write(text)
    rep = report(text, config, min_bytes=int(a.min_mb * (1 << 20)),
                 every=a.all)
    if a.json:
        print(json.dumps(rep))
        return 0
    print(f"{os.path.basename(a.config)} {a.kind}: weights in "
          f"{'/'.join(rep['weight_dtypes'])}; cycles are the compiler's "
          "estimate, not a time")
    for b in rep["bodies"]:
        print(f"\n{'  ' * b['depth']}while body {b['body']}")
        if b["reads"]:
            print(f"{'  ' * b['depth']}  reads {', '.join(b['reads'])}")
        for op in b["ops"]:
            print(f"{'  ' * b['depth']}  {'<<' if op['offender'] else '  '} "
                  f"{op['name']:<44} {op['op']:<18} "
                  f"{op['bytes'] / 1e6:9.3f} MB "
                  f"{op['cycles'] if op['cycles'] is not None else '-':>9} cyc"
                  f"  {op['out']}")
    print(f"\n{len(rep['offenders'])} weight-sized op(s) that are neither "
          "a matmul nor a kernel" + (":" if rep["offenders"] else ""))
    for o in rep["offenders"]:
        print(f"  {o['leaf']}: {o['name']} = {o['out']} ({o['op']}, "
              f"{o['bytes'] / 1e6:.2f} MB, {o['cycles']} cyc)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
