"""sharding-contract: GSPMD layout contracts on the paged serving path.

PR 12's hardest bug class: GSPMD miscompiles the paged
gather -> forward -> scatter program unless every fallback branch pins
the gathered window's layout (``engine/cache_route.py``
``_pin_win_sharding``) — jit vs
eager silently diverges on the written pages, O(1)-wrong hidden states,
no error anywhere. This rule makes that class un-reintroducible, plus
two adjacent layout contracts:

1. **Pin discipline** — in any function (engine/, ops/,
   parallel/multihost.py) that both ``gather_kv_pages(...)`` and
   ``scatter_kv_pages(...)``, every name bound from the gather must be
   re-bound through ``_pin_win_sharding(name, ..., batch=True)`` before
   the forward, and every window passed to the scatter must come out of
   ``_pin_win_sharding(name, ..., batch=False)`` — the dense-layout /
   arena-layout round trip that anchors GSPMD.
2. **No inline PartitionSpec literals** — every ``P(...)`` spec in the
   scoped modules must be built from the named constants in
   ``parallel/sharding.py`` (``PAGED_KV_SPEC``, ``KV_CACHE_SPEC``,
   ``DENSE_ROW_SPEC``, ``REPLICATED``, ...); an inline literal is a
   layout fork that drifts from the arena the first time the arena
   changes.
3. **Host-owned page tables stay global** — int32 page/write tables
   (``phys``, ``wb``, ``page_table``, ``write_table``, ``pt``, ...) are
   scheduler state every device reads whole; passing one to
   ``with_sharding_constraint`` / ``device_put`` / ``_pin_win_sharding``
   turns host bookkeeping into a mesh-resident operand and re-opens the
   layout-guess hole.

Scope: ``localai_tfp_tpu/engine/*``, ``localai_tfp_tpu/ops/*`` and
``parallel/multihost.py``. ``parallel/sharding.py`` itself is where the
named constants LIVE and is exempt; ``parallel/ring_attention.py``
builds specs from dynamic axis names and is out of scope.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..core import Context, Finding, Module
from .scalar_payload import walk_shallow

_SCOPED_DIRS = ("localai_tfp_tpu/engine/", "localai_tfp_tpu/ops/")
_SCOPED_FILES = ("localai_tfp_tpu/parallel/multihost.py",)

_GATHER = "gather_kv_pages"
_SCATTER = "scatter_kv_pages"
_PIN = "_pin_win_sharding"

# identifiers that name host-owned int32 page/write tables
PAGE_TABLE_NAMES = {
    "phys", "wb", "pt", "wt", "page_table", "write_table",
    "page_tables", "paged_tables", "ptab", "tables",
}
_CONSTRAIN_CALLS = {"with_sharding_constraint", "device_put", _PIN}


def _in_scope(rel: str) -> bool:
    return rel.startswith(_SCOPED_DIRS) or rel in _SCOPED_FILES


def _call_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _terminal_name(node: ast.AST) -> str:
    """`phys` / `self.phys` / `payload["phys"]`-style terminal id."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Subscript):
        return _terminal_name(node.value)
    return ""


def _pin_batch_arg(call: ast.Call):
    """The `batch` argument of a _pin_win_sharding call: True / False /
    None (not a literal — dynamic, counts for both directions)."""
    for kw in call.keywords:
        if kw.arg == "batch":
            if isinstance(kw.value, ast.Constant):
                return bool(kw.value.value)
            return None
    if len(call.args) >= 3 and isinstance(call.args[2], ast.Constant):
        return bool(call.args[2].value)
    return None


class ShardingContract:
    id = "sharding-contract"
    doc = ("paged-window pin discipline, named-constant PartitionSpecs "
           "and host-global page tables on the GSPMD serving path")

    def check(self, ctx: Context) -> Iterator[Finding]:
        for m in ctx.modules:
            if not _in_scope(m.rel):
                continue
            yield from self._check_spec_literals(m)
            yield from self._check_page_tables(m)
            yield from self._check_pins(m)

    # ------------------------------------------- inline P(...) literals

    def _spec_aliases(self, m: Module) -> set[str]:
        """Local names bound to jax.sharding.PartitionSpec by import."""
        aliases: set[str] = set()
        for node in ast.walk(m.tree):
            if isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.startswith("jax"):
                for a in node.names:
                    if a.name == "PartitionSpec":
                        aliases.add(a.asname or a.name)
        return aliases

    def _check_spec_literals(self, m: Module) -> Iterator[Finding]:
        aliases = self._spec_aliases(m)
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            is_alias = isinstance(f, ast.Name) and f.id in aliases
            is_attr = (isinstance(f, ast.Attribute)
                       and f.attr == "PartitionSpec")
            if is_alias or is_attr:
                yield m.finding(
                    self.id, node,
                    "inline PartitionSpec literal — build specs from "
                    "the named constants in parallel/sharding.py "
                    "(PAGED_KV_SPEC, KV_CACHE_SPEC, REPLICATED, ...) "
                    "so layouts cannot drift from the arena")

    # --------------------------------------------- page-table globality

    def _check_page_tables(self, m: Module) -> Iterator[Finding]:
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            if _call_name(node) not in _CONSTRAIN_CALLS:
                continue
            if not node.args:
                continue
            name = _terminal_name(node.args[0])
            if name in PAGE_TABLE_NAMES:
                yield m.finding(
                    self.id, node,
                    f"sharding constraint on host-owned page table "
                    f"'{name}' — int32 page/write tables are scheduler "
                    "state every device reads whole and must never be "
                    "mesh-constrained")

    # -------------------------------------------------- pin discipline

    def _check_pins(self, m: Module) -> Iterator[Finding]:
        # assign each call to its INNERMOST enclosing function so the
        # jitted-closure fallbacks (`_spec` under `_spec_decode_fn`)
        # are analyzed once, at the level their calls actually live
        funcs = [n for n in ast.walk(m.tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for fn in funcs:
            gathers: list[tuple[str, ast.Call]] = []  # bound name, call
            scatters: list[ast.Call] = []
            pins: list[tuple[str, ast.Call, object]] = []
            for node in walk_shallow(fn):
                if isinstance(node, ast.Assign) and \
                        isinstance(node.value, ast.Call):
                    call = node.value
                    if _call_name(call) == _GATHER and \
                            len(node.targets) == 1 and \
                            isinstance(node.targets[0], ast.Name):
                        gathers.append((node.targets[0].id, call))
                if isinstance(node, ast.Call):
                    cn = _call_name(node)
                    if cn == _SCATTER:
                        scatters.append(node)
                    elif cn == _PIN and node.args and \
                            isinstance(node.args[0], ast.Name):
                        pins.append((node.args[0].id, node,
                                     _pin_batch_arg(node)))
            if not gathers or not scatters:
                continue  # gather-only (kernel_check) / scatter-only
            for name, call in gathers:
                if not any(pn == name and batch in (True, None)
                           for pn, _, batch in pins):
                    yield m.finding(
                        self.id, call,
                        f"paged fallback gathers window '{name}' and "
                        "scatters it back without routing through "
                        "_pin_win_sharding(..., batch=True) — GSPMD "
                        "picks a miscompiling layout for the fused "
                        "gather->forward->scatter program (PR 12 bug "
                        "class)")
            for call in scatters:
                win = (_terminal_name(call.args[1])
                       if len(call.args) >= 2 else "")
                if not win:
                    continue
                # the window fed to the scatter must have been pinned
                # back to the arena layout (batch=False) in this scope,
                # unless it IS a freshly gathered name that was pinned
                # (the pin rebinding keeps the same name)
                if not any(pn == win and batch in (False, None)
                           for pn, _, batch in pins):
                    yield m.finding(
                        self.id, call,
                        f"scatter_kv_pages writes window '{win}' that "
                        "never went through _pin_win_sharding(..., "
                        "batch=False) — the writeback must see updates "
                        "pinned to the arena's layout")
