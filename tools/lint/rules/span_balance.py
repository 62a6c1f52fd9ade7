"""span-balance: every explicit trace span is closed on all paths.

``TraceRecorder.begin_span`` returns a token that MUST reach
``end_span`` on every control-flow path — including exceptions — or the
span silently never closes and the trace undercounts the very interval
it was added to measure. The enforced shape is exactly one idiom:

    tok = TRACER.begin_span(rid, "name")
    try:
        ...
    finally:
        TRACER.end_span(tok, ...)

(the assignment immediately followed by a ``try`` whose ``finally``
calls ``end_span``), or the balanced-by-construction context manager
``with TRACER.span(rid, "name"):``. Anything else — a discarded token,
an end_span outside the protecting ``finally``, statements between the
begin and the try that could raise — flags here.

The scheduler's phase spans (``telemetry/flightrec.py`` ``PhaseClock``)
and load watches (``LoadWatch``) have NO begin/end pair to misuse: the
only way in is ``with clock.span(name):`` / ``with watch.watch(...):``,
balanced by construction, so there is nothing for this rule to find
there — a future ``begin_span``-shaped entry point on them would be
checked like any other.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..core import Context, Finding


def _is_begin(call: ast.Call) -> bool:
    f = call.func
    return isinstance(f, ast.Attribute) and f.attr == "begin_span"


def _has_end_span(stmts: list) -> bool:
    for s in stmts:
        for node in ast.walk(s):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "end_span"):
                return True
    return False


def _stmt_lists(tree: ast.AST) -> Iterator[list]:
    for node in ast.walk(tree):
        for name in ("body", "orelse", "finalbody"):
            lst = getattr(node, name, None)
            if isinstance(lst, list) and lst and isinstance(lst[0],
                                                            ast.stmt):
                yield lst


def _begin_calls_of(stmt: ast.stmt) -> Iterator[ast.Call]:
    """begin_span calls belonging to THIS statement's own expressions.

    Nested statement blocks (a compound statement's body) are yielded
    as their own lists by ``_stmt_lists`` and checked there, so the
    scan stops at child statements to avoid double-reporting."""
    todo: list = [stmt]
    while todo:
        n = todo.pop()
        if isinstance(n, ast.Call) and _is_begin(n):
            yield n
        for child in ast.iter_child_nodes(n):
            if not isinstance(child, ast.stmt):
                todo.append(child)


class SpanBalance:
    id = "span-balance"
    doc = ("begin_span without a guaranteed end_span — use "
           "`tok = ...begin_span(...)` immediately followed by "
           "try/finally end_span(tok), or the span() context manager")

    def check(self, ctx: Context) -> Iterator[Finding]:
        for m in ctx.modules:
            for stmts in _stmt_lists(m.tree):
                for i, stmt in enumerate(stmts):
                    yield from self._check_stmt(m, stmts, i, stmt)

    def _check_stmt(self, m, stmts: list, i: int,
                    stmt: ast.stmt) -> Iterator[Finding]:
        calls = list(_begin_calls_of(stmt))
        if not calls:
            return
        # the one balanced shape: `tok = ...begin_span(...)` as the
        # WHOLE statement, with the very next statement a try whose
        # finally reaches end_span
        if (isinstance(stmt, ast.Assign)
                and isinstance(stmt.value, ast.Call)
                and _is_begin(stmt.value) and len(calls) == 1):
            nxt = stmts[i + 1] if i + 1 < len(stmts) else None
            if isinstance(nxt, ast.Try) and _has_end_span(nxt.finalbody):
                return
            yield m.finding(
                self.id, stmt,
                "begin_span result is not protected by an immediately "
                "following try/finally that calls end_span")
            return
        for call in calls:
            yield m.finding(
                self.id, call,
                "begin_span token is discarded or buried in a larger "
                "expression — it cannot reach end_span on all paths")
