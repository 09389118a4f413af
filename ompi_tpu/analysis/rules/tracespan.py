"""Trace-coverage rule.

``tracespan``: a public collective/p2p entry point in coll/ or pml/
that dispatches outside the communicator and the selection seams never
lands on the commtrace timeline — the flight recorder shows a gap
exactly where the interesting call happened. Components registered
with the framework (``@COLL.register`` / ``@PML.register``) are covered
automatically: ``Communicator._coll_call`` opens a span around every
vtable dispatch and trace/span.py wraps the selected pml at selection
time, so this rule skips them. What it flags is the
*unregistered* surface — module-level helpers or ad-hoc classes that
expose an entry-op name (``allreduce``, ``send``, ...) with no span or
instant call in the body and no selection-time wrap to catch them.

Evidence that satisfies the rule, anywhere in the function body:
a call named ``span``/``instant``/``Span``/``coll_trace_id`` or the
pml wrap helper from trace/span.py.

Suppression: ``# commlint: allow(tracespan)`` on the def line, for
entry points that are deliberately span-free (pure-dispatch persistent
starts, internal per-slice helpers).
"""

from __future__ import annotations

import ast
from typing import Iterable

from ..report import Severity
from . import COLL_BASE_OPS, COMMLINT, LintRule, call_name, scope_walk, tree_walk

#: Entry-op names whose public implementations belong on the timeline.
_ENTRY_OPS = frozenset(
    set(COLL_BASE_OPS) | {"send", "recv", "isend", "irecv"}
)

#: Call names that count as span evidence inside a body.
_SPAN_CALLS = frozenset({
    "span", "instant", "Span", "coll_trace_id", "maybe_wrap_pml",
})

#: Directories whose entry points the rule audits ('/'-normalised).
_TRACED_DIRS = ("coll/", "pml/")


def _in_scope(relpath: str) -> bool:
    p = relpath.replace("\\", "/")
    if p.endswith("framework.py"):
        return False  # the seams themselves install the wrapping
    return any(f"/{d}" in p or p.startswith(d) for d in _TRACED_DIRS)


def _registered_classes(tree: ast.Module) -> set[ast.ClassDef]:
    """Classes whose entry ops are wrapped at selection time: anything
    decorated with a framework ``.register`` decorator, plus same-file
    mixin bases of such classes (their methods land in the registered
    component's vtable)."""
    by_name: dict[str, ast.ClassDef] = {}
    registered: set[ast.ClassDef] = set()
    for node in tree_walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        by_name[node.name] = node
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if isinstance(target, ast.Attribute) \
                    and target.attr == "register":
                registered.add(node)
                break
    for cls in list(registered):
        for base in cls.bases:
            if isinstance(base, ast.Name) and base.id in by_name:
                registered.add(by_name[base.id])
    return registered


def _takes_comm(fn: ast.AST) -> bool:
    """True when the def's positional parameters include ``comm`` —
    the signature shape of every vtable/pml entry point. Builder and
    slice-level helpers (no comm param) are out of scope."""
    args = fn.args
    names = [a.arg for a in args.posonlyargs + args.args]
    return "comm" in names


def _has_span_evidence(fn: ast.AST) -> bool:
    for node in scope_walk(fn):
        if call_name(node) in _SPAN_CALLS:
            return True
    return False


@COMMLINT.register
class TraceSpanRule(LintRule):
    NAME = "tracespan"
    PRIORITY = 40
    DESCRIPTION = ("public coll/pml entry points outside the "
                   "selection seams should run under a trace span")
    SEVERITY = Severity.WARNING

    def check(self, ctx) -> Iterable:
        if not _in_scope(ctx.relpath):
            return
        registered = _registered_classes(ctx.tree)
        covered: set[ast.AST] = set()
        for cls in registered:
            covered.update(ast.walk(cls))
        for node in ctx.walk():
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name not in _ENTRY_OPS:
                continue
            if not _takes_comm(node):
                continue
            if node in covered:
                continue  # _coll_call / the pml wrap cover registered comps
            if _has_span_evidence(node):
                continue
            if ctx.suppressed(node.lineno, self.NAME):
                continue
            yield self.finding(
                ctx, node,
                f"entry point {node.name}() is outside the selection "
                "seams and emits no trace span/instant — calls through "
                "it leave a gap on the commtrace timeline; wrap the "
                "body in trace.span.span() or emit an instant",
            )
