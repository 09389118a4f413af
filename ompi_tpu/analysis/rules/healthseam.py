"""Health-probe coverage rule.

``healthseam``: a transport component registered at the btl/pml
selection seam (``@BTL.register`` / ``@PML.register`` /
``@MTL.register``) carries traffic the health supervisor is supposed
to keep alive — but a tier without a registered prober is invisible
to it: the ledger can quarantine it on in-band failures yet nothing
ever re-probes it back to HEALTHY, so one wedge silently downgrades
the job for its remaining lifetime (the failure the health subsystem
exists to end).

Evidence that satisfies the rule, anywhere in the file: a call named
``register_probe`` / ``register_health_probe`` /
``register_health_probes`` — the component either registers its
canary directly or exposes the registration helper its wiring seam
calls.

Seam-file exemptions (the ``tracespan`` pattern): ``framework.py``
(the seams themselves), ``template.py`` (the documented skeleton),
and ``self.py``/``ici.py`` (in-process loopback — there is no
transport to die).

Suppression: ``# commlint: allow(healthseam)`` on the class line, for
components that deliberately delegate liveness to the engine they
ride (pml/ob1 and pml/cm sit on the fabric engine, whose probe covers
them).
"""

from __future__ import annotations

import ast
from typing import Iterable

from ..report import Severity
from . import COMMLINT, LintRule, call_name, tree_walk

#: Directories whose registered components the rule audits.
_SEAM_DIRS = ("btl/", "pml/")

#: Seam/skeleton files exempt from the requirement.
_EXEMPT_FILES = ("framework.py", "template.py", "self.py", "ici.py")

#: Call names that count as prober evidence inside a file.
_PROBE_CALLS = frozenset({
    "register_probe", "register_health_probe", "register_health_probes",
})

#: Framework attributes whose .register decorator marks a transport
#: component (coll components ride these, they don't carry bytes).
_TRANSPORT_FWS = frozenset({"BTL", "PML", "MTL"})


def _in_scope(relpath: str) -> bool:
    p = relpath.replace("\\", "/")
    if any(p.endswith(x) for x in _EXEMPT_FILES):
        return False
    return any(f"/{d}" in p or p.startswith(d) for d in _SEAM_DIRS)


def _registered_transport_classes(tree: ast.Module) -> list[ast.ClassDef]:
    out = []
    for node in tree_walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if isinstance(target, ast.Attribute) \
                    and target.attr == "register" \
                    and isinstance(target.value, ast.Name) \
                    and target.value.id in _TRANSPORT_FWS:
                out.append(node)
                break
    return out


def _has_probe_evidence(tree: ast.Module) -> bool:
    return any(call_name(n) in _PROBE_CALLS for n in tree_walk(tree))


@COMMLINT.register
class HealthSeamRule(LintRule):
    NAME = "healthseam"
    PRIORITY = 35
    DESCRIPTION = ("transport components registered at btl/pml "
                   "selection should register a health prober")
    SEVERITY = Severity.WARNING

    def check(self, ctx) -> Iterable:
        if not _in_scope(ctx.relpath):
            return
        classes = _registered_transport_classes(ctx.tree)
        if not classes:
            return
        if _has_probe_evidence(ctx.tree):
            return
        for cls in classes:
            if ctx.suppressed(cls.lineno, self.NAME):
                continue
            yield self.finding(
                ctx, cls,
                f"transport component {cls.name} registers at the "
                "selection seam but this file registers no health "
                "prober — a quarantined tier through it can never be "
                "background-restored; call health.prober."
                "register_probe at wiring (or allow() if liveness is "
                "delegated to the engine underneath)",
            )
