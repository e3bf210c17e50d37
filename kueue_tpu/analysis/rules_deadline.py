"""deadline-discipline — every remote exchange names its deadline.

The gray-failure postmortem shape: a transport built with
``timeout=10.0`` and call sites that never think about time again. A
limping worker answering in 9.9 s then stalls every such call site for
the full constructor default, and nothing in the code says which calls
could have tolerated less. The federation's adaptive-deadline plane
(federation/health.py) fixes the *mechanism*; this rule fixes the
*habit*: under the scoped prefixes, a remote call site must carry an
explicit per-call deadline so the bound is a reviewed decision at the
point of use, not a constructor-line accident.

Flagged, inside ``SCOPE_PREFIXES`` only:

- ``*.call(op, ...)`` — the RemoteClient/MultiKueueCluster transport
  verb — without a ``deadline_s=`` keyword;
- constructing ``HTTPTransport`` / ``KueueClient`` / ``HTTPTailSource``
  without an explicit ``timeout=`` (the default exists for scripts and
  tests; long-running control loops must name their cap);
- ``*.journal_tail(...)`` — the replication-feed poll — without a
  ``timeout_s=`` keyword (the HTTPTailSource adaptive deadline wire).

A ``**kwargs`` splat at the call site counts as satisfied: the bound
is being threaded, not defaulted. The allowlist below is the same
shrink-only triage ledger the clock rule keeps — each entry names one
scope (``file`` or ``file::Qual.name``) with the reviewed reason the
discipline does not apply, and a stale entry is itself a finding.
"""

from __future__ import annotations

import ast
from typing import Dict, List

from kueue_tpu.analysis.core import (
    AnalysisContext,
    Finding,
    Rule,
    SourceFile,
    register,
    resolve_call_name,
)

#: path prefixes where the discipline is enforced: the control loops
#: that keep running while a worker limps. CLI one-shots and tests
#: stay out — a human is watching those.
SCOPE_PREFIXES = (
    "kueue_tpu/federation/",
    "kueue_tpu/replica/",
    "kueue_tpu/admissionchecks/",
)

#: method attribute -> required keyword
DEADLINE_CALL_ATTRS: Dict[str, str] = {
    "call": "deadline_s",
    "journal_tail": "timeout_s",
}

#: constructors that bake a wide default timeout; scoped code must
#: pass an explicit ``timeout=``
DEADLINE_CTORS = ("HTTPTransport", "KueueClient", "HTTPTailSource")

#: scope -> justification (file or file::Qualified.name). Same ledger
#: contract as CLOCK_ALLOWLIST: honest reasons, shrink-only.
DEADLINE_ALLOWLIST: Dict[str, str] = {}


@register
class DeadlineDisciplineRule(Rule):
    name = "deadline-discipline"
    description = (
        "remote call site in federation/replica/admissionchecks "
        "control loops riding a constructor-default timeout — pass an "
        "explicit deadline_s=/timeout_s= per call (or timeout= at "
        "construction) so the bound is decided where the call is made"
    )

    def check(self, src: SourceFile, ctx: AnalysisContext) -> List[Finding]:
        prefixes = tuple(
            ctx.config.get("deadline_scope_prefixes", SCOPE_PREFIXES)
        )
        if not src.rel.startswith(prefixes):
            return []
        allowlist = ctx.config.get("deadline_allowlist", DEADLINE_ALLOWLIST)
        used_scopes = ctx.config.setdefault("_deadline_used_scopes", set())
        findings: List[Finding] = []

        def allowed(qual: str) -> bool:
            scope_file = src.rel
            scope_fn = f"{src.rel}::{qual}" if qual else src.rel
            if scope_file in allowlist:
                used_scopes.add(scope_file)
                return True
            if scope_fn in allowlist:
                used_scopes.add(scope_fn)
                return True
            return False

        def visit(node: ast.AST, stack: List[str]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(
                    child,
                    (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
                ):
                    visit(child, stack + [child.name])
                    continue
                if isinstance(child, ast.Call):
                    self._check_call(child, stack, allowed, findings, src)
                visit(child, stack)

        visit(src.tree, [])
        return findings

    def _check_call(self, call, stack, allowed, findings, src) -> None:
        kwargs = {kw.arg for kw in call.keywords}
        if None in kwargs:
            return  # a **splat threads the caller's bound through
        qual = ".".join(stack)
        func = call.func
        if isinstance(func, ast.Attribute):
            required = DEADLINE_CALL_ATTRS.get(func.attr)
            if required is not None and required not in kwargs:
                if not allowed(qual):
                    findings.append(
                        Finding(
                            self.name,
                            src.rel,
                            call.lineno,
                            f".{func.attr}(...) in {qual or '<module>'} "
                            f"without {required}= — the exchange rides "
                            "the constructor-default timeout; name the "
                            "per-call deadline",
                        )
                    )
                return
        canon = resolve_call_name(call, {}) or ""
        ctor = canon.rsplit(".", 1)[-1] if canon else (
            func.id if isinstance(func, ast.Name) else
            func.attr if isinstance(func, ast.Attribute) else ""
        )
        if ctor in DEADLINE_CTORS and "timeout" not in kwargs:
            if not allowed(qual):
                findings.append(
                    Finding(
                        self.name,
                        src.rel,
                        call.lineno,
                        f"{ctor}(...) in {qual or '<module>'} without "
                        "an explicit timeout= — a control loop must "
                        "name the cap its exchanges run under",
                    )
                )

    def finalize(self, ctx: AnalysisContext) -> List[Finding]:
        """Stale allowlist entries shrink, exactly like the clock
        ledger — an entry whose scope is clean is debt marked paid."""
        allowlist = ctx.config.get("deadline_allowlist", DEADLINE_ALLOWLIST)
        used = ctx.config.get("_deadline_used_scopes", set())
        scanned = {s.rel for s in ctx.sources}
        findings: List[Finding] = []
        for scope in sorted(allowlist):
            rel = scope.split("::", 1)[0]
            if rel not in scanned:
                continue  # partial runs must not flag unscanned scopes
            if scope not in used:
                findings.append(
                    Finding(
                        self.name,
                        rel,
                        1,
                        f"stale deadline allowlist entry {scope!r} — no "
                        "default-timeout call site remains there; "
                        "shrink DEADLINE_ALLOWLIST",
                    )
                )
        return findings
