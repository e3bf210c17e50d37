"""registry-lints — the five legacy in-test source scans as rules.

Each of these grew ad hoc inside a test file (PR 2 reason-enum, PR 5
fault-point, PR 7/8 kernel-mirrors, PR 10 span-name, PR 1 metrics
exposition); they all share one shape — *literal call sites must
belong to a closed registry* — so they now share one scanning
implementation with file:line findings, pragmas and baseline support.
The original tests remain as thin wrappers (old names preserved).

Closed registries are imported from their single sources of truth at
check time (``EVENT_REASONS``, ``SPAN_NAMES``, ``FAULT_POINTS``,
``KERNEL_MIRRORS``/``SHARDED_KERNELS``); fixture tests swap them
through ``AnalysisContext.config``.
"""

from __future__ import annotations

import ast
import importlib
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from kueue_tpu.analysis.core import (
    AnalysisContext,
    Finding,
    Rule,
    SourceFile,
    dotted_name,
    module_str_constants,
    register,
    str_const,
)

_ALPHA = re.compile(r"^[A-Za-z]+$")
_SPANISH = re.compile(r"^[A-Za-z_.]+$")
_POINT = re.compile(r"^[a-z_.]+$")


def _first_str_arg(call: ast.Call) -> Optional[str]:
    if call.args:
        return str_const(call.args[0])
    return None


# ---- reason-enum ----
@register
class ReasonEnumRule(Rule):
    name = "reason-enum"
    description = (
        "literal event reasons at .event()/.events()/.record() call "
        "sites must belong to models.constants.EVENT_REASONS"
    )

    _CALL_ATTRS = {"event", "events", "record"}

    def _reasons(self, ctx: AnalysisContext) -> Set[str]:
        reasons = ctx.config.get("event_reasons")
        if reasons is None:
            from kueue_tpu.models.constants import EVENT_REASONS

            reasons = EVENT_REASONS
        return set(reasons)

    def check(self, src: SourceFile, ctx: AnalysisContext) -> List[Finding]:
        reasons = self._reasons(ctx)
        findings: List[Finding] = []
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (
                isinstance(fn, ast.Attribute)
                and fn.attr in self._CALL_ATTRS
            ):
                continue
            s = _first_str_arg(node)
            if s is None or not _ALPHA.match(s):
                continue
            if s not in reasons:
                findings.append(
                    Finding(
                        self.name, src.rel, node.lineno,
                        f"ad-hoc event reason {s!r} — add it to "
                        "EVENT_REASONS or fix the call site",
                    )
                )
        return findings


# ---- span-name ----
@register
class SpanNameRule(Rule):
    name = "span-name"
    description = (
        "literal span names at recording call sites must belong to "
        "tracing.names.SPAN_NAMES, and literal Phase(...) names to "
        "tracing.names.PHASE_NAMES"
    )

    _CALL_ATTRS = {
        "add_cycle_span", "add_workload_span", "record_span", "_trace_span",
    }
    _PHASE = "Phase"

    def _names(self, ctx: AnalysisContext, key: str) -> Set[str]:
        names = ctx.config.get(key)
        if names is None:
            from kueue_tpu.tracing import names as registry

            names = (
                registry.SPAN_NAMES if key == "span_names"
                else registry.PHASE_NAMES
            )
        return set(names)

    def check(self, src: SourceFile, ctx: AnalysisContext) -> List[Finding]:
        registries = {
            "SPAN_NAMES": self._names(ctx, "span_names"),
            "PHASE_NAMES": self._names(ctx, "phase_names"),
        }
        findings: List[Finding] = []
        matched = ctx.config.setdefault("_span_sites", [])
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr in self._CALL_ATTRS:
                registry = "SPAN_NAMES"
            elif (
                isinstance(fn, ast.Name) and fn.id == self._PHASE
            ) or (isinstance(fn, ast.Attribute) and fn.attr == self._PHASE):
                registry = "PHASE_NAMES"
            else:
                continue
            s = _first_str_arg(node)
            if s is None or not _SPANISH.match(s):
                continue
            matched.append(s)
            if s not in registries[registry]:
                findings.append(
                    Finding(
                        self.name, src.rel, node.lineno,
                        f"ad-hoc span name {s!r} — add it to "
                        f"{registry} or fix the call site",
                    )
                )
        return findings

    def finalize(self, ctx: AnalysisContext) -> List[Finding]:
        if not ctx.config.get("require_call_sites", True):
            return []
        if ctx.config.get("_span_sites"):
            return []
        rel = next(
            (s.rel for s in ctx.sources if s.rel.endswith("tracer.py")),
            ctx.sources[0].rel if ctx.sources else "<tree>",
        )
        return [
            Finding(
                self.name, rel, 1,
                "span-name lint matched no call sites — the call-site "
                "pattern rotted (recording API renamed?)",
            )
        ]


# ---- policy-name ----
@register
class PolicyNameRule(Rule):
    name = "policy-name"
    description = (
        "literal admission-policy names at resolve_policy()/"
        "set_policy()/PolicyDelta() call sites must belong to the "
        "closed kueue_tpu.policy.POLICY registry"
    )

    _CALL_NAMES = {"resolve_policy", "set_policy", "PolicyDelta"}
    _NAME_RE = re.compile(r"^[a-z][a-z0-9-]*$")

    def _policies(self, ctx: AnalysisContext) -> Set[str]:
        names = ctx.config.get("policy_names")
        if names is None:
            from kueue_tpu.policy import POLICY

            names = POLICY
        return set(names)

    def check(self, src: SourceFile, ctx: AnalysisContext) -> List[Finding]:
        policies = self._policies(ctx)
        findings: List[Finding] = []
        matched = ctx.config.setdefault("_policy_sites", [])
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            callee = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None
            )
            if callee not in self._CALL_NAMES:
                continue
            s = _first_str_arg(node)
            if s is None:
                # also accept policy= keyword literals
                for kw in node.keywords:
                    if kw.arg == "policy":
                        s = str_const(kw.value)
                        break
            if s is None or not self._NAME_RE.match(s):
                continue
            matched.append(s)
            if s not in policies:
                findings.append(
                    Finding(
                        self.name, src.rel, node.lineno,
                        f"unregistered admission policy {s!r} — the "
                        "POLICY registry is closed; add the policy "
                        "there or fix the call site",
                    )
                )
        return findings

    def finalize(self, ctx: AnalysisContext) -> List[Finding]:
        if not ctx.config.get("require_call_sites", True):
            return []
        if ctx.config.get("_policy_sites"):
            return []
        rel = next(
            (s.rel for s in ctx.sources if s.rel.endswith("policy/engine.py")),
            ctx.sources[0].rel if ctx.sources else "<tree>",
        )
        return [
            Finding(
                self.name, rel, 1,
                "policy-name lint matched no call sites — the "
                "call-site pattern rotted (resolution API renamed?)",
            )
        ]


# ---- fault-point ----
@register
class FaultPointRule(Rule):
    name = "fault-point"
    description = (
        "faults.fire()/faults.transform()/fault_point= literals must "
        "be registered in testing.faults.FAULT_POINTS, and every "
        "registered point must have a production call site"
    )

    def _points(self, ctx: AnalysisContext) -> Set[str]:
        points = ctx.config.get("fault_points")
        if points is None:
            from kueue_tpu.testing.faults import FAULT_POINTS

            points = FAULT_POINTS
        return set(points)

    def check(self, src: SourceFile, ctx: AnalysisContext) -> List[Finding]:
        if src.rel.endswith("faults.py"):
            return []  # the registry module itself is not a call site
        points = self._points(ctx)
        findings: List[Finding] = []
        seen = ctx.config.setdefault("_fault_sites", set())
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            names: List[str] = []
            dn = dotted_name(node.func)
            if dn is not None and dn.rsplit(".", 1)[-1] in (
                "fire", "transform",
            ) and "faults" in dn:
                s = _first_str_arg(node)
                if s is not None and _POINT.match(s):
                    names.append(s)
            for kw in node.keywords:
                if kw.arg == "fault_point":
                    s = str_const(kw.value)
                    if s is not None and _POINT.match(s):
                        names.append(s)
            for s in names:
                seen.add(s)
                if s not in points:
                    findings.append(
                        Finding(
                            self.name, src.rel, node.lineno,
                            f"unregistered fault point {s!r} — add it "
                            "to FAULT_POINTS",
                        )
                    )
        return findings

    def finalize(self, ctx: AnalysisContext) -> List[Finding]:
        if not ctx.config.get("require_call_sites", True):
            return []
        points = self._points(ctx)
        seen = ctx.config.get("_fault_sites", set())
        rel = next(
            (s.rel for s in ctx.sources if s.rel.endswith("faults.py")),
            ctx.sources[0].rel if ctx.sources else "<tree>",
        )
        return [
            Finding(
                self.name, rel, 1,
                f"registered fault point {p!r} has no production call "
                "site — dead registry entry",
            )
            for p in sorted(set(points) - set(seen))
        ]


# ---- metrics-families ----
@register
class MetricsFamiliesRule(Rule):
    name = "metrics-families"
    description = (
        "metric family names must be kueue_-prefixed, grammar-valid "
        "and unique, with non-empty HELP (static half of the "
        "exposition lint; the runtime grammar/histogram invariants "
        "stay in tests/test_observability.py); families under the "
        "exposed-at-zero prefixes (kueue_gateway_*, kueue_slo_*, "
        "kueue_global_*, kueue_provisioning_*, kueue_elastic_*, "
        "kueue_worker_*, kueue_hedge*) must be materialized at zero "
        "in their defining module"
    )

    _FAMILY_GRAMMAR = re.compile(r"^[a-z][a-z0-9_]*$")
    _FACTORIES = {"counter", "gauge", "histogram"}
    # serving-tier families are scrape-surface contracts: dashboards
    # and burn-rate alerts must see the whole family at zero before the
    # first request/admission, so their defining module must call
    # inc/set/touch on each one (the materialize-at-zero idiom)
    _ZERO_PREFIXES = (
        "kueue_gateway_",
        "kueue_slo_",
        "kueue_global_",
        "kueue_provisioning_",
        "kueue_elastic_",
        # gray-failure health plane: worker health/RTT gauges + hedge
        # accounting (kueue_hedge covers kueue_hedges_total AND
        # kueue_hedge_rate)
        "kueue_worker_",
        "kueue_hedge",
    )
    _ZERO_CALLS = {"inc", "set", "touch"}

    def _resolve_name(
        self, node: ast.AST, consts: Dict[str, str]
    ) -> Optional[str]:
        s = str_const(node)
        if s is not None:
            return s
        if isinstance(node, ast.JoinedStr):
            parts: List[str] = []
            for v in node.values:
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    parts.append(v.value)
                elif isinstance(v, ast.FormattedValue) and isinstance(
                    v.value, ast.Name
                ):
                    sub = consts.get(v.value.id)
                    if sub is None:
                        return None
                    parts.append(sub)
                else:
                    return None
            return "".join(parts)
        return None

    def check(self, src: SourceFile, ctx: AnalysisContext) -> List[Finding]:
        if not ctx.config.get("metrics_all_files") and (
            "/metrics/" not in f"/{src.rel}"
        ):
            return []
        prefix = ctx.config.get("metrics_prefix", "kueue_")
        consts = module_str_constants(src.tree)
        families = ctx.config.setdefault("_metric_families", {})
        findings: List[Finding] = []
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (
                isinstance(fn, ast.Attribute) and fn.attr in self._FACTORIES
            ):
                continue
            if not node.args:
                continue
            name = self._resolve_name(node.args[0], consts)
            if name is None:
                continue
            if not self._FAMILY_GRAMMAR.match(name):
                findings.append(
                    Finding(
                        self.name, src.rel, node.lineno,
                        f"metric family {name!r} violates the "
                        "Prometheus name grammar",
                    )
                )
            elif not name.startswith(prefix):
                findings.append(
                    Finding(
                        self.name, src.rel, node.lineno,
                        f"metric family {name!r} lacks the "
                        f"{prefix!r} namespace prefix",
                    )
                )
            prev = families.get(name)
            if prev is not None and prev != (src.rel, node.lineno):
                findings.append(
                    Finding(
                        self.name, src.rel, node.lineno,
                        f"duplicate metric family {name!r} (first "
                        f"registered at {prev[0]}:{prev[1]})",
                    )
                )
            families.setdefault(name, (src.rel, node.lineno))
            help_text = (
                str_const(node.args[1]) if len(node.args) > 1 else None
            )
            if len(node.args) > 1 and help_text == "":
                findings.append(
                    Finding(
                        self.name, src.rel, node.lineno,
                        f"metric family {name!r} has an empty HELP "
                        "string",
                    )
                )
        findings.extend(self._zero_exposure(src, ctx, consts))
        return findings

    def _zero_exposure(
        self, src: SourceFile, ctx: AnalysisContext, consts: Dict[str, str]
    ) -> List[Finding]:
        """Families under the exposed-at-zero prefixes must have an
        ``self.<attr>.inc/set/touch(...)`` call in the module that
        registers them — the static proxy for "the scrape surface is
        complete before the first observation"."""
        prefixes = tuple(
            ctx.config.get("metrics_zero_prefixes", self._ZERO_PREFIXES)
        )
        if not prefixes:
            return []
        # self.<attr> = r.counter("<name>", ...) assignments
        by_attr: Dict[str, Tuple[str, int]] = {}
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
                continue
            tgt = node.targets[0]
            if not (
                isinstance(tgt, ast.Attribute)
                and isinstance(tgt.value, ast.Name)
                and tgt.value.id == "self"
            ):
                continue
            call = node.value
            if not (
                isinstance(call, ast.Call)
                and isinstance(call.func, ast.Attribute)
                and call.func.attr in self._FACTORIES
                and call.args
            ):
                continue
            name = self._resolve_name(call.args[0], consts)
            if name is not None and name.startswith(prefixes):
                by_attr[tgt.attr] = (name, node.lineno)
        if not by_attr:
            return []
        materialized = set()
        for node in ast.walk(src.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in self._ZERO_CALLS
            ):
                continue
            v = node.func.value
            if (
                isinstance(v, ast.Attribute)
                and isinstance(v.value, ast.Name)
                and v.value.id == "self"
            ):
                materialized.add(v.attr)
        return [
            Finding(
                self.name, src.rel, lineno,
                f"metric family {name!r} is not materialized at zero "
                f"(no self.{attr}.inc/set/touch call in this module — "
                "the scrape surface must be complete before the first "
                "observation)",
            )
            for attr, (name, lineno) in sorted(by_attr.items())
            if attr not in materialized
        ]


# ---- kernel-mirrors ----
@register
class KernelMirrorsRule(Rule):
    name = "kernel-mirrors"
    description = (
        "every ops/*_kernel.py (+ quota) must register a resolving "
        "host mirror and an existing parity test in KERNEL_MIRRORS; "
        "every SHARDED_KERNELS entry must appear there too"
    )

    def _registries(
        self, ctx: AnalysisContext
    ) -> Tuple[Dict[str, Tuple[str, str]], Dict[str, str]]:
        mirrors = ctx.config.get("kernel_mirrors")
        sharded = ctx.config.get("sharded_kernels")
        if mirrors is None:
            from kueue_tpu.ops import KERNEL_MIRRORS

            mirrors = KERNEL_MIRRORS
        if sharded is None:
            from kueue_tpu.parallel import SHARDED_KERNELS

            sharded = SHARDED_KERNELS
        return dict(mirrors), dict(sharded)

    def _scored(self, ctx: AnalysisContext) -> Dict[str, Tuple[str, str]]:
        scored = ctx.config.get("scored_kernels")
        if scored is None:
            if "kernel_mirrors" in ctx.config:
                # fixture run overriding the mirror registry without a
                # scored registry: none, by construction
                return {}
            from kueue_tpu.ops import SCORED_KERNELS

            scored = SCORED_KERNELS
        return dict(scored)

    def finalize(self, ctx: AnalysisContext) -> List[Finding]:
        stems = ctx.config.get("kernel_stems")
        anchor = next(
            (s.rel for s in ctx.sources if s.rel.endswith("ops/__init__.py")),
            ctx.sources[0].rel if ctx.sources else "<tree>",
        )
        if stems is None:
            stems = {
                s.rel.rsplit("/", 1)[-1][: -len(".py")]
                for s in ctx.sources
                if "/ops/" in f"/{s.rel}"
                and s.rel.endswith("_kernel.py")
            }
            if any(s.rel.endswith("ops/quota.py") for s in ctx.sources):
                stems.add("quota")  # the tree recurrences are device code
            if not stems:
                return []
        mirrors, sharded = self._registries(ctx)
        findings: List[Finding] = []
        for stem in sorted(set(stems) - set(mirrors)):
            findings.append(
                Finding(
                    self.name, anchor, 1,
                    f"device kernel {stem!r} has no registered host "
                    "mirror — add a numpy/host twin + parity test to "
                    "KERNEL_MIRRORS",
                )
            )
        for stem in sorted(set(mirrors) - set(stems)):
            findings.append(
                Finding(
                    self.name, anchor, 1,
                    f"KERNEL_MIRRORS entry {stem!r} has no kernel file "
                    "— stale registry entry",
                )
            )
        for stem in sorted(set(sharded) - set(mirrors)):
            findings.append(
                Finding(
                    self.name, anchor, 1,
                    f"sharded kernel {stem!r} (SHARDED_KERNELS) has no "
                    "registered host mirror",
                )
            )
        for stem, (mirror, test_path) in sorted(mirrors.items()):
            self._check_resolves(
                stem, mirror, "mirror", anchor, findings
            )
            if test_path is not None:
                tf = os.path.join(ctx.root, test_path)
                if not (os.path.isfile(tf) and os.path.getsize(tf) > 0):
                    findings.append(
                        Finding(
                            self.name, anchor, 1,
                            f"kernel {stem!r}: parity test "
                            f"{test_path!r} missing or empty",
                        )
                    )
        for stem, entry in sorted(sharded.items()):
            self._check_resolves(
                stem, entry, "sharded entry point", anchor, findings
            )
        # policy-scored entry points (kueue_tpu/policy): every
        # SCORED_KERNELS entry must name a kernel registered above,
        # resolve both halves, and carry an existing parity test —
        # a scored kernel cannot ship without a bit-exact scored mirror
        for ref, (mirror, test_path) in sorted(self._scored(ctx).items()):
            if ":" not in ref:
                findings.append(
                    Finding(
                        self.name, anchor, 1,
                        f"scored kernel {ref!r} is not a "
                        "'module_stem:entry_point' reference",
                    )
                )
                continue
            stem, attr = ref.split(":", 1)
            if stem not in mirrors:
                findings.append(
                    Finding(
                        self.name, anchor, 1,
                        f"scored kernel {ref!r}: module {stem!r} is not "
                        "registered in KERNEL_MIRRORS",
                    )
                )
            self._check_resolves(
                ref, f"kueue_tpu.ops.{stem}:{attr}",
                "scored entry point", anchor, findings,
            )
            self._check_resolves(
                ref, mirror, "scored mirror", anchor, findings
            )
            if test_path is not None:
                tf = os.path.join(ctx.root, test_path)
                if not (os.path.isfile(tf) and os.path.getsize(tf) > 0):
                    findings.append(
                        Finding(
                            self.name, anchor, 1,
                            f"scored kernel {ref!r}: parity test "
                            f"{test_path!r} missing or empty",
                        )
                    )
        return findings

    def _check_resolves(
        self,
        stem: str,
        ref: str,
        what: str,
        anchor: str,
        findings: List[Finding],
    ) -> None:
        if ":" not in ref:
            findings.append(
                Finding(
                    self.name, anchor, 1,
                    f"kernel {stem!r}: {what} {ref!r} is not a "
                    "'module:attr' reference",
                )
            )
            return
        mod_name, attr = ref.split(":", 1)
        try:
            mod = importlib.import_module(mod_name)
        except ImportError as e:
            findings.append(
                Finding(
                    self.name, anchor, 1,
                    f"kernel {stem!r}: {what} module {mod_name!r} "
                    f"does not import ({e})",
                )
            )
            return
        if not hasattr(mod, attr):
            findings.append(
                Finding(
                    self.name, anchor, 1,
                    f"kernel {stem!r}: {what} {ref!r} does not resolve",
                )
            )
