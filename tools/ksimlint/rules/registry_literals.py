"""registry-literals: every taxonomy string resolves into its registry.

The trace/fault planes and the replay fallback histogram are keyed by
string literals spelled at call sites; the registries
(``faults.SITES``, ``obs.SPAN_NAMES`` / ``EVENT_NAMES``,
``engine.replay.FALLBACK_REASONS`` / ``FALLBACK_REASON_PREFIXES``) are
what docs, dashboards and the registry-sync tests consume.  This rule
scans every call site in the tree and checks BOTH directions:

- every ``FAULTS.check("...")`` literal is a declared site, and every
  declared site is wired somewhere (a dead registry entry is a lie in
  the docs);
- every declared site has a same-named span (a fault event always has
  an enclosing phase on the timeline);
- every ``TRACE.span("...")`` / ``TRACE.event("...")`` name is in the
  span/event taxonomy — and so is every name given to the two helpers
  that open spans by another spelling: ``TRACE.phase("...", ...)`` (a
  span that also feeds a Metrics timer) and ``<span>.lap("...")`` (the
  next sequential child of an open span, on any receiver) — and to
  ``TRACE.stage("...")`` (a timed stage: histograms and the profiler
  bridge, never the ring);
- every ``_expo_family("...")`` Prometheus exposition family declared
  in obs.py resolves into ``obs.METRIC_NAMES`` (and every registry
  entry is declared somewhere — a family in the registry with no
  exposition declaration would be a scrape-dashboard lie);
- every static ``_reject("...")`` / ``_Unsupported("...")`` reason in
  engine/replay.py is in FALLBACK_REASONS (and f-string reason families
  match FALLBACK_REASON_PREFIXES); registry entries must appear in the
  source as a call reason or a returned discard string;
- a NON-literal first argument to any of these calls is itself a
  finding: the registries can only vouch for strings the AST can see.

The registries are read from the defining modules' ASTs (never by
import), so the analyzer stays stdlib-only; tests/test_obs.py
cross-checks this AST view against the imported runtime values, and the
former grep-based registry-sync tests are re-backed by the scan
functions below.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from tools.ksimlint.core import Finding, Project

RULE = "registry-literals"


@dataclass(frozen=True)
class RegistryConfig:
    """Where the registries and their call sites live (overridable so
    fixture mini-trees can exercise the rule)."""

    faults_module: str = "ksim_tpu/faults.py"
    obs_module: str = "ksim_tpu/obs.py"
    replay_module: str = "ksim_tpu/engine/replay.py"
    faults_object: str = "FAULTS"  # <obj>.check(site)
    trace_object: str = "TRACE"  # <obj>.span(name) / <obj>.event(name)
    span_methods: tuple = ("span", "phase", "stage")  # <obj>.<method>(name, ...)
    lap_method: str = "lap"  # <any span>.lap(name): a child span
    metric_helper: str = "_expo_family"  # <helper>(family, kind, help)


DEFAULT_CONFIG = RegistryConfig()


@dataclass(frozen=True)
class Registries:
    sites: tuple[str, ...]
    sites_line: int
    span_names: tuple[str, ...]
    event_names: tuple[str, ...]
    metric_names: tuple[str, ...]
    metric_names_line: int
    fallback_reasons: frozenset[str]
    fallback_reasons_line: int
    fallback_prefixes: tuple[str, ...]


def _literal_assignment(tree: ast.Module, name: str):
    """(value, line) of a module-level ``NAME = <literal>`` assignment;
    unwraps a single ``frozenset(...)`` / ``tuple(...)`` call."""
    for stmt in tree.body:
        value = None
        if isinstance(stmt, ast.Assign):
            if any(isinstance(t, ast.Name) and t.id == name for t in stmt.targets):
                value = stmt.value
        elif isinstance(stmt, ast.AnnAssign):
            if isinstance(stmt.target, ast.Name) and stmt.target.id == name:
                value = stmt.value
        if value is None:
            continue
        if (
            isinstance(value, ast.Call)
            and isinstance(value.func, ast.Name)
            and value.func.id in ("frozenset", "tuple", "set")
            and len(value.args) == 1
        ):
            value = value.args[0]
        return ast.literal_eval(value), stmt.lineno
    raise KeyError(name)


def load_registries(project: Project, cfg: RegistryConfig = DEFAULT_CONFIG) -> Registries:
    faults = project.files[cfg.faults_module].tree
    obs = project.files[cfg.obs_module].tree
    replay = project.files[cfg.replay_module].tree
    sites, sites_line = _literal_assignment(faults, "SITES")
    span_names, _ = _literal_assignment(obs, "SPAN_NAMES")
    event_names, _ = _literal_assignment(obs, "EVENT_NAMES")
    metric_names, metric_names_line = _literal_assignment(obs, "METRIC_NAMES")
    reasons, reasons_line = _literal_assignment(replay, "FALLBACK_REASONS")
    prefixes, _ = _literal_assignment(replay, "FALLBACK_REASON_PREFIXES")
    return Registries(
        sites=tuple(sites),
        sites_line=sites_line,
        span_names=tuple(span_names),
        event_names=tuple(event_names),
        metric_names=tuple(metric_names),
        metric_names_line=metric_names_line,
        fallback_reasons=frozenset(reasons),
        fallback_reasons_line=reasons_line,
        fallback_prefixes=tuple(prefixes),
    )


def _method_calls(
    project: Project, obj: "str | None", method: str, skip: frozenset[str]
):
    """Every ``<obj>.<method>(...)`` call in the tree (minus ``skip``
    files): yields (rel, call node).  ``obj=None`` matches any
    receiver."""
    for rel, sf in project.files.items():
        if rel in skip:
            continue
        for node in ast.walk(sf.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == method
                and (
                    obj is None
                    or (
                        isinstance(node.func.value, ast.Name)
                        and node.func.value.id == obj
                    )
                )
            ):
                yield rel, node


@dataclass
class LiteralScan:
    """Call-site literals: value -> [(rel, line)], plus non-literal
    call sites the registries cannot vouch for."""

    literals: dict
    dynamic: list

    def __init__(self) -> None:
        self.literals = {}
        self.dynamic = []

    def add(self, rel: str, node: ast.Call) -> None:
        arg = node.args[0] if node.args else None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            self.literals.setdefault(arg.value, []).append((rel, node.lineno))
        else:
            self.dynamic.append((rel, node.lineno))


def scan_fault_sites(
    project: Project, cfg: RegistryConfig = DEFAULT_CONFIG
) -> LiteralScan:
    """Every ``FAULTS.check(...)`` call site (the declaring module is
    excluded: it defines the idiom, the wiring lives elsewhere)."""
    scan = LiteralScan()
    for rel, node in _method_calls(
        project, cfg.faults_object, "check", frozenset({cfg.faults_module})
    ):
        scan.add(rel, node)
    return scan


def _function_calls(project: Project, fname: str):
    """Every bare ``<fname>(...)`` call in the tree: yields (rel, call
    node).  The attribute-call spelling is out of scope on purpose —
    the exposition helper is module-local by construction."""
    for rel, sf in project.files.items():
        for node in ast.walk(sf.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == fname
            ):
                yield rel, node


def scan_metric_literals(
    project: Project, cfg: RegistryConfig = DEFAULT_CONFIG
) -> LiteralScan:
    """Every ``_expo_family(...)`` exposition-family declaration — the
    lint-scannable spelling of the `/metrics` surface."""
    scan = LiteralScan()
    for rel, node in _function_calls(project, cfg.metric_helper):
        scan.add(rel, node)
    return scan


def scan_trace_literals(
    project: Project, cfg: RegistryConfig = DEFAULT_CONFIG
) -> tuple[LiteralScan, LiteralScan]:
    """(span call sites, event call sites) for the trace plane."""
    spans, events = LiteralScan(), LiteralScan()
    for method in cfg.span_methods:
        for rel, node in _method_calls(project, cfg.trace_object, method, frozenset()):
            spans.add(rel, node)
    # The lap helper is called on the span object, whatever it is named;
    # the defining module spells the method, not a site.
    for rel, node in _method_calls(
        project, None, cfg.lap_method, frozenset({cfg.obs_module})
    ):
        spans.add(rel, node)
    for rel, node in _method_calls(project, cfg.trace_object, "event", frozenset()):
        events.add(rel, node)
    return spans, events


@dataclass
class FallbackScan:
    call_reasons: dict  # literal -> [(rel, line)]
    fstring_prefixes: dict  # leading text of f-string reasons -> [(rel, line)]
    return_strings: frozenset  # every string returned anywhere in the module


def scan_fallback_reasons(
    project: Project, cfg: RegistryConfig = DEFAULT_CONFIG
) -> FallbackScan:
    """Static ``_reject(...)`` / ``_Unsupported(...)`` reasons in the
    replay module (the exact scan the registry-sync test used to
    implement inline with re+ast)."""
    rel = cfg.replay_module
    tree = project.files[rel].tree
    call_reasons: dict = {}
    fstring_prefixes: dict = {}
    return_strings: set = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fname = (
                node.func.id
                if isinstance(node.func, ast.Name)
                else getattr(node.func, "attr", "")
            )
            if fname in ("_Unsupported", "_reject") and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                    call_reasons.setdefault(arg.value, []).append((rel, node.lineno))
                elif isinstance(arg, ast.JoinedStr) and arg.values and isinstance(
                    arg.values[0], ast.Constant
                ):
                    fstring_prefixes.setdefault(str(arg.values[0].value), []).append(
                        (rel, node.lineno)
                    )
        elif (
            isinstance(node, ast.Return)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
        ):
            return_strings.add(node.value.value)
    return FallbackScan(call_reasons, fstring_prefixes, frozenset(return_strings))


def check(project: Project, cfg: RegistryConfig = DEFAULT_CONFIG) -> list[Finding]:
    findings: list[Finding] = []
    registry_modules = (cfg.faults_module, cfg.obs_module, cfg.replay_module)
    present = [m for m in registry_modules if m in project.files]
    if len(present) < len(registry_modules):
        # On the full default tree a missing registry module is a real
        # structural finding; on a partial run (one file, a subtree)
        # the registries are simply out of scope and the rule does not
        # apply.
        if project.covers_default_targets():
            for m in registry_modules:
                if m not in project.files:
                    findings.append(
                        Finding(
                            RULE,
                            m,
                            1,
                            f"registry module {m} missing from the analyzed tree",
                        )
                    )
        return findings
    try:
        regs = load_registries(project, cfg)
    except KeyError as e:
        return [
            Finding(RULE, cfg.faults_module, 1, f"registry {e} not found in source")
        ]

    def flag(rel: str, line: int, msg: str) -> None:
        findings.append(Finding(RULE, rel, line, msg))

    # -- fault sites -----------------------------------------------------
    sites = frozenset(regs.sites)
    scan = scan_fault_sites(project, cfg)
    for value, locs in sorted(scan.literals.items()):
        if value not in sites:
            for rel, line in locs:
                flag(rel, line, f"FAULTS.check site {value!r} is not declared in SITES")
    for rel, line in scan.dynamic:
        flag(rel, line, "FAULTS.check with a non-literal site name (unverifiable)")
    for site in regs.sites:
        if site not in scan.literals:
            flag(
                cfg.faults_module,
                regs.sites_line,
                f"SITES entry {site!r} has no FAULTS.check call site",
            )
        if site not in regs.span_names:
            flag(
                cfg.faults_module,
                regs.sites_line,
                f"SITES entry {site!r} has no same-named span in SPAN_NAMES",
            )

    # -- trace names -----------------------------------------------------
    spans, events = scan_trace_literals(project, cfg)
    for value, locs in sorted(spans.literals.items()):
        if value not in regs.span_names:
            for rel, line in locs:
                flag(rel, line, f"span name {value!r} is not in obs.SPAN_NAMES")
    for value, locs in sorted(events.literals.items()):
        if value not in regs.event_names:
            for rel, line in locs:
                flag(rel, line, f"event name {value!r} is not in obs.EVENT_NAMES")
    for kind, scan_ in (("span", spans), ("event", events)):
        for rel, line in scan_.dynamic:
            flag(rel, line, f"TRACE.{kind} with a non-literal name (unverifiable)")

    # -- exposition metric families --------------------------------------
    metrics = scan_metric_literals(project, cfg)
    metric_names = frozenset(regs.metric_names)
    for value, locs in sorted(metrics.literals.items()):
        if value not in metric_names:
            for rel, line in locs:
                flag(
                    rel,
                    line,
                    f"exposition family {value!r} is not in obs.METRIC_NAMES",
                )
    for rel, line in metrics.dynamic:
        flag(
            rel,
            line,
            f"{cfg.metric_helper} with a non-literal family name (unverifiable)",
        )
    for name in regs.metric_names:
        if name not in metrics.literals:
            flag(
                cfg.obs_module,
                regs.metric_names_line,
                f"METRIC_NAMES entry {name!r} has no {cfg.metric_helper} "
                "declaration (dead registry entry)",
            )

    # -- fallback reasons ------------------------------------------------
    fb = scan_fallback_reasons(project, cfg)
    for value, locs in sorted(fb.call_reasons.items()):
        if value not in regs.fallback_reasons:
            for rel, line in locs:
                flag(rel, line, f"fallback reason {value!r} not in FALLBACK_REASONS")
    for prefix, locs in sorted(fb.fstring_prefixes.items()):
        if not any(prefix.startswith(p) for p in regs.fallback_prefixes):
            for rel, line in locs:
                flag(
                    rel,
                    line,
                    f"dynamic fallback reason family {prefix!r} not covered by "
                    "FALLBACK_REASON_PREFIXES",
                )
    dead = regs.fallback_reasons - set(fb.call_reasons) - fb.return_strings
    for reason in sorted(dead):
        flag(
            cfg.replay_module,
            regs.fallback_reasons_line,
            f"FALLBACK_REASONS entry {reason!r} appears nowhere in "
            f"{cfg.replay_module} (dead registry entry)",
        )
    # Registry-definition invariants the event taxonomy depends on.
    for required in ("fault.fired", "replay.fallback"):
        if required not in regs.event_names:
            flag(
                cfg.obs_module,
                1,
                f"EVENT_NAMES must contain {required!r} (fault/fallback "
                "timeline evidence)",
            )
    return findings
