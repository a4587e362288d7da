"""import-boundary: the stdlib-only surfaces stay stdlib-only.

A module import graph over the tree proves, statically, the contracts
that today live in docstrings and CLAUDE.md prose:

- The PARENT process of ``tools/trace_check.py`` and ``chip_smoke.py``
  never imports jax/numpy/ksim_tpu: its verdict must exist under ANY
  hardware condition, including a backend whose init hangs, and
  ``chip_smoke.py``'s parent must never hold the chip its one server
  child needs.  Child payloads (the ``child*`` / ``_child*`` functions,
  which only ever run in subprocesses) are the sanctioned exception.
- ``ksim_tpu/obs.py``, ``ksim_tpu/faults.py`` and ``ksim_tpu/errors.py``
  must not reach jax or numpy AT IMPORT TIME, transitively through
  their ksim_tpu-internal imports (function-scope lazy imports — the
  guarded ``jax.profiler`` bridge — stay legal).  This is what lets the
  fault/trace planes configure themselves from the environment inside
  stdlib-only subprocess parents.
- ``tools/ksimlint`` itself may import NOTHING outside the stdlib: the
  analyzer must run in any environment and must never execute the code
  it analyzes.

Scopes:

- ``import-time``: module-scope imports only (including class bodies
  and top-level if/try blocks; ``if TYPE_CHECKING:`` is skipped),
  chased transitively through ksim_tpu-internal modules — the finding
  message carries the offending import chain.
- ``parent-child``: module scope must be clean, and function-scope
  forbidden imports are only legal inside top-level functions whose
  name starts with ``child``/``_child``.
- ``everywhere``: no forbidden import at any scope.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from tools.ksimlint.core import Finding, Project, SourceFile

RULE = "import-boundary"

_ACCEL = frozenset({"jax", "jaxlib", "numpy"})


@dataclass(frozen=True)
class Boundary:
    target: str  # file or directory prefix, repo-relative posix
    forbidden: frozenset[str]  # top-level package names
    scope: str  # "import-time" | "parent-child" | "everywhere"
    child_prefixes: tuple[str, ...] = ("child", "_child")


DEFAULT_BOUNDARIES: tuple[Boundary, ...] = (
    Boundary("tools/trace_check.py", _ACCEL | {"ksim_tpu"}, "parent-child"),
    Boundary("chip_smoke.py", _ACCEL | {"ksim_tpu"}, "parent-child"),
    Boundary("tools/ksimlint", _ACCEL | {"ksim_tpu", "tests"}, "everywhere"),
    Boundary("ksim_tpu/obs.py", _ACCEL, "import-time"),
    Boundary("ksim_tpu/faults.py", _ACCEL, "import-time"),
    Boundary("ksim_tpu/errors.py", _ACCEL, "import-time"),
    # The trace ingestion plane: parsers/registry/resample must stay
    # stdlib-only at import time (they configure and fail cleanly in
    # jax-free processes — stdlib-only parents, the HTTP surface); jax may
    # enter only through the compile path's function-scope imports.
    Boundary("ksim_tpu/traces", _ACCEL, "import-time"),
)


def _is_type_checking(test: ast.expr) -> bool:
    return (isinstance(test, ast.Name) and test.id == "TYPE_CHECKING") or (
        isinstance(test, ast.Attribute) and test.attr == "TYPE_CHECKING"
    )


def _resolve_import_from(node: ast.ImportFrom, rel: str) -> list[str]:
    """Dotted module names an ImportFrom reaches, with RELATIVE imports
    resolved against the importing file's package (a relative import is
    just spelling — it must not bypass the boundary).  Each alias is
    also emitted as a possible submodule (``from .engine import replay``
    imports ksim_tpu.engine.replay); non-module aliases resolve to no
    file downstream and are harmless."""
    if node.level == 0:
        return [node.module] if node.module else []
    dir_parts = rel.split("/")[:-1]
    base = dir_parts[: len(dir_parts) - (node.level - 1)]
    if not base or len(dir_parts) < node.level - 1:
        return []  # relative import escaping the scanned tree
    if node.module:
        base = base + node.module.split(".")
    prefix = ".".join(base)
    return [prefix] + [f"{prefix}.{a.name}" for a in node.names if a.name != "*"]


def module_scope_imports(tree: ast.Module, rel: str = "") -> list[tuple[str, int]]:
    """(module, line) for every import executed at import time: module
    scope, class bodies, top-level if/try/with — NOT function bodies,
    NOT ``if TYPE_CHECKING:`` branches.  Relative imports resolve
    against ``rel``'s package."""
    out: list[tuple[str, int]] = []

    def walk(stmts) -> None:
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(s, ast.Import):
                out.extend((a.name, s.lineno) for a in s.names)
            elif isinstance(s, ast.ImportFrom):
                out.extend((m, s.lineno) for m in _resolve_import_from(s, rel))
            elif isinstance(s, ast.If):
                if not _is_type_checking(s.test):
                    walk(s.body)
                walk(s.orelse)
            elif isinstance(s, ast.Try):
                walk(s.body)
                for h in s.handlers:
                    walk(h.body)
                walk(s.orelse)
                walk(s.finalbody)
            elif isinstance(s, (ast.With, ast.AsyncWith)):
                walk(s.body)
            elif isinstance(s, ast.ClassDef):
                walk(s.body)

    walk(tree.body)
    return out


def _internal_files(project: Project, module: str) -> list[str]:
    """Repo files executed when ``module`` (dotted, ksim_tpu-internal)
    is imported: every ancestor package __init__ plus the module file."""
    parts = module.split(".")
    files: list[str] = []
    for i in range(1, len(parts) + 1):
        prefix = "/".join(parts[:i])
        if i < len(parts):
            files.append(f"{prefix}/__init__.py")
        else:
            if f"{prefix}/__init__.py" in project.files:
                files.append(f"{prefix}/__init__.py")
            elif f"{prefix}.py" in project.files:
                files.append(f"{prefix}.py")
    return [f for f in files if f in project.files]


def _import_time_chain(
    project: Project,
    rel: str,
    forbidden: frozenset[str],
    seen: dict[str, "list[str] | None"],
) -> "list[str] | None":
    """DFS: the first chain of module-scope imports from ``rel`` that
    reaches a forbidden top-level package, or None.  ``seen`` memoizes
    per-file results (None = proven clean)."""
    if rel in seen:
        return seen[rel]
    seen[rel] = None  # cycle guard: a cycle cannot introduce new imports
    sf = project.files.get(rel)
    if sf is None:
        return None
    for module, line in module_scope_imports(sf.tree, rel):
        top = module.partition(".")[0]
        if top in forbidden:
            chain = [f"{rel}:{line} imports {module}"]
            seen[rel] = chain
            return chain
        # Follow any module that resolves to a file in the analyzed
        # tree (stdlib and third-party names resolve to nothing).
        for sub in _internal_files(project, module):
            if sub == rel:
                continue
            tail = _import_time_chain(project, sub, forbidden, seen)
            if tail:
                chain = [f"{rel}:{line} imports {module}"] + tail
                seen[rel] = chain
                return chain
    return None


def _first_line(chain: list[str]) -> int:
    # "path:LINE imports x" -> LINE of the boundary file's own import
    return int(chain[0].split(" ", 1)[0].rsplit(":", 1)[1])


def _check_import_time(
    project: Project, sf: SourceFile, b: Boundary, findings: list[Finding]
) -> None:
    chain = _import_time_chain(project, sf.rel, b.forbidden, {})
    if chain:
        findings.append(
            Finding(
                RULE,
                sf.rel,
                _first_line(chain),
                f"{sf.rel} must not reach {{{', '.join(sorted(b.forbidden))}}} "
                f"at import time: {' -> '.join(chain)}",
            )
        )


def _all_imports(node, rel: str) -> list[tuple[str, int]]:
    out = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Import):
            out.extend((a.name, sub.lineno) for a in sub.names)
        elif isinstance(sub, ast.ImportFrom):
            out.extend((m, sub.lineno) for m in _resolve_import_from(sub, rel))
    return out


def _check_parent_child(sf: SourceFile, b: Boundary, findings: list[Finding]) -> None:
    for module, line in module_scope_imports(sf.tree, sf.rel):
        if module.partition(".")[0] in b.forbidden:
            findings.append(
                Finding(
                    RULE,
                    sf.rel,
                    line,
                    f"stdlib-only parent imports {module} at module scope "
                    "(move it into a child payload function)",
                )
            )
    for stmt in sf.tree.body:
        if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if stmt.name.startswith(b.child_prefixes):
            continue  # sanctioned child payload: runs only in subprocesses
        for module, line in _all_imports(stmt, sf.rel):
            if module.partition(".")[0] in b.forbidden:
                findings.append(
                    Finding(
                        RULE,
                        sf.rel,
                        line,
                        f"parent-side function {stmt.name!r} imports {module} "
                        f"(only child payload functions "
                        f"({'/'.join(b.child_prefixes)}*) may)",
                    )
                )


def _check_everywhere(sf: SourceFile, b: Boundary, findings: list[Finding]) -> None:
    for module, line in _all_imports(sf.tree, sf.rel):
        if module.partition(".")[0] in b.forbidden:
            findings.append(
                Finding(
                    RULE,
                    sf.rel,
                    line,
                    f"{sf.rel} is stdlib-only but imports {module}",
                )
            )


def check(
    project: Project, boundaries: tuple[Boundary, ...] = DEFAULT_BOUNDARIES
) -> list[Finding]:
    findings: list[Finding] = []
    for b in boundaries:
        for rel, sf in project.files.items():
            if not (rel == b.target or rel.startswith(b.target.rstrip("/") + "/")):
                continue
            if b.scope == "import-time":
                _check_import_time(project, sf, b, findings)
            elif b.scope == "parent-child":
                _check_parent_child(sf, b, findings)
            elif b.scope == "everywhere":
                _check_everywhere(sf, b, findings)
            else:  # pragma: no cover - config error
                raise ValueError(f"unknown boundary scope {b.scope!r}")
    return findings
