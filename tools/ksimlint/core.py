"""ksimlint core: source loading, comment directives, the rule runner.

Stdlib-only BY CONTRACT (enforced by ksimlint's own import-boundary
rule): the analyzer runs in the sanitized environment, in stdlib-only
parents, and in CI shells where jax backend init may be wedged — it must never import jax, numpy, or ksim_tpu itself.  All
facts about the codebase are extracted from Python ASTs and the token
stream, never by importing the code under analysis.

Vocabulary (docs/lint.md has the full catalogue):

- A **rule** is a module under ``tools/ksimlint/rules`` exposing
  ``RULE`` (its kebab-case name) and ``check(project) -> [Finding]``.
- A **directive** is a structured comment the rules read:
  ``# guarded-by: <lock>`` on an attribute's initializing assignment,
  ``# ksimlint: lock-held(<lock>)`` / ``# ksimlint: worker-thread`` on
  a ``def`` line, and ``# ksimlint: disable=<rule>[,<rule>...]`` to
  suppress findings on that line (or, from a comment-only line, on the
  line below it).
- A **finding** is one contract violation at one source line; the run
  fails (exit 1) on any finding that is not suppressed.
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, replace

#: What ``make lint`` (and the no-argument CLI) analyzes.  tests/ is
#: deliberately out of scope: fixtures there contain SEEDED violations.
DEFAULT_TARGETS: tuple[str, ...] = ("ksim_tpu", "chip_smoke.py", "tools")

_DISABLE_RE = re.compile(r"ksimlint:\s*disable=([\w,-]+)")


@dataclass(frozen=True)
class Finding:
    """One contract violation at one source line."""

    rule: str
    path: str  # repo-relative posix path
    line: int
    message: str
    suppressed: bool = False

    def format(self) -> str:
        tag = " (suppressed)" if self.suppressed else ""
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}{tag}"

    def to_json(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "suppressed": self.suppressed,
        }


def _disabled_rules(comment: str) -> frozenset[str]:
    m = _DISABLE_RE.search(comment)
    if not m:
        return frozenset()
    return frozenset(r for r in m.group(1).split(",") if r)


class SourceFile:
    """One parsed source file: AST + per-line comment map.

    ``comments`` maps line number -> comment text (with the ``#``);
    ``comment_only`` holds lines where the comment is the whole line,
    so a directive there can apply to the statement below it.
    """

    __slots__ = ("path", "rel", "text", "tree", "comments", "comment_only")

    def __init__(self, path: str, rel: str, text: str) -> None:
        self.path = path
        self.rel = rel
        self.text = text
        self.tree = ast.parse(text, filename=rel)
        self.comments: dict[int, str] = {}
        self.comment_only: set[int] = set()
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.COMMENT:
                line = tok.start[0]
                self.comments[line] = tok.string
                if tok.line[: tok.start[1]].strip() == "":
                    self.comment_only.add(line)

    def disabled_at(self, line: int) -> frozenset[str]:
        """Rules suppressed for findings on ``line``: a disable comment
        on the line itself, or on a comment-only line directly above."""
        out = _disabled_rules(self.comments.get(line, ""))
        if line - 1 in self.comment_only:
            out |= _disabled_rules(self.comments[line - 1])
        return out

    def directive_in_range(self, start: int, end: int, pattern: re.Pattern):
        """First regex match of ``pattern`` over the comments on lines
        ``start..end`` inclusive (rules use this to read annotations
        anywhere inside a statement's line span)."""
        for ln in range(start, end + 1):
            c = self.comments.get(ln)
            if c:
                m = pattern.search(c)
                if m:
                    return m
        return None


class Project:
    """The analyzed tree: repo root + the loaded source files.
    ``targets`` records what was requested, so rules whose cross-file
    directions only make sense over the full default tree (env-contract
    dead rows) can tell a partial run apart."""

    def __init__(
        self,
        root: str,
        files: dict[str, SourceFile],
        targets: tuple[str, ...] = DEFAULT_TARGETS,
    ) -> None:
        self.root = root
        self.files = files
        self.targets = targets
        self._callgraph = None

    def callgraph(self):
        """The interprocedural layer (tools/ksimlint/callgraph.py),
        built lazily ONCE per Project and shared by every rule that asks
        — the lock-order, thread-role and exception-flow rules all walk
        the same call graph instead of re-deriving it per rule."""
        if self._callgraph is None:
            from tools.ksimlint.callgraph import CallGraph

            self._callgraph = CallGraph(self)
        return self._callgraph

    @classmethod
    def load(cls, root: str, targets: tuple[str, ...] = DEFAULT_TARGETS) -> "Project":
        root = os.path.abspath(root)
        files: dict[str, SourceFile] = {}

        def add(path: str) -> None:
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path, encoding="utf-8") as f:
                files[rel] = SourceFile(path, rel, f.read())

        for target in targets:
            path = os.path.join(root, target)
            if os.path.isfile(path):
                add(path)
            elif os.path.isdir(path):
                for dirpath, dirnames, filenames in os.walk(path):
                    dirnames[:] = sorted(
                        d
                        for d in dirnames
                        if d != "__pycache__" and not d.startswith(".")
                    )
                    for fn in sorted(filenames):
                        if fn.endswith(".py"):
                            add(os.path.join(dirpath, fn))
            else:
                # A typo'd target silently scanning nothing would make
                # the gate vacuously green — refuse loudly (exit 2).
                raise OSError(f"lint target not found: {path}")
        return cls(root, dict(sorted(files.items())), tuple(targets))

    def covers_default_targets(self) -> bool:
        """True when the run includes the whole default tree (the only
        scope where \"documented but unused\" style cross-file checks
        are meaningful)."""
        return all(t in self.targets for t in DEFAULT_TARGETS)

    def read_text(self, rel: str) -> "str | None":
        """Non-Python project file (e.g. docs/env.md); None if absent."""
        path = os.path.join(self.root, rel.replace("/", os.sep))
        if not os.path.isfile(path):
            return None
        with open(path, encoding="utf-8") as f:
            return f.read()


def mark_suppressed(project: Project, findings: list[Finding]) -> list[Finding]:
    """Apply inline suppressions; returns findings sorted by location."""
    out: list[Finding] = []
    for f in findings:
        sf = project.files.get(f.path)
        if sf is not None:
            disabled = sf.disabled_at(f.line)
            if f.rule in disabled or "all" in disabled:
                f = replace(f, suppressed=True)
        out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return out


def run(
    root: str,
    targets: tuple[str, ...] = DEFAULT_TARGETS,
    rules: "tuple[str, ...] | None" = None,
) -> list[Finding]:
    """Load the tree and run every (or the selected) rule.  Returns ALL
    findings; callers filter on ``suppressed`` for the exit status."""
    from tools.ksimlint.rules import ALL_RULES

    if rules is not None:
        unknown = sorted(set(rules) - set(ALL_RULES))
        if unknown:
            # A typo'd rule filter running zero rules would be the same
            # vacuously-green gate Project.load refuses for bad targets.
            raise ValueError(
                f"unknown rule(s) {', '.join(unknown)} "
                f"(have: {', '.join(sorted(ALL_RULES))})"
            )
    project = Project.load(root, targets)
    findings: list[Finding] = []
    for name, check in ALL_RULES.items():
        if rules is not None and name not in rules:
            continue
        findings.extend(check(project))
    return mark_suppressed(project, findings)
