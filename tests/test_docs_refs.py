"""Documents point at files and `make` targets that exist.

Every repo-relative ``*.py`` / ``*.json`` / ``*.md`` path and every
``make <target>`` a document mentions must resolve in the checkout.  A
path resolves when a file is named by it or ends with it
(``engine/core.py`` names ``ksim_tpu/engine/core.py``), or when it
resolves against the document's own directory (``../tools/x.py``).

Not checked, because they are not this repo's: paths of the reference
simulator's tree and of third-party packages (``_FOREIGN``), absolute
paths, patterns (``BENCH_*.json``, ``<id>.json``) and bare lower-case
``*.json`` names, which are a user's own files (``snap.json``,
``out.json``).  A ``make`` mention counts where it is code: after a
backtick, at the start of a line, or in a workflow's ``run:``.
"""

from __future__ import annotations

import os
import posixpath
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_FOREIGN = ("simulator/", "jax/")
_FOREIGN_TARGETS = {"docker_up"}  # the reference simulator's Makefile

_PATH = re.compile(
    r"(?<![\w/<*{\-])((?:\.\./)*(?:[\w.\-]+/)*[\w.\-]+\.(?:py|json|md))(?![\w*])"
)
_MAKE = re.compile(r"(?:`|^[ \t]*(?:\$ )?|run:[ \t]*)make ([a-z][a-z0-9_\-]*)", re.M)


_SKIP_DIRS = {
    ".git", "__pycache__", ".jax_cache", ".pytest_cache", ".hypothesis",
    "chiprun_out", "parent_src", "final_src",
}


def _tree() -> list[str]:
    """Every file of the checkout, repo-relative (no git needed: the
    suite also runs in a bare copy of the committed files)."""
    files = []
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        rel = Path(root).relative_to(REPO).as_posix()
        files += [posixpath.normpath(posixpath.join(rel, n)) for n in names]
    return files


_FILES = _tree()
_TARGETS = set(
    re.findall(r"^([a-z][a-z0-9_\-]*):", (REPO / "Makefile").read_text(), re.M)
)


def _references(doc: str) -> tuple[set[str], set[str]]:
    text = (REPO / doc).read_text()
    paths = {
        p
        for p in _PATH.findall(text)
        if not p.startswith(_FOREIGN)
        and not ("/" not in p and p.endswith(".json") and p == p.lower())
    }
    return paths, set(_MAKE.findall(text)) - _FOREIGN_TARGETS


def _resolves(path: str, doc: str) -> bool:
    local = posixpath.normpath(posixpath.join(posixpath.dirname(doc), path))
    return any(
        f == path or f == local or f.endswith("/" + path) for f in _FILES
    )


_DOCUMENTS = [
    "README.md",
    "CLAUDE.md",
    "Makefile",
    ".github/workflows/ci.yml",
    ".claude/skills/verify/SKILL.md",
] + [
    f
    for f in sorted(_FILES)
    if f.startswith("docs/") and f.endswith(".md") and any(_references(f))
]


@pytest.mark.parametrize("doc", _DOCUMENTS)
def test_document_mentions_only_what_exists(doc):
    paths, targets = _references(doc)
    missing = sorted(p for p in paths if not _resolves(p, doc))
    assert not missing, f"{doc} names files that do not exist: {missing}"
    unknown = sorted(targets - _TARGETS)
    assert not unknown, f"{doc} names make targets the Makefile lacks: {unknown}"
