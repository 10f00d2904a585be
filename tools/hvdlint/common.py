"""Shared infrastructure for the hvdlint checkers.

Findings, source-tree walking, dotted-name resolution and the pragma
grammar live here so each rule module is just its analysis.

Pragma grammar (``docs/static_analysis.md``)::

    # hvdlint: allow(<rule>[, <rule>...])

placed on the flagged line, the line directly above it, or the line of
the enclosing rank-conditional statement.  Rule names are the checker
slugs (``rank-divergent``, ``env-registry``, ``metrics-drift``).
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

# Directories scanned by default, relative to the repo root (ISSUE 10:
# the correctness surface is the library, its tests and the examples).
DEFAULT_SCAN_DIRS = ("horovod_tpu", "tests", "examples", "tools", "ci")

_SKIP_PARTS = {"__pycache__", ".git", ".pytest_cache", "build", "node_modules"}

_PRAGMA_RE = re.compile(r"#\s*hvdlint:\s*allow\(([^)]*)\)")

# Every pragma that actually suppressed a finding during a rule run is
# recorded here as (repo-relative path, pragma line, rule slug).  The
# ``stale-pragma`` rule re-runs the pragma-consuming rules against a
# cleared registry and reports the pragmas that were never consulted —
# escape-hatch rot.  Rules record via Source.allowed() (Python) or
# record_pragma_hit() directly (the native C++ scanner).
PRAGMA_HITS: Set[Tuple[str, int, str]] = set()


def record_pragma_hit(path: str, line: int, rule: str) -> None:
    PRAGMA_HITS.add((path, line, rule))


def clear_pragma_hits() -> None:
    PRAGMA_HITS.clear()


@dataclass(frozen=True)
class Finding:
    rule: str            # checker slug, e.g. "rank-divergent"
    path: str            # repo-relative path
    line: int            # 1-indexed; 0 for whole-file/-repo findings
    message: str

    def __str__(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        return f"{loc}: [{self.rule}] {self.message}"


def repo_root(start: Optional[str] = None) -> str:
    """The enclosing repo root: nearest ancestor holding horovod_tpu/."""
    d = os.path.abspath(start or os.getcwd())
    while True:
        if os.path.isdir(os.path.join(d, "horovod_tpu")):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            raise FileNotFoundError(
                "hvdlint: could not locate the repo root (no horovod_tpu/ "
                "in any ancestor directory); pass --root")
        d = parent


def iter_py_files(root: str,
                  dirs: Sequence[str] = DEFAULT_SCAN_DIRS) -> Iterator[str]:
    """Yield repo-relative paths of every .py file under the scan dirs."""
    for entry in dirs:
        top = os.path.join(root, entry)
        if os.path.isfile(top) and entry.endswith(".py"):
            yield entry
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in _SKIP_PARTS)
            for f in sorted(filenames):
                if f.endswith(".py"):
                    yield os.path.relpath(os.path.join(dirpath, f), root)


def iter_native_files(root: str) -> Iterator[str]:
    """Repo-relative paths of the native runtime's C++ sources."""
    cc = os.path.join(root, "horovod_tpu", "native", "cc")
    for sub in ("src", "include", "tests"):
        d = os.path.join(cc, sub)
        if not os.path.isdir(d):
            continue
        for f in sorted(os.listdir(d)):
            if f.endswith((".cc", ".h")):
                yield os.path.relpath(os.path.join(d, f), root)


class Source:
    """One parsed Python file: AST plus per-line pragma allowances."""

    def __init__(self, path: str, text: str):
        self.path = path
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=path)
        # line -> set of allowed rule slugs.  Pragmas are COMMENTS: scan
        # tokenized comment text, not raw lines, so a pragma inside a
        # string literal (e.g. a lint-test fixture) is not one.
        self.pragmas: Dict[int, Set[str]] = {}
        for line_no, comment in self._iter_comments(text):
            m = _PRAGMA_RE.search(comment)
            if m:
                rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
                self.pragmas.setdefault(line_no, set()).update(rules)

    @staticmethod
    def _iter_comments(text: str) -> Iterator[Tuple[int, str]]:
        try:
            for tok in tokenize.generate_tokens(io.StringIO(text).readline):
                if tok.type == tokenize.COMMENT:
                    yield tok.start[0], tok.string
        except (tokenize.TokenError, IndentationError, SyntaxError):
            # Unterminated constructs etc.: fall back to raw-line scan
            # (over-approximates, which only makes pragmas more lenient).
            for i, line in enumerate(text.splitlines(), start=1):
                if "#" in line:
                    yield i, line

    @classmethod
    def load(cls, root: str, rel: str) -> "Source":
        with open(os.path.join(root, rel), encoding="utf-8") as f:
            return cls(rel, f.read())

    def allowed(self, rule: str, *lines: int) -> bool:
        """True if any of the given lines (or the line above the first)
        carries ``# hvdlint: allow(<rule>)``.  Every pragma line that
        matches is recorded in PRAGMA_HITS (stale-pragma bookkeeping)."""
        candidates = set(lines)
        if lines:
            candidates.add(lines[0] - 1)
        hit = False
        for ln in candidates:
            if rule in self.pragmas.get(ln, ()):
                record_pragma_hit(self.path, ln, rule)
                hit = True
        return hit


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    if isinstance(node, ast.Call):
        # topology().is_leader — represent the call link as ().
        inner = dotted_name(node.func)
        return f"{inner}()" if inner else None
    return None


def str_const(node: ast.AST,
              consts: Optional[Dict[str, str]] = None) -> Optional[str]:
    """The string value of a Constant, or of a Name bound to a
    module-level string constant (``consts`` map)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name) and consts:
        return consts.get(node.id)
    return None


def module_str_consts(tree: ast.Module) -> Dict[str, str]:
    """Module-level ``NAME = "literal"`` bindings (resolves indirections
    like ops/compression.py's HOROVOD_COMPRESSION_VAR)."""
    out: Dict[str, str] = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            out[node.targets[0].id] = node.value.value
    return out
