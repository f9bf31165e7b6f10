"""graft-lint engine: file index, findings, pragma plane.

The engine parses every file ONCE (``ast`` tree + ``tokenize`` comment
stream) and hands checkers a :class:`RepoIndex`; checkers return
:class:`Finding` lists and never touch the filesystem themselves, so
the whole suite stays one pass over the tree (<30s is the ci.sh
stage-0 budget; in practice it is ~2s on this host).

Suppressions ride tokenize COMMENT tokens, not regex over lines — a
pragma spelled inside a string literal (the lint test fixtures hold
exactly those) is data, not a suppression.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from pathlib import Path

#: pragma grammar: ``# graft-lint: disable=GL01[,GL03] -- reason``
_PRAGMA_RE = re.compile(
    r"#\s*graft-lint:\s*disable=(?P<codes>[A-Za-z0-9,\s]+?)"
    r"(?:\s*--\s*(?P<reason>.*\S))?\s*$")
_CODE_RE = re.compile(r"^GL\d\d$")


@dataclasses.dataclass(frozen=True)
class Finding:
    code: str        # "GL01".."GL05", "GL00" for pragma-plane defects
    path: str        # repo-relative, posix separators
    line: int        # 1-based
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"

    def sort_key(self):
        return (self.path, self.line, self.code, self.message)


@dataclasses.dataclass
class Pragma:
    line: int
    codes: frozenset  # of "GLxx"
    reason: str | None
    own_line: bool    # a full-line comment (suppresses the NEXT line too)


class SourceFile:
    """One parsed python file: tree + comment-derived pragma map."""

    def __init__(self, relpath: str, text: str):
        self.path = relpath
        self.text = text
        self.tree: ast.Module | None = None
        self.parse_error: str | None = None
        try:
            self.tree = ast.parse(text)
        except SyntaxError as e:  # surfaced as a finding by the engine
            self.parse_error = str(e)
        self.pragmas: list[Pragma] = []
        self._suppressed: dict[int, set] = {}  # line -> codes
        self._scan_pragmas()

    def _scan_pragmas(self) -> None:
        try:
            toks = list(tokenize.generate_tokens(
                io.StringIO(self.text).readline))
        except (tokenize.TokenError, SyntaxError, IndentationError):
            return
        for tok in toks:
            if tok.type != tokenize.COMMENT:
                continue
            m = _PRAGMA_RE.search(tok.string)
            if m is None:
                continue
            codes = frozenset(c.strip() for c in
                              m.group("codes").split(",") if c.strip())
            own_line = tok.string.strip() == tok.line.strip()
            self.pragmas.append(Pragma(tok.start[0], codes,
                                       m.group("reason"), own_line))
        for p in self.pragmas:
            if p.reason is None or not all(_CODE_RE.match(c)
                                           for c in p.codes):
                continue  # malformed pragmas never suppress (GL00 below)
            self._suppressed.setdefault(p.line, set()).update(p.codes)
            if p.own_line:
                self._suppressed.setdefault(p.line + 1,
                                            set()).update(p.codes)

    def suppressed(self, code: str, line: int) -> bool:
        return code in self._suppressed.get(line, ())


class RepoIndex:
    """Parsed view of the tree.  ``code`` files get the full checker
    battery; ``test`` files only the pragma plane + GL05's reference
    scan (a test asserting a family name that does not exist pins
    nothing); ``docs`` are raw text for GL02/GL05 drift checks."""

    def __init__(self, root: Path):
        self.root = root
        self.code: dict[str, SourceFile] = {}
        self.tests: dict[str, SourceFile] = {}
        self.docs: dict[str, str] = {}

    # -- construction ------------------------------------------------------

    CODE_GLOBS = ("glusterfs_tpu/**/*.py", "tools/**/*.py",
                  "__graft_entry__.py")
    TEST_GLOBS = ("tests/**/*.py",)
    DOC_GLOBS = ("docs/*.md",)

    @classmethod
    def load(cls, root: Path, only: list[str] | None = None) -> "RepoIndex":
        idx = cls(root)
        #: narrowed runs skip cross-file STALE-entry checks: deciding
        #: that a table row is dead needs the whole tree in view (a
        #: lock defined in an unscanned file must not read as gone)
        idx.full_tree = only is None

        def want(rel: str) -> bool:
            if "__pycache__" in rel:
                return False
            return only is None or any(
                rel == o or rel.startswith(o.rstrip("/") + "/")
                for o in only)

        for pat in cls.CODE_GLOBS:
            for p in sorted(root.glob(pat)):
                rel = p.relative_to(root).as_posix()
                if p.is_file() and want(rel):
                    idx.code[rel] = SourceFile(
                        rel, p.read_text(encoding="utf-8"))
        for pat in cls.TEST_GLOBS:
            for p in sorted(root.glob(pat)):
                rel = p.relative_to(root).as_posix()
                if p.is_file() and want(rel):
                    idx.tests[rel] = SourceFile(
                        rel, p.read_text(encoding="utf-8"))
        if only is None:  # doc drift checks are whole-tree only
            for pat in cls.DOC_GLOBS:
                for p in sorted(root.glob(pat)):
                    rel = p.relative_to(root).as_posix()
                    if p.is_file():
                        idx.docs[rel] = p.read_text(encoding="utf-8")
        return idx

    # -- checker conveniences ----------------------------------------------

    def file(self, relpath: str) -> SourceFile | None:
        return self.code.get(relpath) or self.tests.get(relpath)

    def all_py(self) -> dict[str, SourceFile]:
        out = dict(self.code)
        out.update(self.tests)
        return out


def pragma_findings(idx: RepoIndex) -> list[Finding]:
    """GL00 — the pragma plane checks itself: a suppression without a
    reason, or with a malformed checker id, is a finding (and never
    suppresses anything)."""
    out = []
    for sf in idx.all_py().values():
        for p in sf.pragmas:
            bad = [c for c in p.codes if not _CODE_RE.match(c)]
            if bad:
                out.append(Finding(
                    "GL00", sf.path, p.line,
                    f"malformed graft-lint pragma: {','.join(bad)!r} is "
                    "not a checker id (GLxx)"))
            if p.reason is None:
                out.append(Finding(
                    "GL00", sf.path, p.line,
                    "suppression without a reason: write "
                    "'# graft-lint: disable=GLxx -- <why this site is "
                    "exempt>'"))
    return out


class NoFilesMatched(Exception):
    """A narrowed run whose paths select nothing must not report clean."""


def run(root: Path, only: list[str] | None = None,
        timings: dict | None = None) -> list[Finding]:
    """Parse the tree, run every checker, apply suppressions.  Pass a
    dict as ``timings`` to receive per-checker wall seconds (the ci.sh
    archived-json surface that makes a slow checker visible before it
    eats the 30s stage-0 budget)."""
    import time
    from . import all_checkers

    t0 = time.perf_counter()
    idx = RepoIndex.load(root, only)
    if only is not None and not idx.code and not idx.tests:
        raise NoFilesMatched(
            f"no scanned files match {only!r} — a typo'd path must not "
            "read as a clean tree")
    if timings is not None:
        timings["parse"] = round(time.perf_counter() - t0, 3)
    findings: list[Finding] = []
    for sf in idx.all_py().values():
        if sf.parse_error is not None:
            findings.append(Finding("GL00", sf.path, 1,
                                    f"does not parse: {sf.parse_error}"))
    findings.extend(pragma_findings(idx))
    for name, check in all_checkers():
        t0 = time.perf_counter()
        findings.extend(check(idx))
        if timings is not None:
            timings[name] = round(time.perf_counter() - t0, 3)
    kept = [f for f in findings
            if f.code == "GL00"
            or not _is_suppressed(idx, f)]
    return sorted(kept, key=Finding.sort_key)


def _is_suppressed(idx: RepoIndex, f: Finding) -> bool:
    sf = idx.file(f.path)
    return sf is not None and sf.suppressed(f.code, f.line)
