"""Compiled-program corpus: ledger records joined to retained module texts.

A corpus directory is what the compile ledger writes (PR 10 + this PR):
``ledger-<pid>.jsonl`` record streams plus ``module-<fingerprint>.mlir``
canonicalized StableHLO texts, deduped by content address. This module
loads one or more such directories into :class:`CompiledProgram` objects —
each the join of every ledger record carrying a fingerprint with the
retained text for that fingerprint — and runs the ``scope = "ir"``
checkers over them.

The join is deliberately tolerant in both directions: a record without a
retained text still checks the record-level rules (the committed
``tests/fixtures/costmodel/ledger`` predates text retention and must keep
scanning clean), and a bare
``.mlir`` file without a record still checks the text-level rules (so a
module pasted into a fixture directory is lintable on its own). What is
*not* tolerated is a lying content address: a ``module-<fp>.mlir`` whose
canonicalized content no longer hashes to ``<fp>`` gets an IR000 finding —
every other rule's anchor, the exec cache, and the dup-waste accounting all
trust that name.
"""
from __future__ import annotations

import json
import os
import re
import hashlib
from typing import Dict, Iterable, List, Optional, Sequence

from ..core import Checker, Finding, SourceFile
from . import parser as irparser

__all__ = ["CompiledProgram", "Corpus", "IRChecker", "lint_corpus",
           "lint_ir_paths", "iter_corpus_dirs"]

_MODULE_FILE_RE = re.compile(r"^module-([0-9a-f]{16,64})\.mlir$")
_LEDGER_FILE_RE = re.compile(r"^ledger-.*\.jsonl$")
_MESH_AXIS_RE = re.compile(r"([A-Za-z_][\w.]*)=(\d+)")


def mesh_size_from_key(key: Dict) -> Optional[int]:
    """Device count implied by a trigger key's ``mesh`` label
    (``"dp=2,mp=2"`` -> 4); None when the key declares no mesh."""
    label = key.get("mesh") if isinstance(key, dict) else None
    if not isinstance(label, str):
        return None
    axes = _MESH_AXIS_RE.findall(label)
    if not axes:
        return None
    n = 1
    for _, size in axes:
        n *= int(size)
    return n


class CompiledProgram:
    """One distinct compiled program: its fingerprint, every ledger record
    that produced it, and (when retained) the canonicalized module text."""

    __slots__ = ("fingerprint", "records", "text", "text_path", "path",
                 "_module", "_fp_seen")

    def __init__(self, fingerprint: str, path: str):
        self.fingerprint = fingerprint
        self.records: List[Dict] = []
        self.text: Optional[str] = None
        self.text_path: Optional[str] = None
        #: repo-relative display path findings anchor to (module file when
        #: retained, else the ledger file of the first record)
        self.path = path
        self._module: Optional[irparser.IRModule] = None
        self._fp_seen: Dict[str, int] = {}

    @property
    def site(self) -> str:
        return str(self.records[0].get("site", "")) if self.records else ""

    @property
    def key(self) -> Dict:
        k = self.records[0].get("key") if self.records else None
        return k if isinstance(k, dict) else {}

    @property
    def module(self) -> Optional[irparser.IRModule]:
        if self._module is None and self.text is not None:
            self._module = irparser.IRModule(self.text)
        return self._module

    def anchor(self) -> str:
        """Short site+key context appended to every finding message so an
        offline report says *which compile* — the CompileRecord's trigger —
        produced the flagged program."""
        bits = []
        if self.site:
            bits.append(f"site={self.site}")
        for k in ("endpoint", "bucket", "mesh", "dtype", "op"):
            v = self.key.get(k)
            if v is not None:
                bits.append(f"{k}={v}")
        bits.append(f"fp={self.fingerprint[:12]}")
        return " ".join(bits)

    def finding(self, rule: str, message: str, line: int = 1,
                snippet: str = "") -> Finding:
        """Build a Finding with the same drift-stable fingerprint scheme the
        Python scanner uses (rule + path + snippet + occurrence index) so IR
        findings ride the existing baseline/SARIF machinery unchanged."""
        snippet = snippet or f"fp={self.fingerprint[:12]}"
        idx = self._fp_seen.get((rule, snippet), 0)
        self._fp_seen[(rule, snippet)] = idx + 1
        raw = f"{rule}|{self.path}|{snippet}|{idx}"
        fp = hashlib.sha256(raw.encode("utf-8")).hexdigest()[:16]
        return Finding(rule, self.path, line, 0,
                       f"{message} [{self.anchor()}]", snippet, fp)


class Corpus:
    """Every program found under a set of corpus directories."""

    def __init__(self, root: Optional[str] = None):
        self.root = root
        self.programs: List[CompiledProgram] = []
        self.errors: List[Finding] = []        # IR000 integrity findings
        self._by_fp: Dict[str, CompiledProgram] = {}

    def _rel(self, filename: str) -> str:
        return SourceFile._relpath(filename, self.root)

    def _program(self, fp: str, path: str) -> CompiledProgram:
        prog = self._by_fp.get(fp)
        if prog is None:
            prog = CompiledProgram(fp, path)
            self._by_fp[fp] = prog
            self.programs.append(prog)
        return prog

    def load_dir(self, d: str):
        """Load one directory (recursively): ledger records first so module
        texts attach to programs that already carry site/key context."""
        ledgers: List[str] = []
        modules: List[str] = []
        for dirpath, dirnames, filenames in os.walk(d):
            dirnames[:] = sorted(x for x in dirnames if x != "__pycache__")
            for n in sorted(filenames):
                if _LEDGER_FILE_RE.match(n):
                    ledgers.append(os.path.join(dirpath, n))
                elif _MODULE_FILE_RE.match(n):
                    modules.append(os.path.join(dirpath, n))
        for path in ledgers:
            self._load_ledger(path)
        for path in modules:
            self._load_module(path)

    def _load_ledger(self, path: str):
        rel = self._rel(path)
        try:
            with open(path, "r", encoding="utf-8") as f:
                lines = f.read().splitlines()
        except OSError:
            return
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if not isinstance(rec, dict):
                continue
            fp = rec.get("fingerprint")
            if not isinstance(fp, str) or not fp:
                continue
            self._program(fp, rel).records.append(rec)

    def _load_module(self, path: str):
        m = _MODULE_FILE_RE.match(os.path.basename(path))
        named_fp = m.group(1) if m else ""
        rel = self._rel(path)
        try:
            with open(path, "r", encoding="utf-8") as f:
                text = f.read()
        except OSError:
            return
        actual = irparser.fingerprint(text)
        if named_fp and not actual.startswith(named_fp) \
                and named_fp != actual:
            raw = f"IR000|{rel}|{named_fp}"
            self.errors.append(Finding(
                "IR000", rel, 1, 0,
                f"module text does not hash to its filename fingerprint "
                f"(content address {actual[:12]}.., filename {named_fp[:12]}"
                "..) — retained corpus is corrupt; every downstream rule, "
                "the exec cache, and dup-waste accounting key on this name",
                snippet=f"fp={named_fp[:12]}",
                fingerprint=hashlib.sha256(
                    raw.encode("utf-8")).hexdigest()[:16]))
            return
        prog = self._by_fp.get(actual) or self._program(actual, rel)
        prog.text = text
        prog.text_path = rel
        prog.path = rel          # anchor findings at the text once we have it
        prog._module = None


class IRChecker(Checker):
    """Base for corpus-scoped rules: ``scope = "ir"`` keeps them inert in
    Python file/project scans while :func:`~..core.ruleset_digest` still
    covers them (an edited IR rule cold-scans the Python cache too — one
    digest, one rule registry)."""

    scope = "ir"

    def check_corpus(self, corpus: Corpus) -> Iterable[Finding]:
        raise NotImplementedError


def iter_corpus_dirs(paths: Sequence[str]) -> List[str]:
    out = [p for p in paths if os.path.isdir(p)]
    return out


def lint_corpus(corpus: Corpus,
                rules: Optional[Sequence[str]] = None) -> List[Finding]:
    from ..core import all_checkers
    findings: List[Finding] = list(corpus.errors)
    for checker in all_checkers():
        if checker.scope != "ir":
            continue
        findings.extend(checker.check_corpus(corpus))
    wanted = {r.upper() for r in rules} if rules else None
    if wanted is not None:
        findings = [f for f in findings if f.rule in wanted]
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def lint_ir_paths(paths: Sequence[str],
                  rules: Optional[Sequence[str]] = None,
                  root: Optional[str] = None) -> List[Finding]:
    """Scan ledger corpus directories with the IR rules — the ``--ir``
    entry point. All directories load into ONE corpus so cross-bucket rules
    (IR1005) see the fleet's programs together, matching how the ledger's
    own duplicate detection treats a shared directory."""
    corpus = Corpus(root=root)
    for d in iter_corpus_dirs(paths):
        corpus.load_dir(d)
    return lint_corpus(corpus, rules=rules)
