"""Analysis core: findings, fingerprints, baseline, driver.

The suite is a project-native linter: each rule module encodes one of
this repo's hard-won concurrency/performance invariants (see
``decls.py`` for the registry the rules read and ADVICE.md for the
postmortems that motivated them).  Everything here is stdlib ``ast`` —
no third-party deps, no imports of the code under analysis.

Fingerprints are deliberately line-number free: ``rule|path|qualname|
stripped-source-line``.  A finding keeps the same identity when code
above it moves, so the committed baseline (ANALYSIS_BASELINE.json)
survives unrelated edits; it breaks — loudly — when the flagged line
itself changes, which is exactly when a human should re-triage it.
"""

from __future__ import annotations

import ast
import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence


@dataclass(frozen=True)
class Finding:
    """One rule violation at one source location."""

    rule: str           # rule id, e.g. "lock-order"
    path: str           # repo-relative posix path
    line: int           # 1-based line (display only; not identity)
    qualname: str       # "Class.method" / "function" / "<module>"
    message: str        # human explanation
    snippet: str        # stripped source line (identity component)

    @property
    def fingerprint(self) -> str:
        return "|".join((self.rule, self.path, self.qualname,
                         self.snippet))

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}] "
                f"{self.qualname}: {self.message}\n"
                f"    {self.snippet}")


@dataclass
class SourceFile:
    """A parsed module under analysis."""

    path: Path
    rel: str                     # repo-relative posix path
    src: str
    tree: ast.Module
    lines: List[str] = field(default_factory=list)

    def snippet(self, node: ast.AST) -> str:
        ln = getattr(node, "lineno", 0)
        if 1 <= ln <= len(self.lines):
            return self.lines[ln - 1].strip()
        return ""


@dataclass
class Context:
    """Everything a rule may read.

    ``doc_text`` / ``conftest_src`` / ``usage_files`` are normally
    loaded from the repo by :func:`build_context`; fixture tests
    override them to analyze forged samples in isolation.
    """

    files: List[SourceFile]
    decls: "object"              # decls.Decls (duck-typed for tests)
    root: Path
    doc_text: str = ""           # README + MIGRATING (knob docs)
    conftest_src: str = ""       # tests/conftest.py (knob resets)
    usage_files: List[SourceFile] = field(default_factory=list)
    _callgraph: "object" = field(default=None, repr=False)

    def all_files(self) -> List[SourceFile]:
        """Files whose ASTs count as knob *usage* (tree + tests)."""
        return self.files + self.usage_files

    def callgraph(self):
        """The project call graph, built once and shared across rules
        (the interprocedural rules all read it; rebuilding per rule
        would blow the sweep's time budget)."""
        if self._callgraph is None:
            from gigapaxos_tpu.analysis import callgraph
            self._callgraph = callgraph.build(self.files)
        return self._callgraph


class ScopedVisitor(ast.NodeVisitor):
    """NodeVisitor that tracks the Class.method qualname stack.

    Subclasses override the ``check_*`` hooks (not ``visit_ClassDef`` /
    ``visit_FunctionDef`` — those own the stack bookkeeping).
    """

    def __init__(self, sf: SourceFile):
        self.sf = sf
        self.findings: List[Finding] = []
        self._names: List[str] = []
        self._classes: List[ast.ClassDef] = []
        self._funcs: List[ast.AST] = []

    # -- stack machinery ------------------------------------------------
    @property
    def qualname(self) -> str:
        return ".".join(self._names) or "<module>"

    @property
    def cur_class(self) -> Optional[ast.ClassDef]:
        return self._classes[-1] if self._classes else None

    @property
    def cur_func(self):
        return self._funcs[-1] if self._funcs else None

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._names.append(node.name)
        self._classes.append(node)
        self.enter_class(node)
        self.generic_visit(node)
        self.leave_class(node)
        self._classes.pop()
        self._names.pop()

    def _visit_func(self, node) -> None:
        self._names.append(node.name)
        self._funcs.append(node)
        self.enter_function(node)
        self.generic_visit(node)
        self.leave_function(node)
        self._funcs.pop()
        self._names.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    # -- subclass hooks -------------------------------------------------
    def enter_class(self, node: ast.ClassDef) -> None: ...
    def leave_class(self, node: ast.ClassDef) -> None: ...
    def enter_function(self, node) -> None: ...
    def leave_function(self, node) -> None: ...

    # -- helpers --------------------------------------------------------
    def add(self, rule: str, node: ast.AST, message: str,
            qualname: Optional[str] = None) -> None:
        self.findings.append(Finding(
            rule=rule, path=self.sf.rel,
            line=getattr(node, "lineno", 0),
            qualname=qualname if qualname is not None else self.qualname,
            message=message, snippet=self.sf.snippet(node)))


# ---------------------------------------------------------------------------
# shared AST utilities

FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)


def self_attr(node: ast.AST, names=("self", "cls")) -> Optional[str]:
    """``self.X`` / ``cls.X`` -> ``"X"``, else None."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id in names):
        return node.attr
    return None


def names_read(node: ast.AST) -> set:
    """All Name ids loaded anywhere under ``node``."""
    return {n.id for n in ast.walk(node)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}


def first_arg_name(func) -> Optional[str]:
    args = func.args.posonlyargs + func.args.args
    return args[0].arg if args else None


# ---------------------------------------------------------------------------
# loading

def load_file(path: Path, root: Path) -> Optional[SourceFile]:
    try:
        src = path.read_text()
        tree = ast.parse(src, filename=str(path))
    except (SyntaxError, UnicodeDecodeError, OSError):
        return None
    try:
        rel = path.resolve().relative_to(root.resolve()).as_posix()
    except ValueError:
        rel = path.name
    return SourceFile(path=path, rel=rel, src=src, tree=tree,
                      lines=src.splitlines())


def load_tree(pkg_root: Path, repo_root: Path,
              skip_parts: Sequence[str] = ()) -> List[SourceFile]:
    out: List[SourceFile] = []
    for p in sorted(pkg_root.rglob("*.py")):
        if any(part in p.parts for part in skip_parts):
            continue
        sf = load_file(p, repo_root)
        if sf is not None:
            out.append(sf)
    return out


def build_context(repo_root: Path, decls) -> Context:
    """Production context: analyze ``gigapaxos_tpu/``, count knob usage
    across tests/bench/watch too, read README+MIGRATING and conftest."""
    repo_root = Path(repo_root)
    files = load_tree(repo_root / "gigapaxos_tpu", repo_root)
    usage: List[SourceFile] = []
    tests_dir = repo_root / "tests"
    if tests_dir.is_dir():
        # the forged bad/clean samples declare their own PC enums and
        # must not count as knob usage of the real registry
        usage.extend(
            sf for sf in load_tree(tests_dir, repo_root)
            if "analysis_fixtures" not in sf.rel)
    for extra in ("bench.py", "chip_smoke.py", "render_perf.py"):
        p = repo_root / extra
        if p.is_file():
            sf = load_file(p, repo_root)
            if sf is not None:
                usage.append(sf)
    doc = ""
    for name in ("README.md", "MIGRATING.md"):
        p = repo_root / name
        if p.is_file():
            doc += p.read_text() + "\n"
    conftest = ""
    p = tests_dir / "conftest.py"
    if p.is_file():
        conftest = p.read_text()
    return Context(files=files, decls=decls, root=repo_root,
                   doc_text=doc, conftest_src=conftest,
                   usage_files=usage)


# ---------------------------------------------------------------------------
# baseline

class BaselineError(ValueError):
    pass


def load_baseline(path: Path) -> Dict[str, str]:
    """``{fingerprint: why}``.  Every entry MUST carry a non-empty
    ``why`` — a baseline is a reviewed suppression, not a mute button."""
    data = json.loads(Path(path).read_text())
    entries = data.get("entries", data if isinstance(data, list) else [])
    out: Dict[str, str] = {}
    for e in entries:
        fp = e.get("fingerprint", "")
        why = (e.get("why") or "").strip()
        if not fp:
            raise BaselineError("baseline entry missing fingerprint")
        if not why:
            raise BaselineError(
                f"baseline entry for {fp!r} has no 'why' justification")
        out[fp] = why
    return out


def split_baselined(findings: Sequence[Finding],
                    baseline: Dict[str, str]):
    """-> (new, baselined, stale_baseline_fingerprints)."""
    new, old = [], []
    seen = set()
    for f in findings:
        if f.fingerprint in baseline:
            old.append(f)
            seen.add(f.fingerprint)
        else:
            new.append(f)
    stale = sorted(set(baseline) - seen)
    return new, old, stale


# ---------------------------------------------------------------------------
# driver

def all_rules() -> Dict[str, Callable[[Context], List[Finding]]]:
    # local import: rule modules import core
    from gigapaxos_tpu.analysis import (clockpurity, hotpath, initflow,
                                        jitpurity, knobs, locks,
                                        loopblock, resetscope, wiresym)
    return {
        "lock-order": locks.check_lock_order,
        "race": locks.check_races,
        "lazy-init": initflow.check_lazy_init,
        "shadow": initflow.check_shadowing,
        "hot-path": hotpath.check,
        "knobs": knobs.check,
        "jit-purity": jitpurity.check,
        "clockpurity": clockpurity.check,
        "wiresym": wiresym.check,
        "loopblock": loopblock.check,
        "resetscope": resetscope.check,
    }


def analyze(ctx: Context,
            rules: Optional[Sequence[str]] = None,
            timings: Optional[Dict[str, float]] = None) -> List[Finding]:
    """Run the rule table; per-rule wall seconds land in ``timings``
    when a dict is passed (the artifact records them so a slow rule is
    attributable, not a mystery in the sweep total)."""
    table = all_rules()
    if rules:
        table = {k: v for k, v in table.items() if k in rules}
    findings: List[Finding] = []
    for name, fn in table.items():
        t0 = time.perf_counter()
        findings.extend(fn(ctx))
        if timings is not None:
            timings[name] = round(time.perf_counter() - t0, 4)
    findings.sort(key=lambda f: (f.rule, f.path, f.line))
    return findings


def report(findings: Sequence[Finding], baselined: Sequence[Finding],
           stale: Sequence[str], nfiles: int) -> str:
    out: List[str] = []
    by_rule: Dict[str, List[Finding]] = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    for rule in sorted(by_rule):
        out.append(f"== {rule} ({len(by_rule[rule])}) ==")
        out.extend(f.render() for f in by_rule[rule])
        out.append("")
    out.append(f"{nfiles} files scanned; "
               f"{len(findings)} new finding(s), "
               f"{len(baselined)} baselined, "
               f"{len(stale)} stale baseline entr(ies)")
    for fp in stale:
        out.append(f"  stale baseline (no longer fires): {fp}")
    return "\n".join(out)


def to_json(findings: Sequence[Finding], baselined: Sequence[Finding],
            stale: Sequence[str], nfiles: int,
            timings: Optional[Dict[str, float]] = None) -> dict:
    counts: Dict[str, int] = {}
    for f in list(findings) + list(baselined):
        counts[f.rule] = counts.get(f.rule, 0) + 1
    return {
        "schema": "gigapaxos_tpu.analysis/v2",
        "files_scanned": nfiles,
        "rules": sorted(all_rules()),
        "per_rule": counts,
        "rule_timings_s": dict(sorted((timings or {}).items())),
        "new": len(findings),
        "baselined": len(baselined),
        "stale_baseline": list(stale),
        "findings": [{
            "rule": f.rule, "path": f.path, "line": f.line,
            "qualname": f.qualname, "message": f.message,
            "snippet": f.snippet, "fingerprint": f.fingerprint,
        } for f in findings],
    }
