"""Framework core: one parse per file, shared resolution, suppressions.

The driver parses every production module exactly once into a
:class:`ModuleInfo` (AST + source lines + import aliases + suppression
comments) and hands the same objects to every registered pass.  Passes
implement per-module checks and/or whole-tree finalization (call graphs,
lock-order graphs, cross-file deploy agreement); findings carry
``file:line`` plus a stable rule id so CI can key on them.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PACKAGE = "kubernetes_deep_learning_tpu"
SKIP_PARTS = {"tfs_gen", "__pycache__"}

SUPPRESS_RE = re.compile(
    r"#\s*kdlt-lint:\s*disable=([A-Za-z0-9_,\- ]+?)(?:\s+--\s+(?P<why>.*))?\s*$"
)
GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")


@dataclass
class Finding:
    rule: str
    rel: str
    line: int
    message: str
    suppressed: bool = False

    def format(self) -> str:
        return f"{self.rel}:{self.line}: [{self.rule}] {self.message}"

    def as_json(self) -> dict:
        return {
            "rule": self.rule,
            "file": self.rel,
            "line": self.line,
            "message": self.message,
            "suppressed": self.suppressed,
        }


@dataclass
class Suppression:
    line: int            # line the comment sits on
    applies_to: int      # line whose findings it suppresses
    rules: tuple[str, ...]
    justification: str | None
    used: bool = False


class ModuleInfo:
    """One parsed production module, shared by every pass."""

    def __init__(self, rel: str, src: str, tree: ast.Module | None = None):
        self.rel = rel.replace(os.sep, "/")
        self.src = src
        self.lines = src.splitlines()
        self.tree = tree if tree is not None else ast.parse(src, filename=rel)
        self.suppressions = self._parse_suppressions()
        # name -> dotted module ("np" -> "numpy"); covers `import a.b as c`
        self.module_aliases: dict[str, str] = {}
        # name -> fully-qualified symbol ("Lock" -> "threading.Lock")
        self.symbol_aliases: dict[str, str] = {}
        self._collect_imports()

    # --- imports / resolution ---------------------------------------------

    def _collect_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        self.module_aliases[a.asname] = a.name
                    else:
                        self.module_aliases[a.name.split(".")[0]] = a.name.split(".")[0]
            elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
                for a in node.names:
                    self.symbol_aliases[a.asname or a.name] = f"{node.module}.{a.name}"

    def resolve(self, node: ast.expr) -> str | None:
        """Fully-qualified dotted name of a Name/Attribute chain, resolved
        through this module's imports; None when the chain has a non-name
        head (calls, subscripts)."""
        parts = dotted(node)
        if not parts:
            return None
        head, rest = parts[0], parts[1:]
        if head in self.module_aliases:
            head = self.module_aliases[head]
        elif head in self.symbol_aliases:
            head = self.symbol_aliases[head]
        return ".".join([head, *rest]) if rest else head

    # --- suppressions ------------------------------------------------------

    def _parse_suppressions(self) -> list[Suppression]:
        out: list[Suppression] = []
        for i, text in enumerate(self.lines, start=1):
            m = SUPPRESS_RE.search(text)
            if not m:
                continue
            rules = tuple(
                r.strip() for r in m.group(1).split(",") if r.strip()
            )
            standalone = not text[: m.start()].strip()
            out.append(Suppression(
                line=i,
                applies_to=i + 1 if standalone else i,
                rules=rules,
                justification=m.group("why"),
            ))
        return out

    def is_suppressed(self, rule: str, line: int) -> bool:
        hit = False
        for s in self.suppressions:
            if s.applies_to == line and rule in s.rules:
                s.used = True
                hit = True
        return hit

    # --- annotations -------------------------------------------------------

    def guarded_by_on_line(self, line: int) -> str | None:
        """The ``# guarded-by: <lock>`` annotation on a source line."""
        if 1 <= line <= len(self.lines):
            m = GUARDED_BY_RE.search(self.lines[line - 1])
            if m:
                return m.group(1)
        return None


def dotted(node: ast.expr) -> list[str] | None:
    """["a", "b", "c"] for a Name/Attribute chain ``a.b.c``, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return None


def literal_head(node: ast.expr) -> str | None:
    """The statically-known head of a string argument: the whole string for
    a constant, the leading constant of an f-string, else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values:
        head = node.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str):
            return head.value
    return None


class LintContext:
    """Whole-tree state shared across passes: the repo root, every parsed
    module, and a scratch dict passes use between collect and finalize."""

    def __init__(self, repo: str = REPO):
        self.repo = repo
        self.modules: list[ModuleInfo] = []
        self.scratch: dict[str, object] = {}

    def module(self, rel: str) -> ModuleInfo | None:
        rel = rel.replace(os.sep, "/")
        for m in self.modules:
            if m.rel == rel:
                return m
        return None


class LintPass:
    """Base pass: override ``check_module`` (per file) and/or ``finalize``
    (after every module has been seen -- call graphs, cross-file rules)."""

    name = "base"
    # every rule id this pass can emit, for --list-rules and the
    # unused-suppression check
    rules: tuple[str, ...] = ()

    def check_module(self, mod: ModuleInfo, ctx: LintContext) -> list[Finding]:
        return []

    def finalize(self, ctx: LintContext) -> list[Finding]:
        return []


def iter_production_files(repo: str = REPO) -> list[str]:
    files: list[str] = []
    for dirpath, dirnames, filenames in os.walk(os.path.join(repo, PACKAGE)):
        dirnames[:] = [d for d in dirnames if d not in SKIP_PARTS]
        files.extend(
            os.path.join(dirpath, f) for f in sorted(filenames)
            if f.endswith(".py")
        )
    return files


def default_passes() -> list[LintPass]:
    # Imported here so the shims (tools/check_metrics.py, tools/check_env.py)
    # can import their single pass without pulling the whole suite.
    from kdlt_lint.passes.closed_vocab import ClosedVocabPass
    from kdlt_lint.passes.donation import DonationSafetyPass
    from kdlt_lint.passes.env_knobs import EnvKnobsPass
    from kdlt_lint.passes.hotpath import HotPathSyncPass
    from kdlt_lint.passes.locks import LockDisciplinePass
    from kdlt_lint.passes.metrics_names import MetricsNamingPass

    return [
        LockDisciplinePass(),
        HotPathSyncPass(),
        DonationSafetyPass(),
        ClosedVocabPass(),
        MetricsNamingPass(),
        EnvKnobsPass(),
    ]


def run_lint(
    passes: list[LintPass] | None = None,
    repo: str = REPO,
    files: list[str] | None = None,
) -> list[Finding]:
    """Parse every production file once, run every pass, apply suppressions.

    Returns ALL findings; suppressed ones carry ``suppressed=True``.  The
    unused-suppression check runs last so a comment that suppressed nothing
    is itself reported.
    """
    if passes is None:
        passes = default_passes()
    ctx = LintContext(repo)
    findings: list[Finding] = []
    for path in files if files is not None else iter_production_files(repo):
        rel = os.path.relpath(path, repo)
        with open(path) as f:
            src = f.read()
        try:
            ctx.modules.append(ModuleInfo(rel, src))
        except SyntaxError as e:
            findings.append(Finding("parse", rel, e.lineno or 0, f"unparsable: {e}"))
    for p in passes:
        for mod in ctx.modules:
            findings.extend(p.check_module(mod, ctx))
    for p in passes:
        findings.extend(p.finalize(ctx))
    by_rel = {m.rel: m for m in ctx.modules}
    for f in findings:
        mod = by_rel.get(f.rel)
        if mod is not None and mod.is_suppressed(f.rule, f.line):
            f.suppressed = True
    known_rules = {r for p in passes for r in p.rules}
    for mod in ctx.modules:
        for s in mod.suppressions:
            if not s.used and any(r in known_rules for r in s.rules):
                findings.append(Finding(
                    "unused-suppression", mod.rel, s.line,
                    f"suppression for {', '.join(s.rules)} matched no finding; "
                    "remove it (stale suppressions hide future regressions)",
                ))
    findings.sort(key=lambda f: (f.rel, f.line, f.rule))
    return findings
