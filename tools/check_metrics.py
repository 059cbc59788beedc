#!/usr/bin/env python
"""Metrics-naming lint CLI -- a thin shim over kdlt-lint's metrics pass.

The rules (every series kdlt_-prefixed and minted through the central
helpers in utils/metrics.py; bounded labels and the central prefixes
confined to that module; exemplars histogram-only) now live in
tools/kdlt_lint/passes/metrics_names.py, where they run as one pass of
the unified suite alongside lock-discipline, hot-path-sync, donation-
safety and closed-vocab.  This shim keeps the original CLI and the
``lint_source(src, rel)`` API (tests/test_check_metrics.py asserts on its
exact message strings) so nothing keyed on ``check_metrics`` breaks.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from kdlt_lint.core import ModuleInfo, LintContext  # noqa: E402
from kdlt_lint.passes.metrics_names import (  # noqa: E402,F401
    CENTRAL_LABELS,
    CENTRAL_NAMES,
    CENTRAL_PREFIXES,
    METRIC_CLASSES,
    METRIC_PREFIX,
    METRICS_MODULE,
    MINT_METHODS,
    MetricsNamingPass,
)
from kdlt_lint.core import (  # noqa: E402,F401
    PACKAGE,
    REPO,
    SKIP_PARTS,
    iter_production_files as _iter_files,
)


def lint_source(src: str, rel: str) -> list[str]:
    """Lint one module's source; returns violation strings."""
    mod = ModuleInfo(rel.replace(os.sep, "/"), src)
    findings = MetricsNamingPass().check_module(mod, LintContext(REPO))
    return [f"{f.rel}:{f.line}: {f.message}" for f in findings]


def iter_production_files() -> list[str]:
    return _iter_files(REPO)


def main() -> int:
    violations: list[str] = []
    for path in iter_production_files():
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            try:
                violations.extend(lint_source(f.read(), rel))
            except SyntaxError as e:
                violations.append(f"{rel}: unparsable: {e}")
    for v in violations:
        print(v)
    if not violations:
        print("check_metrics: all metric names kdlt_-prefixed and centrally minted")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
