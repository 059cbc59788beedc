#!/usr/bin/env python
"""Env-knob lint CLI -- a thin shim over kdlt-lint's env pass.

The rules (every whole-string ``KDLT_*`` literal documented in GUIDE.md,
deploy-manifest keys read by code, the compose replica pair identical,
compose/k8s tier mirrors agreeing modulo the declared drift allowances)
now live in tools/kdlt_lint/passes/env_knobs.py, where they run as one
pass of the unified suite alongside lock-discipline, hot-path-sync,
donation-safety and closed-vocab.  The drift allowances themselves moved
into that pass's DEPLOY_AGREEMENT declarative config; this shim re-exports
them plus the ``env_literals``/``compose_env``/``k8s_env`` helpers
(tests/test_check_env.py exercises each directly) so nothing keyed on
``check_env`` breaks.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from kdlt_lint.core import (  # noqa: E402,F401
    PACKAGE,
    REPO,
    SKIP_PARTS,
    LintContext,
    ModuleInfo,
    iter_production_files as _iter_files,
)
from kdlt_lint.passes.env_knobs import (  # noqa: E402,F401
    COMPOSE,
    DEPLOY_AGREEMENT,
    ENV_RE,
    GUIDE,
    K8S_GATEWAY,
    K8S_MODEL,
    EnvKnobsPass,
    compose_env,
    env_literals,
    k8s_env,
)

# Back-compat views of the pass's declarative config.
TIERS = DEPLOY_AGREEMENT["tiers"]
ALLOW_VALUE_DRIFT = set(DEPLOY_AGREEMENT["allow_value_drift"])
ALLOW_PRESENCE_DRIFT = set(DEPLOY_AGREEMENT["allow_presence_drift"])


def iter_production_files() -> list[str]:
    return _iter_files(REPO)


def main() -> int:
    violations: list[str] = []
    env_pass = EnvKnobsPass()
    ctx = LintContext(REPO)
    for path in iter_production_files():
        rel = os.path.relpath(path, REPO).replace(os.sep, "/")
        with open(path) as f:
            src = f.read()
        try:
            mod = ModuleInfo(rel, src)
        except SyntaxError as e:
            violations.append(f"{rel}: unparsable: {e}")
            continue
        env_pass.check_module(mod, ctx)
    for f in env_pass.finalize(ctx):
        # Manifest-level findings (line 0) already carry their location in
        # the message; code-level ones get the classic rel:line prefix.
        violations.append(f"{f.rel}:{f.line}: {f.message}" if f.line else f.message)
    for v in violations:
        print(v)
    if not violations:
        print(
            f"check_env: {ctx.scratch.get('env.knob_count', 0)} KDLT_* knobs "
            "documented; deploy mirrors agree"
        )
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
