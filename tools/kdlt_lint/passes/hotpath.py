"""Hot-path host-sync pass.

The serving forward path -- everything reachable from the dispatcher's
``submit`` and the engine's ``predict_async`` via the in-package call graph
-- must never block on the device or serialize host work it does not have
to:

- ``hot-path-sync``: no ``np.asarray`` / ``np.array`` on the dispatch side,
  no ``.block_until_ready()``, no ``.item()``, no ``float(...)`` of a
  non-constant (the classic implicit device sync);
- ``lock-around-jit``: no jitted call (an attribute built by ``jax.jit`` /
  ``_donate_jit``, i.e. any ``self.*jit*`` callable) invoked while holding
  a lock, unless the lock exists precisely to serialize the enqueue (which
  must then be suppressed with a justification at the site).

Roots are seeded by name below; the closure follows ``self.method()``
calls, same-module functions, and ``module_alias.function()`` calls into
other package modules.  Calls through untyped parameters are not followed
-- the roots list names both sides of such seams explicitly.
"""

from __future__ import annotations

import ast

from kdlt_lint.core import (
    PACKAGE,
    Finding,
    LintContext,
    LintPass,
    ModuleInfo,
    dotted,
)

# (rel, class-or-None, function): the forward path's entry points.
HOT_PATH_ROOTS = (
    (f"{PACKAGE}/runtime/engine.py", "InFlightDispatcher", "submit"),
    (f"{PACKAGE}/runtime/engine.py", "InferenceEngine", "predict_async"),
    # The mesh/cross-host forward entry: the leader's broadcast+dispatch
    # half is what overlaps round N+1 with round N's collective, so a host
    # sync here stalls the whole fleet's pipeline, not one process.
    (f"{PACKAGE}/parallel/crosshost.py", "CrossHostForward", "predict_async"),
    # The decode token loop's per-step dispatch: one host sync here is
    # paid EVERY token of EVERY active generation, so the step must stay
    # async -- materialization happens once per iteration in the scheduler
    # loop (emit_tokens), never inside the step dispatch itself.
    (f"{PACKAGE}/runtime/decode.py", "DecodeEngine", "step_async"),
    # ... and the round that carries a step inside a prefill chunk's program.
    (f"{PACKAGE}/runtime/decode.py", "DecodeEngine", "round_async"),
    # Raw-bytes ingest (GUIDE 10q): the model tier's decode-stage entry
    # and the engine's fused-ingest dispatch surface.  decode_batch runs
    # pre-dispatch by design -- its intentional host materializations
    # carry explicit suppressions in ops/preprocess.py; anything NEW that
    # blocks on device work from these roots is flagged.
    (f"{PACKAGE}/ops/preprocess.py", "BatchDecoder", "decode_batch"),
    (f"{PACKAGE}/runtime/engine.py", "InferenceEngine", "predict_ingest_async"),
    (f"{PACKAGE}/parallel/crosshost.py", "CrossHostForward", "predict_encoded_async"),
)

SYNC_NP_FUNCS = {"numpy.asarray", "numpy.array"}
LOCK_FACTORIES = {"threading.Lock", "threading.RLock", "threading.Condition"}


def _rel_to_dotted(rel: str) -> str | None:
    rel = rel.replace("\\", "/")
    if not rel.startswith(PACKAGE + "/") or not rel.endswith(".py"):
        return None
    mod = rel[: -len(".py")].replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


class _FnInfo:
    def __init__(self, key):
        self.key = key                      # (rel, cls|None, name)
        self.calls: list[tuple] = []        # ("self"|"module", target)
        self.sync_sites: list[tuple[int, str]] = []
        self.jit_under_lock: list[int] = []


class HotPathSyncPass(LintPass):
    name = "hot-path"
    rules = ("hot-path-sync", "lock-around-jit")

    def check_module(self, mod: ModuleInfo, ctx: LintContext) -> list[Finding]:
        fns: dict = ctx.scratch.setdefault("hotpath.fns", {})
        dotted_mod = _rel_to_dotted(mod.rel)

        def scan_function(fn, cls_name: str | None, jit_attrs: set[str],
                          lock_attrs: set[str]) -> None:
            key = (mod.rel, cls_name, fn.name)
            info = _FnInfo(key)
            fns[key] = info
            self._scan_body(mod, fn, info, jit_attrs, lock_attrs, dotted_mod)

        for node in mod.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan_function(node, None, set(), set())
            elif isinstance(node, ast.ClassDef):
                jit_attrs: set[str] = set()
                lock_attrs: set[str] = set()
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Assign):
                        for tgt in sub.targets:
                            if (
                                isinstance(tgt, ast.Attribute)
                                and isinstance(tgt.value, ast.Name)
                                and tgt.value.id == "self"
                            ):
                                if isinstance(sub.value, ast.Call):
                                    resolved = mod.resolve(sub.value.func) or ""
                                    if resolved in LOCK_FACTORIES:
                                        lock_attrs.add(tgt.attr)
                                    elif "jit" in resolved.split(".")[-1].lower():
                                        jit_attrs.add(tgt.attr)
                                if "jit" in tgt.attr.lower():
                                    jit_attrs.add(tgt.attr)
                for fn in node.body:
                    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        scan_function(fn, node.name, jit_attrs, lock_attrs)
        return []

    def _scan_body(self, mod: ModuleInfo, fn, info: _FnInfo,
                   jit_attrs: set[str], lock_attrs: set[str],
                   dotted_mod: str | None) -> None:
        held_depth = [0]

        def walk(node, in_lock: bool) -> None:
            if isinstance(node, (ast.With, ast.AsyncWith)):
                acquires = False
                for item in node.items:
                    expr = item.context_expr
                    if (
                        isinstance(expr, ast.Attribute)
                        and isinstance(expr.value, ast.Name)
                        and expr.value.id == "self"
                        and (expr.attr in lock_attrs or "lock" in expr.attr)
                    ):
                        acquires = True
                for child in ast.iter_child_nodes(node):
                    walk(child, in_lock or acquires)
                return
            if isinstance(node, ast.Call):
                self._scan_call(mod, node, info, jit_attrs, dotted_mod, in_lock)
            for child in ast.iter_child_nodes(node):
                walk(child, in_lock)

        for stmt in fn.body:
            walk(stmt, False)

    def _scan_call(self, mod: ModuleInfo, node: ast.Call, info: _FnInfo,
                   jit_attrs: set[str], dotted_mod: str | None,
                   in_lock: bool) -> None:
        fnode = node.func
        resolved = mod.resolve(fnode) or ""
        # --- call-graph edges ---
        if (
            isinstance(fnode, ast.Attribute)
            and isinstance(fnode.value, ast.Name)
            and fnode.value.id == "self"
        ):
            info.calls.append(("self", fnode.attr))
            if fnode.attr in jit_attrs and in_lock:
                info.jit_under_lock.append(node.lineno)
        elif isinstance(fnode, ast.Name):
            if dotted_mod is not None:
                info.calls.append(("module", (mod.rel, fnode.id)))
        elif isinstance(fnode, ast.Attribute) and resolved.startswith(PACKAGE + "."):
            target_mod, _, name = resolved.rpartition(".")
            info.calls.append(("module", (target_mod.replace(".", "/") + ".py", name)))
        # --- sync sites ---
        if resolved in SYNC_NP_FUNCS:
            info.sync_sites.append((node.lineno, f"{resolved}() host materialization"))
        elif isinstance(fnode, ast.Attribute) and fnode.attr == "block_until_ready":
            info.sync_sites.append((node.lineno, ".block_until_ready() device sync"))
        elif isinstance(fnode, ast.Attribute) and fnode.attr == "item" and not node.args:
            info.sync_sites.append((node.lineno, ".item() scalar device sync"))
        elif (
            isinstance(fnode, ast.Name)
            and fnode.id == "float"
            and node.args
            and not isinstance(node.args[0], ast.Constant)
        ):
            info.sync_sites.append(
                (node.lineno, "float(...) of a runtime value (implicit device sync)")
            )

    def finalize(self, ctx: LintContext) -> list[Finding]:
        fns: dict = ctx.scratch.get("hotpath.fns", {})
        # closure over the call graph from the seeded roots
        reachable: dict[tuple, tuple] = {}  # key -> root it was reached from
        work = [(root, root) for root in HOT_PATH_ROOTS if root in fns]
        while work:
            key, root = work.pop()
            if key in reachable:
                continue
            reachable[key] = root
            info = fns[key]
            rel, cls, _name = key
            for kind, target in info.calls:
                if kind == "self" and cls is not None:
                    nxt = (rel, cls, target)
                    if nxt in fns:
                        work.append((nxt, root))
                elif kind == "module":
                    t_rel, t_name = target
                    nxt = (t_rel, None, t_name)
                    if nxt in fns:
                        work.append((nxt, root))
        findings: list[Finding] = []
        for key, root in sorted(reachable.items(), key=str):
            info = fns[key]
            rel, cls, name = key
            qual = f"{cls}.{name}" if cls else name
            root_qual = f"{root[1]}.{root[2]}" if root[1] else root[2]
            for line, what in info.sync_sites:
                findings.append(Finding(
                    "hot-path-sync", rel, line,
                    f"{what} in {qual}, which is on the serving hot path "
                    f"(reachable from {root_qual}); host syncs here "
                    "serialize the dispatch pipeline",
                ))
            for line in info.jit_under_lock:
                findings.append(Finding(
                    "lock-around-jit", rel, line,
                    f"jitted call under a lock in {qual} (hot path via "
                    f"{root_qual}); holding a lock across dispatch "
                    "serializes callers against device work",
                ))
        return findings
