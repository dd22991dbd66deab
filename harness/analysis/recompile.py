"""recompile-hazard: unbounded jit compiles on the verifier hot path.

Every distinct operand shape reaching a ``jax.jit`` function triggers a
fresh trace + XLA compile — about two minutes of tracing and a quarter
of a minute of compiling per kernel-path bucket.  The repo's discipline is to bound that cost two
ways: operand shapes are snapped to the fixed bucket ladder
(``crypto/bucketing.bucket_round`` / ``_pad``) before upload, and jit
wrappers are built once per (mesh, bucket) behind an
``functools.lru_cache`` builder or an ``__init__``-time assignment.
This rule fails the build when either bound is missing on the hot path:

* a ``jax.jit(...)`` **call site inside a hot function** that is not an
  ``lru_cache``/``cache``-decorated builder re-traces on every window;
* an **upload whose operand never went through bucketing** — arguments
  of ``jnp.asarray``/``jnp.array``/``jax.device_put``/
  ``self._to_device`` are tracked through a per-function fixpoint:
  values returned by ``bucket_round``/``_pad`` (and anything derived
  from them) are bucketed; values derived only from raw entry-function
  parameters are not.  Non-entry parameters are unknown and stay
  silent — their callers are checked at the point the raw data enters;
* a call to a module-level ``NAME = jax.jit(fn, static_argnums=...)``
  wrapper passing a **non-constant, non-bucketed value at a static
  position** — every distinct static value is its own compile cache
  entry.
"""

from __future__ import annotations

import ast

from harness.analysis.core import Finding, Project
from harness.analysis import hotpath

RULE = "recompile-hazard"

_BUCKET_FNS = frozenset({"bucket_round", "_pad"})
_UPLOAD_ATTRS = frozenset({"asarray", "array"})


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _is_jit_call(node: ast.Call) -> bool:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id == "jit"
    if isinstance(f, ast.Attribute):
        return f.attr == "jit"
    return False


def _static_jit_table(mod) -> dict[str, list[int]]:
    """Module-level ``NAME = jax.jit(f, static_argnums=K)`` wrappers →
    their static positions."""
    table: dict[str, list[int]] = {}
    for node in mod.src.tree.body:
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and _is_jit_call(node.value)):
            continue
        static: list[int] = []
        for kw in node.value.keywords:
            if kw.arg == "static_argnums":
                try:
                    val = ast.literal_eval(kw.value)
                except ValueError:
                    continue
                static = list(val) if isinstance(val, (tuple, list)) \
                    else [int(val)]
        if not static:
            continue
        for t in node.targets:
            if isinstance(t, ast.Name):
                table[t.id] = static
    return table


def _bucket_flow(fn: ast.FunctionDef, is_entry: bool) -> tuple[set, set]:
    """Fixpoint classification of local names: BUCKETED (reached
    through ``bucket_round``/``_pad``) vs RAW (derived only from entry
    parameters).  Anything else — non-entry parameters, attributes,
    call results — is unknown and never reported."""
    bucketed: set[str] = set()
    raw: set[str] = set()
    if is_entry:
        args = fn.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs):
            if a.arg != "self":
                raw.add(a.arg)
        for a in (args.vararg, args.kwarg):
            if a is not None:
                raw.add(a.arg)

    assigns = [node for node in ast.walk(fn)
               if isinstance(node, ast.Assign)]
    changed = True
    while changed:
        changed = False
        for node in assigns:
            value = node.value
            refs = _names_in(value)
            if isinstance(value, ast.Call) and \
                    _call_name(value) in _BUCKET_FNS:
                cls = "bucketed"
            elif refs & bucketed:
                # derived from a bucketed value (slices, arithmetic,
                # tuple packing) stays shape-bounded
                cls = "bucketed"
            elif refs and refs <= raw:
                cls = "raw"
            else:
                continue
            for t in node.targets:
                for n in ast.walk(t):
                    if not isinstance(n, ast.Name):
                        continue
                    # monotone: bucketed wins and is never demoted
                    # (guarantees the fixpoint terminates)
                    if cls == "bucketed":
                        if n.id not in bucketed:
                            bucketed.add(n.id)
                            raw.discard(n.id)
                            changed = True
                    elif n.id not in raw and n.id not in bucketed:
                        raw.add(n.id)
                        changed = True
    return bucketed, raw


def _is_upload(node: ast.Call) -> list[ast.expr]:
    """Arguments of this call that are device uploads, or []."""
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr in _UPLOAD_ATTRS and isinstance(f.value, ast.Name) \
                and f.value.id in ("jnp", "jax"):
            return node.args[:1]
        if f.attr == "device_put":
            return node.args[:1]
        if f.attr == "_to_device":
            return list(node.args)
    return []


def check(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    graph = hotpath.hot_graph(project)
    for fn in graph.functions():
        if not hotpath.imports_jax(fn.src):
            continue
        mod = graph.modules[fn.path]
        static_table = _static_jit_table(mod)
        cached = hotpath.is_cached_builder(fn.node)
        bucketed, raw = _bucket_flow(fn.node, fn.is_entry())

        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue

            if _is_jit_call(node) and not cached:
                findings.append(Finding(
                    rule=RULE, path=fn.path, line=node.lineno,
                    symbol=fn.qualname,
                    message="jax.jit call site inside a hot function "
                            f"(via {fn.entry}) re-traces every window — "
                            "each miss costs a 129–151 s ladder compile; "
                            "memoize the builder with functools."
                            "lru_cache or hoist it to __init__"))
                continue

            for arg in _is_upload(node):
                hits = _names_in(arg) & raw
                if hits and not (_names_in(arg) & bucketed):
                    findings.append(Finding(
                        rule=RULE, path=fn.path, line=node.lineno,
                        symbol=fn.qualname,
                        message=f"operand '{sorted(hits)[0]}' is "
                                "uploaded without passing through "
                                "bucket_round/_pad — every distinct "
                                "request size becomes its own jit "
                                "compile cache entry"))

            f = node.func
            if isinstance(f, ast.Name) and f.id in static_table:
                for pos in static_table[f.id]:
                    if pos >= len(node.args):
                        continue
                    a = node.args[pos]
                    if isinstance(a, ast.Constant):
                        continue
                    if _names_in(a) & bucketed:
                        continue
                    findings.append(Finding(
                        rule=RULE, path=fn.path, line=node.lineno,
                        symbol=fn.qualname,
                        message=f"static_argnums position {pos} of "
                                f"{f.id} receives a per-call value — "
                                "every distinct value is a fresh "
                                "compile; pass a bucketed/constant "
                                "width instead"))
    return findings
