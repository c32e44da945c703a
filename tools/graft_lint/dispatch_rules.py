"""Dispatch-policy checkers: "auto" resolution must route through
``raft_tpu.plan``.

The repo's dispatch decisions ("auto" search/merge/comm/delta modes)
each resolve by one rule in ``raft_tpu.plan``, so every policy is read
in one place instead of re-derived by scattered heuristics that drift
apart:

* ``scattered-auto`` — an ``== "auto"`` / ``!= "auto"`` string-literal
  comparison inside a function with no reference to ``raft_tpu.plan``
  is a local dispatch heuristic growing outside it. Resolve the branch
  by one rule in ``raft_tpu.plan`` (calling a ``plan_*`` rule marks the
  function as routed), or carry a rationale'd inline suppression.

Membership validations (``mode in ("auto", ...)``) are not flagged —
an allowlist check is input validation, not dispatch. Only equality
comparisons against the literal decide a branch.
"""
from __future__ import annotations

import ast
from typing import Iterator, List

from tools.graft_lint.core import Checker, LintModule, Violation

#: attribute/name spellings that mark a function as routed through raft_tpu.plan
_PLAN_PREFIXES = ("plan_", "_plan", "planned_")


def _is_plan_ref(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        nid = node.id
        return nid == "plan" or nid.endswith("_plan") or nid.startswith(_PLAN_PREFIXES)
    if isinstance(node, ast.Attribute):
        attr = node.attr
        return attr == "plan" or attr.startswith(_PLAN_PREFIXES)
    if isinstance(node, ast.ImportFrom):
        mod = node.module or ""
        if mod == "raft_tpu.plan" or mod.startswith("raft_tpu.plan."):
            return True
        return mod == "raft_tpu" and any(a.name == "plan" for a in node.names)
    return False


def _routes_through_plan(fn: ast.AST) -> bool:
    return any(_is_plan_ref(n) for n in ast.walk(fn))


def _auto_compares(fn: ast.AST, nested: List[ast.AST]) -> Iterator[ast.Compare]:
    """Eq/NotEq comparisons against the literal "auto" directly in
    ``fn`` (not inside one of its ``nested`` function definitions —
    those are scoped to the nested function's own walk)."""
    skip = set()
    for sub in nested:
        skip.update(id(n) for n in ast.walk(sub))
        skip.discard(id(sub))
    for node in ast.walk(fn):
        if id(node) in skip or not isinstance(node, ast.Compare):
            continue
        sides = [node.left, *node.comparators]
        literal = any(
            isinstance(s, ast.Constant) and s.value == "auto" for s in sides  # graft-lint: ignore[scattered-auto] — the detector's own matching literal, not a dispatch branch
        )
        if literal and all(isinstance(op, (ast.Eq, ast.NotEq)) for op in node.ops):
            yield node


class ScatteredAutoChecker(Checker):
    rule = "scattered-auto"
    doc = (
        'string-literal "auto" dispatch branch in a function that never '
        "references raft_tpu.plan — resolve the decision by one rule in "
        "raft_tpu.plan so every policy is read in one place instead of "
        "a drifting local heuristic"
    )

    def check(self, module: LintModule) -> Iterator[Violation]:
        funcs = [
            n for n in ast.walk(module.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for fn in funcs:
            nested = [
                n for n in ast.walk(fn)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                and n is not fn
            ]
            cmps = list(_auto_compares(fn, nested))
            if not cmps:
                continue
            # the function (or any function nesting it) routing through
            # raft_tpu.plan clears its whole subtree: an "auto" branch
            # that calls its rule is the sanctioned pattern
            if any(
                _routes_through_plan(f)
                for f in funcs
                if f is fn or any(n is fn for n in ast.walk(f))
            ):
                continue
            for cmp_node in cmps:
                yield self.violation(
                    module, cmp_node,
                    '"auto" resolved by a local heuristic in '
                    f"{fn.name}() — resolve the decision by one rule in "
                    "raft_tpu.plan (plan_search_mode, plan_merge_mode, "
                    "...), or suppress with a rationale",
                )


CHECKERS = [ScatteredAutoChecker()]
