"""Sharding audit (rule family MESH, DESIGN.md §12).

MESH001  Every ``shard_map`` call must pass ``check_vma`` explicitly.
         It silently governs whether the varying-manifest-axes
         (replication) invariants of the body are verified; mesh code
         must say which contract it relies on.
MESH002  A sampling call (``jax.random.categorical`` or
         ``sampling.sample``) must be *dominated* by a
         ``replicate_logits`` rebinding of its logits operand in the
         same function.  Under tensor parallelism the lm_head output is
         vocab-sharded; sampling a sharded row draws a different token
         on every device (the PR 5 bug class).  The one categorical
         primitive inside ``repro/serve/sampling.py`` is the audited
         chokepoint and lives in the baseline.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set

from .core import Finding, ModuleCtx, assigned_names, dotted_name, unparse

# dotted-name leaves treated as sampling entry points whose first
# argument is a logits row that must be replicated first
_SAMPLING_LEAVES = {"categorical"}
_SAMPLING_FNS = {"sample"}          # repro.serve.sampling.sample
_REPLICATORS = {"replicate_logits"}


def check_shard_map_check_vma(ctx: ModuleCtx) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = dotted_name(node.func)
        if fname.split(".")[-1] != "shard_map":
            continue
        if any(kw.arg == "check_vma" for kw in node.keywords):
            continue
        findings.append(Finding(
            rule="MESH001", path=ctx.rel, line=node.lineno,
            context="", detail=unparse(node, 50),
            message="shard_map without explicit check_vma= — declare the "
                    "replication contract the body relies on"))
    return findings


def _sampling_logits_arg(node: ast.Call) -> Optional[ast.expr]:
    """The logits operand if this call samples from logits, else None."""
    fname = dotted_name(node.func)
    leaf = fname.split(".")[-1] if fname else ""
    if leaf in _SAMPLING_LEAVES and fname.startswith(("jax.random.",
                                                      "random.",
                                                      "jrandom.")):
        # categorical(key, logits)
        return node.args[1] if len(node.args) > 1 else None
    if leaf in _SAMPLING_FNS and (
            "sampling" in fname or fname == leaf):
        # sampling.sample(logits, keys, temperature)
        return node.args[0] if node.args else None
    return None


class _DominationChecker(ast.NodeVisitor):
    """Linear scan of one function: names rebound from a
    ``replicate_logits`` call are *replicated*; a sampling call whose
    logits operand isn't built from a replicated name is MESH002."""

    def __init__(self, ctx: ModuleCtx, qualname: str,
                 findings: List[Finding]) -> None:
        self.ctx = ctx
        self.qualname = qualname
        self.findings = findings
        self.replicated: Set[str] = set()

    def _is_replicate_call(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Call):
            fname = dotted_name(node.func)
            if fname.split(".")[-1] in _REPLICATORS:
                return True
            return any(self._is_replicate_call(a) for a in node.args)
        return False

    def _is_replicated_expr(self, node: ast.AST) -> bool:
        if self._is_replicate_call(node):
            return True
        if isinstance(node, ast.Name):
            return node.id in self.replicated
        if isinstance(node, (ast.Subscript, ast.BinOp, ast.UnaryOp)):
            return any(self._is_replicated_expr(c)
                       for c in ast.iter_child_nodes(node)
                       if isinstance(c, ast.expr))
        if isinstance(node, ast.Call):
            # projections of a replicated value stay replicated
            return any(self._is_replicated_expr(a) for a in node.args)
        return False

    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node.value)
        if self._is_replicated_expr(node.value):
            for t in node.targets:
                self.replicated.update(assigned_names(t))

    def visit_Call(self, node: ast.Call) -> None:
        self.generic_visit(node)
        logits = _sampling_logits_arg(node)
        if logits is None:
            return
        if not self._is_replicated_expr(logits):
            self.findings.append(Finding(
                rule="MESH002", path=self.ctx.rel, line=node.lineno,
                context=self.qualname, detail=unparse(node, 50),
                message=f"sampling call `{unparse(node, 50)}` not dominated "
                        f"by replicate_logits — under TP a vocab-sharded "
                        f"row draws a different token per device"))

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        return  # nested functions are their own scope

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]


def check_sampling_replicated(ctx: ModuleCtx) -> List[Finding]:
    findings: List[Finding] = []
    stack: List[str] = []

    def walk(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                prefix = ".".join(stack)
                qn = f"{prefix}.{child.name}" if prefix else child.name
                chk = _DominationChecker(ctx, qn, findings)
                for st in child.body:
                    chk.visit(st)
                stack.append(child.name + ".<locals>")
                walk(child)
                stack.pop()
            elif isinstance(child, ast.ClassDef):
                stack.append(child.name)
                walk(child)
                stack.pop()
            else:
                walk(child)

    walk(ctx.tree)
    return findings


def check_module(ctx: ModuleCtx) -> List[Finding]:
    return check_shard_map_check_vma(ctx) + check_sampling_replicated(ctx)
