"""hot-path-sync: host syncs reachable from the per-step train loops.

The trainers' contract (worker/trainer.py ABC) is that the per-step path
stays dispatch-ahead: the jitted step's results are LAZY device values,
materialized only where a caller deliberately logs/persists them. A
`float()`, `np.asarray`, `.item()`, or `.block_until_ready()` anywhere
on the step path blocks the host on the device every step — the exact
serialization the round-1 bench identified as the throughput ceiling —
and jit-purity cannot see it because these syncs run OUTSIDE the jitted
function.

This rule walks the dataflow engine's call graph from every trainer
step entry point (`train_minibatch` / `train_lease_minibatch` on
classes under worker/), taints the RESULTS of jit-binding calls (and
values derived from them, interprocedurally through helper calls), and
flags sync sinks on tainted values. `jax.device_get` is the sanctioned
batched-materialization API: its results are host values, so code that
transfers once and works on numpy after is clean.

Deferred edges (thread targets, executor submissions) are excluded —
work on the push thread overlaps the step and is off the critical path.
"""

import ast
import os

from tools.edl_lint.core import Finding, Rule
from tools.edl_lint.dataflow import get_engine, self_attr

_ENTRY_NAMES = {"train_minibatch", "train_lease_minibatch"}
_ENTRY_SCOPE = ("elasticdl_tpu/worker/",)
# Reachability stays inside the training layers; instrumentation
# (observability/) and transport helpers (proto/) have their own rules.
_WALK_SCOPE = (
    "elasticdl_tpu/worker/",
    "elasticdl_tpu/parallel/",
    "elasticdl_tpu/layers/",
    "elasticdl_tpu/common/",
)

_SYNC_FUNCS = {
    "numpy.asarray", "numpy.array", "numpy.copy", "numpy.float32",
    "numpy.float64",
}
_SYNC_METHODS = {"item", "block_until_ready"}
_CAST_BUILTINS = {"float", "int", "bool"}


class _FunctionAnalysis:
    """One (function, tainted-params) taint pass: emits sink events and
    reports whether the return value is tainted."""

    def __init__(self, rule, engine, info, tainted_params, emit, visit):
        self.rule = rule
        self.engine = engine
        self.info = info
        self.minfo = info.minfo
        self.emit = emit
        self.visit = visit  # callback: (callee key, tainted param names) -> returns_tainted
        self.jit_calls = engine.jit_call_returns(info)
        self.call_edges = {}
        for edge in engine.callees(info.key):
            self.call_edges.setdefault(id(edge.call), []).append(
                edge.callee
            )
        self.tainted = set(tainted_params)
        self.returns_tainted = False

    # -- expression taint ------------------------------------------------

    def expr_tainted(self, expr):
        """Structural taint: a Name in the tainted set, or a Call that
        returns a device value. Recursion (rather than a flat walk) is
        what lets `jax.device_get(<tainted>)` SANITIZE its subtree —
        the sanctioned one-transfer materialization reads as host data
        downstream."""
        if expr is None:
            return False
        if isinstance(expr, ast.Name):
            return expr.id in self.tainted
        if isinstance(expr, ast.Call):
            return self.call_tainted(expr)
        if isinstance(expr, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return False
        for child in ast.iter_child_nodes(expr):
            if isinstance(child, ast.expr) and self.expr_tainted(child):
                return True
            if isinstance(child, ast.comprehension):
                if self.expr_tainted(child.iter) or any(
                    self.expr_tainted(cond) for cond in child.ifs
                ):
                    return True
        return False

    def call_tainted(self, call):
        """Does this call RETURN a device value?"""
        dotted = self.minfo.dotted(call.func) or ""
        tail = dotted.rsplit(".", 1)[-1]
        if tail == "device_get":
            return False  # sanctioned batched materialization
        if id(call) in self.jit_calls:
            return True
        # In-scope callee: taint its params, recurse for return taint.
        for callee in self.call_edges.get(id(call), ()):
            callee_info = self.engine.functions.get(callee)
            if callee_info is None or not callee_info.rel.startswith(
                self.rule.walk_prefixes
            ):
                continue
            tainted_params = self._tainted_params_for(
                callee_info, call
            )
            if self.visit(callee, tainted_params):
                return True
        # Unknown call with a tainted argument: conservative
        # pass-through (jnp ops, tree_map, tuple plumbing).
        return any(
            self._arg_tainted(a)
            for a in list(call.args)
            + [kw.value for kw in call.keywords]
        )

    def _arg_tainted(self, expr):
        if isinstance(expr, ast.Starred):
            expr = expr.value
        return self.expr_tainted(expr)

    def _tainted_params_for(self, callee_info, call):
        args = callee_info.node.args
        params = [a.arg for a in args.posonlyargs + args.args]
        if params and params[0] == "self":
            params = params[1:]
        out = set()
        for i, arg in enumerate(call.args):
            if isinstance(arg, ast.Starred):
                break
            if i < len(params) and self._arg_tainted(arg):
                out.add(params[i])
        for kw in call.keywords:
            if kw.arg and kw.arg in params and self._arg_tainted(
                kw.value
            ):
                out.add(kw.arg)
        return frozenset(out)

    # -- ordered statement walk ------------------------------------------

    def run(self):
        self._walk_block(self.info.node.body)
        return self.returns_tainted

    def _walk_block(self, stmts):
        for stmt in stmts:
            self._walk_stmt(stmt)

    def _walk_stmt(self, stmt):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs run later (trace time / callbacks)
        if isinstance(stmt, ast.Return):
            if self.expr_tainted(stmt.value):
                self.returns_tainted = True
            self._scan_sinks(stmt)
            return
        if isinstance(stmt, ast.Assign):
            self._scan_sinks(stmt)
            taint = self.expr_tainted(stmt.value)
            for target in stmt.targets:
                for node in ast.walk(target):
                    if isinstance(node, ast.Name):
                        if taint:
                            self.tainted.add(node.id)
                        else:
                            self.tainted.discard(node.id)
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._scan_sinks(stmt.iter)
            if self.expr_tainted(stmt.iter):
                for node in ast.walk(stmt.target):
                    if isinstance(node, ast.Name):
                        self.tainted.add(node.id)
            # Two passes so taint introduced late in the body reaches
            # sinks earlier in the next iteration.
            self._walk_block(stmt.body)
            self._walk_block(stmt.body)
            self._walk_block(stmt.orelse)
            return
        if isinstance(stmt, ast.While):
            self._scan_sinks(stmt.test)
            self._walk_block(stmt.body)
            self._walk_block(stmt.body)
            self._walk_block(stmt.orelse)
            return
        compound = False
        for field in ("body", "orelse", "finalbody"):
            block = getattr(stmt, field, None)
            if block and all(isinstance(s, ast.stmt) for s in block):
                compound = True
                if field == "body":
                    for item in getattr(stmt, "items", ()) or ():
                        self._scan_sinks(item.context_expr)
                    test = getattr(stmt, "test", None)
                    if test is not None:
                        self._scan_sinks(test)
                self._walk_block(block)
        for handler in getattr(stmt, "handlers", ()) or ():
            compound = True
            self._walk_block(handler.body)
        if not compound:
            # Simple statement (Expr, AugAssign, Raise, ...): scan its
            # expressions for sinks and in-scope calls to recurse into.
            self._scan_sinks(stmt)

    # -- sinks -----------------------------------------------------------

    def _scan_sinks(self, root):
        for node in ast.walk(root):
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue
            if not isinstance(node, ast.Call):
                continue
            if id(node) in self.call_edges:
                # Force the interprocedural visit even when the call's
                # result is unused (bare-expression helper calls).
                self.call_tainted(node)
            dotted = self.minfo.dotted(node.func) or ""
            if dotted in _CAST_BUILTINS:
                if node.args and self.expr_tainted(node.args[0]):
                    self._flag(node, f"{dotted}()", "cast")
            elif dotted in _SYNC_FUNCS:
                if node.args and self.expr_tainted(node.args[0]):
                    self._flag(node, dotted, "numpy")
            elif dotted.endswith("block_until_ready") and "jax" in dotted:
                if node.args and self.expr_tainted(node.args[0]):
                    self._flag(node, "jax.block_until_ready", "block")
            elif isinstance(node.func, ast.Attribute):
                if node.func.attr in _SYNC_METHODS:
                    receiver = node.func.value
                    if self.expr_tainted(receiver):
                        self._flag(node, f".{node.func.attr}()", "method")

    def _flag(self, node, what, kind):
        attr = (
            self_attr(node.args[0])
            if node.args and self_attr(node.args[0])
            else None
        )
        detail = attr or (
            node.args[0].id
            if node.args and isinstance(node.args[0], ast.Name)
            else what
        )
        self.emit(self.info, node.lineno, what, f"{kind}:{detail}")


class HotPathSyncRule(Rule):
    name = "hot-path-sync"
    doc = (
        "No host syncs (float()/np.asarray/.item()/.block_until_ready) "
        "on device values reachable from the trainers' per-step loops — "
        "each one blocks dispatch every step."
    )

    def __init__(self):
        self.walk_prefixes = tuple(
            s.replace("/", os.sep) for s in _WALK_SCOPE
        )

    def check(self, project):
        engine = get_engine(project)
        entry_prefixes = tuple(
            s.replace("/", os.sep) for s in _ENTRY_SCOPE
        )
        findings = []
        seen_sinks = set()
        # Memo: (key, frozenset tainted params) -> returns_tainted; None
        # marks in-progress (recursion: assume untainted return).
        memo = {}

        def emit(info, line, what, detail):
            marker = (info.rel, line, what)
            if marker in seen_sinks:
                return
            seen_sinks.add(marker)
            findings.append(Finding(
                self.name,
                info.rel,
                line,
                f"host sync on the per-step path: {what} on a device "
                f"value in `{info.qualname}` — blocks dispatch every "
                f"step (trainers return lazy losses; materialize at "
                f"the logging/persistence boundary instead)",
                key=f"sync:{info.qualname}:{detail}",
                fix_hint=(
                    "keep the value lazy (return the device array), or "
                    "move the materialization behind jax.device_get at "
                    "a deliberate boundary"
                ),
            ))

        def visit(key, tainted_params):
            info = engine.functions.get(key)
            if info is None:
                return False
            memo_key = (key, tainted_params)
            if memo_key in memo:
                return memo[memo_key] or False
            memo[memo_key] = None  # in progress
            analysis = _FunctionAnalysis(
                self, engine, info, tainted_params, emit, visit
            )
            result = analysis.run()
            memo[memo_key] = result
            return result

        for info in engine.functions.values():
            if (
                info.class_name
                and info.name in _ENTRY_NAMES
                and info.rel.startswith(entry_prefixes)
            ):
                visit(info.key, frozenset())
        yield from findings
