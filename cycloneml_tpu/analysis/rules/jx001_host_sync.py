"""JX001 — implicit host↔device synchronization.

Two shapes of the same hazard:

a) **Inside jit-reachable (traced) code**: ``float(x)`` / ``int(x)`` /
   ``bool(x)`` / ``x.item()`` / ``np.asarray(x)`` on a traced value.
   Under ``jax.jit`` these either raise a ``TracerConversionError`` at
   first trace or — worse, outside jit but on device values in a hot
   loop — force a blocking device->host transfer per call.

b) **In host driver code**: pulling several scalars piecemeal out of the
   result of a compiled aggregation program (``out = run(...)`` then
   ``float(out["loss"])``, ``float(out["wsum"])``, ...). Each conversion
   is its own blocking device-to-host transfer; one
   ``jax.device_get(out)`` batches them into a single round trip. Only
   flagged at >= 2 pulls — a single conversion is already minimal.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Set

from cycloneml_tpu.analysis.astutil import (TaintTracker, assigned_names,
                                            call_name, iter_own_statements,
                                            last_component)
from cycloneml_tpu.analysis.engine import AnalysisContext, Finding, ModuleInfo
from cycloneml_tpu.analysis.rules.base import Rule

COERCIONS = {"float", "int", "bool", "complex"}
HOST_ARRAY_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                    "onp.asarray", "onp.array"}
# callables whose result is a live device program: `out = prog(...)` marks
# `out` as a device pytree whose fields should be fetched with ONE
# device_get, not piecemeal conversions
PROGRAM_BUILDERS = {"tree_aggregate_fn", "tree_aggregate",
                    "tree_aggregate_with_state", "jit", "pjit"}


class HostSyncRule(Rule):
    rule_id = "JX001"

    def check(self, mod: ModuleInfo, ctx: AnalysisContext) -> Iterator[Finding]:
        for fn in mod.functions:
            if fn.jit_reachable:
                yield from self._check_traced(mod, fn)
            else:
                yield from self._check_piecemeal_pulls(mod, fn)

    # -- (a) syncs inside traced code ---------------------------------------
    def _check_traced(self, mod: ModuleInfo, fn) -> Iterator[Finding]:
        taint = TaintTracker(fn.node, seed_params=fn.params_traced)
        for node in iter_own_statements(fn.node):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name in COERCIONS and node.args:
                if taint.expr_tainted(node.args[0]):
                    yield self.finding(
                        mod, node,
                        f"`{name}()` on a traced value inside jit-reachable "
                        f"code forces a host sync (or a TracerConversionError "
                        f"under jit); keep the value on device or move the "
                        f"conversion outside the traced region",
                        fn.qualname)
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("item", "tolist")
                    and not node.args
                    and taint.expr_tainted(node.func.value)):
                yield self.finding(
                    mod, node,
                    f"`.{node.func.attr}()` on a traced value inside "
                    f"jit-reachable code is an implicit device->host "
                    f"transfer",
                    fn.qualname)
            elif name in HOST_ARRAY_CALLS and node.args:
                if taint.expr_tainted(node.args[0]):
                    yield self.finding(
                        mod, node,
                        f"`{name}()` on a traced value materializes a host "
                        f"copy inside jit-reachable code; use jnp (or hoist "
                        f"the conversion out of the traced region)",
                        fn.qualname)

    # -- (b) piecemeal pulls in host drivers --------------------------------
    def _check_piecemeal_pulls(self, mod: ModuleInfo, fn) -> Iterator[Finding]:
        # names bound from a compiled-program factory: prog = ds.tree_aggregate_fn(f)
        program_names: Set[str] = set()
        # names bound from calling such a program: out = prog(...)
        output_pulls: Dict[str, List[ast.AST]] = {}
        fetched: Set[str] = set()

        for node in iter_own_statements(fn.node):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                callee = call_name(node.value)
                names = [n for t in node.targets for n in assigned_names(t)]
                if callee and last_component(callee) in PROGRAM_BUILDERS:
                    program_names.update(names)
                elif callee and last_component(callee) == "device_get":
                    for n in names:
                        fetched.add(n)
                elif callee in program_names or (
                        callee and callee.split(".", 1)[0] in program_names):
                    for n in names:
                        output_pulls.setdefault(n, [])
                continue
            if not isinstance(node, ast.Call):
                continue
            callee = call_name(node)
            if callee and last_component(callee) == "device_get":
                for arg in node.args:
                    for sub in ast.walk(arg):
                        if isinstance(sub, ast.Name):
                            fetched.add(sub.id)
                continue
            target = None
            if callee in COERCIONS and node.args:
                target = node.args[0]
            elif callee in HOST_ARRAY_CALLS and node.args:
                target = node.args[0]
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                target = node.func.value
            if target is None:
                continue
            for sub in ast.walk(target):
                if isinstance(sub, ast.Name) and sub.id in output_pulls \
                        and sub.id not in fetched:
                    output_pulls[sub.id].append(node)
                    break

        for name, pulls in output_pulls.items():
            if len(pulls) >= 2:
                yield self.finding(
                    mod, pulls[1],
                    f"{len(pulls)} separate implicit device->host transfers "
                    f"from aggregate output `{name}`; fetch once with "
                    f"`jax.device_get({name})` and convert on the host",
                    fn.qualname)
