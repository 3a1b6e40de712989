"""device-flow: implicit device->host syncs outside the gather stages.

The warm tick's latency contract (CHANGES PR 13–15) is that exactly ONE
blocking device->host transfer happens per dispatch: the gather inside
`ColumnarPending.wait` / `HealthJudge._fetch`. Everything upstream is
async dispatch; everything downstream decodes HOST arrays. The failure
mode this rule encodes is the quiet regression: somebody touches a
device value with `np.asarray`, `float()`, `.item()`, Python iteration
or a per-element `x[i]` loop, and the tick grows a synchronous
round-trip per call that no test times but every Prometheus user feels.

The model is an interprocedural taint over `interproc.Program`:

  * SOURCES — values returned by the dispatch roots (`judge_columnar`,
    `judge_columnar_async`, a ``.wait()`` on a pending), by any
    function the package jits (``jax.jit`` decorators and the
    ``name = jax.jit(fn)`` assignment form), by ``jnp.*`` /
    ``jax.device_put`` expressions, and by the placement hooks
    (`_place`/`_place_cols`);
  * PROPAGATION — through local assignments, through call arguments
    into resolved callees' parameters, and through return values of
    functions whose returns are tainted (fixpoint over the resolved
    call graph);
  * SINKS — the D2H idioms above, applied to a tainted value;
  * BOUNDARY — a function annotated ``# foremast: device-boundary``
    (on/above its ``def``) is a sanctioned gather/decode stage: sinks
    inside it are the design, its RESULT is host, and the values it
    hands to callees are host-side products of that decode (a boundary
    neither returns nor propagates taint — annotations therefore go on
    the STAGE ENTRY POINTS, not on every helper a decode stage feeds).
    The annotation inventory lives in docs/static-analysis.md — adding
    one is a reviewed contract change, not a suppression.

Static-under-tracing accesses (``.shape``/``.ndim``/``.dtype``/
``.size``, ``len()``, ``isinstance()``) neither taint nor sink, same
as jit-hygiene.

SINKS are checked only on the dispatch path (``engine/``, ``jobs/``,
``parallel/``) and never inside a jitted function: `ops/` and
`models/` are traced-interior libraries where Python iteration and
`x[i]` unroll at TRACE time (fixed-shape idiom, jit-hygiene's domain),
and the host-only packages (ingest/, metrics/, mesh/, cli, deploy,
observe) hold no device values by construction — scoping them out
keeps the taint fixpoint from amplifying resolver noise into
package-wide false positives. Taint still PROPAGATES through all of
them, so a device value that round-trips through a helper module is
caught when it reaches a scoped sink.
"""

from __future__ import annotations

import ast

from foremast_tpu.analysis.core import Finding
from foremast_tpu.analysis.interproc import (
    FunctionInfo,
    Program,
    dotted,
    own_body_walk,
)
from foremast_tpu.analysis.jit_hygiene import _is_jax_jit, _jit_call_statics

RULE = "device-flow"
BOUNDARY_MARKER = "device-boundary"

DISPATCH_ROOTS = frozenset({"judge_columnar", "judge_columnar_async"})
PLACEMENT_HOOKS = frozenset({"_place", "_place_cols"})
SINK_SCOPE = (
    "foremast_tpu/engine/",
    "foremast_tpu/jobs/",
    "foremast_tpu/parallel/",
)
_NP_NAMES = frozenset({"np", "numpy"})
_NP_MATERIALIZERS = frozenset(
    {"asarray", "array", "asanyarray", "ascontiguousarray"}
)
_SYNC_BUILTINS = frozenset({"float", "int", "bool"})
_ITER_BUILTINS = frozenset({"list", "tuple", "sorted", "sum"})
_STATIC_ATTRS = frozenset({"shape", "ndim", "dtype", "size", "nbytes"})
_STATIC_CALLS = frozenset({"len", "isinstance", "hasattr", "getattr"})


def _jit_root_names(program: Program) -> frozenset[str]:
    """Every name the package binds to a jitted callable: decorated
    defs plus `name = jax.jit(fn)` / `self.attr = jax.jit(fn)`
    assignment targets."""
    names: set[str] = set()
    for module in program.modules:
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    if _is_jax_jit(deco) or (
                        isinstance(deco, ast.Call)
                        and _jit_call_statics(deco, {}) is not None
                    ):
                        names.add(node.name)
            elif isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ):
                call = node.value
                wraps_jit = _is_jax_jit(call.func) or (
                    isinstance(call.func, ast.Call)
                    and _jit_call_statics(call.func, {}) is not None
                )
                if not wraps_jit:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
                    elif isinstance(t, ast.Attribute):
                        names.add(t.attr)
    return frozenset(names)


def _is_pending_wait(call: ast.Call) -> bool:
    """`<something pending-ish>.wait()` — the gather half of the
    async dispatch split. Receiver must mention "pending" so bare
    `event.wait()` never taints."""
    func = call.func
    if not (isinstance(func, ast.Attribute) and func.attr == "wait"):
        return False
    recv = dotted(func.value) or ""
    return "pending" in recv.lower()


class _Taint:
    """Per-function taint computation against shared program state."""

    def __init__(self, program: Program, jit_names: frozenset[str]):
        self.program = program
        self.jit_names = jit_names
        # interprocedural state, grown to a fixpoint by analyze():
        self.tainted_params: dict[int, set[str]] = {}
        self.returns_device: set[int] = set()
        self.boundary: set[int] = set()

    def is_boundary(self, fn: FunctionInfo) -> bool:
        return id(fn) in self.boundary

    # -- expression classification ---------------------------------------

    def _call_is_source(self, call: ast.Call, fn: FunctionInfo) -> bool:
        d = dotted(call.func)
        if d is not None:
            root = d.split(".", 1)[0]
            if root in ("jnp",) or d.startswith("jax.numpy."):
                return True
            if d in ("jax.device_put", "device_put"):
                return True
        name = None
        if isinstance(call.func, ast.Name):
            name = call.func.id
        elif isinstance(call.func, ast.Attribute):
            name = call.func.attr
        if name in DISPATCH_ROOTS or name in PLACEMENT_HOOKS:
            return True
        if name in self.jit_names:
            return True
        if _is_pending_wait(call):
            return True
        for callee in self.program.resolve_call_direct(call, fn):
            if id(callee) in self.returns_device and not self.is_boundary(
                callee
            ):
                return True
        return False

    def _call_is_barrier(self, call: ast.Call, fn: FunctionInfo) -> bool:
        """Calls whose RESULT is host even when their arguments are
        device values: the designated gathers (`_fetch`,
        `jax.device_get`, any `# foremast: device-boundary` function)
        and the explicit conversions (which the sink pass flags on
        their own — taint must not survive them and double-report
        downstream)."""
        d = dotted(call.func)
        if d in ("jax.device_get", "device_get"):
            return True
        if (
            d is not None
            and "." in d
            and d.split(".", 1)[0] in _NP_NAMES
            and d.rsplit(".", 1)[1] in _NP_MATERIALIZERS
        ):
            return True
        if d in _SYNC_BUILTINS or d in _ITER_BUILTINS:
            return True
        name = call.func.attr if isinstance(call.func, ast.Attribute) else None
        if name in ("_fetch", "item", "tolist"):
            return True
        return any(
            self.is_boundary(callee)
            for callee in self.program.resolve_call_direct(call, fn)
        )

    def expr_device(
        self, expr: ast.AST, tainted: set[str], fn: FunctionInfo
    ) -> bool:
        """Does `expr` evaluate to (or contain, outside static-safe
        subtrees) a device/traced value?"""
        stack = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
                continue  # x.shape and friends are host metadata
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d in _STATIC_CALLS:
                    continue
                if self._call_is_barrier(node, fn):
                    continue  # gathered/converted: host from here on
                if self._call_is_source(node, fn):
                    return True
                # a non-source, non-barrier call's RESULT is unknown
                # (host by default) but its ARGUMENTS still flow into
                # it, so keep walking the whole call expression
            if isinstance(node, ast.Name) and node.id in tainted:
                return True
            stack.extend(ast.iter_child_nodes(node))
        return False

    # -- per-function local fixpoint -------------------------------------

    def local_taint(self, fn: FunctionInfo) -> set[str]:
        tainted = set(self.tainted_params.get(id(fn), ()))
        changed = True
        while changed:
            changed = False
            for node in own_body_walk(fn.node):
                if isinstance(node, ast.Assign):
                    value, targets = node.value, node.targets
                elif isinstance(node, ast.AnnAssign) and node.value is not None:
                    value, targets = node.value, [node.target]
                elif isinstance(node, ast.AugAssign):
                    value, targets = node.value, [node.target]
                else:
                    continue
                if not self.expr_device(value, tainted, fn):
                    continue
                for t in targets:
                    for leaf in ast.walk(t):
                        if (
                            isinstance(leaf, ast.Name)
                            and leaf.id not in tainted
                        ):
                            tainted.add(leaf.id)
                            changed = True
        return tainted


def _callee_params(callee: FunctionInfo) -> list[str]:
    a = callee.node.args
    return [
        p.arg
        for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)
        if p.arg not in ("self", "cls")
    ]


def check_device_flow(program: Program) -> list[Finding]:
    taint = _Taint(program, _jit_root_names(program))
    for fn in program.functions:
        if fn.module.marked_def(fn.node, BOUNDARY_MARKER):
            taint.boundary.add(id(fn))

    # interprocedural fixpoint: parameter taint + device-returning fns
    changed = True
    while changed:
        changed = False
        for fn in program.functions:
            if taint.is_boundary(fn):
                # a boundary's whole body is the sanctioned decode:
                # nothing it returns or passes onward is device taint
                continue
            tainted = taint.local_taint(fn)
            for node in own_body_walk(fn.node):
                if isinstance(node, ast.Return) and node.value is not None:
                    if (
                        id(fn) not in taint.returns_device
                        and not taint.is_boundary(fn)
                        and taint.expr_device(node.value, tainted, fn)
                    ):
                        taint.returns_device.add(id(fn))
                        changed = True
                elif isinstance(node, ast.Call):
                    callees = program.resolve_call_direct(node, fn)
                    if not callees:
                        continue
                    for callee in callees:
                        params = _callee_params(callee)
                        into = taint.tainted_params.setdefault(
                            id(callee), set()
                        )
                        for i, arg in enumerate(node.args):
                            if i >= len(params) or params[i] in into:
                                continue
                            if taint.expr_device(arg, tainted, fn):
                                into.add(params[i])
                                changed = True
                        for kw in node.keywords:
                            if kw.arg is None or kw.arg in into:
                                continue
                            if kw.arg in params and taint.expr_device(
                                kw.value, tainted, fn
                            ):
                                into.add(kw.arg)
                                changed = True

    findings: list[Finding] = []
    for fn in program.functions:
        if taint.is_boundary(fn):
            continue
        if not fn.module.relpath.startswith(SINK_SCOPE):
            continue
        # a jitted function's body (and its nested defs) is traced
        # code: `for`/`x[i]` unroll at trace time, conversions raise
        # TracerError on their own — jit-hygiene's domain, not a sync
        if any(part in taint.jit_names for part in fn.qualname.split(".")):
            continue
        findings.extend(_sink_findings(taint, fn))
    return findings


def _sink_findings(taint: _Taint, fn: FunctionInfo) -> list[Finding]:
    tainted = taint.local_taint(fn)
    module = fn.module
    out: list[Finding] = []

    def dev(expr: ast.AST) -> bool:
        return taint.expr_device(expr, tainted, fn)

    def flag(node: ast.AST, what: str) -> None:
        out.append(
            module.finding(
                RULE,
                node,
                f"{what} on a device/traced value in `{fn.name}` — an "
                "implicit device->host sync outside the gather/decode "
                "stages",
                hint="keep the value device-resident until the designated "
                "gather (`ColumnarPending.wait` / `_fetch`), or — if this "
                "function IS a gather/decode stage — mark the def with "
                "`# foremast: device-boundary` and document the contract "
                "(docs/static-analysis.md)",
            )
        )

    # per-element indexing: `buf[i]` where i is a range-loop variable
    range_vars: set[str] = set()
    for node in own_body_walk(fn.node):
        if isinstance(node, ast.For) and isinstance(node.iter, ast.Call):
            if dotted(node.iter.func) == "range":
                for leaf in ast.walk(node.target):
                    if isinstance(leaf, ast.Name):
                        range_vars.add(leaf.id)

    for node in own_body_walk(fn.node):
        if isinstance(node, ast.Call):
            d = dotted(node.func)
            if (
                d is not None
                and "." in d
                and d.split(".", 1)[0] in _NP_NAMES
                and d.rsplit(".", 1)[1] in _NP_MATERIALIZERS
            ):
                if any(dev(a) for a in node.args):
                    flag(node, f"`{d}()`")
            elif d in _SYNC_BUILTINS:
                if any(dev(a) for a in node.args):
                    flag(node, f"`{d}()`")
            elif d in _ITER_BUILTINS:
                if any(dev(a) for a in node.args):
                    flag(node, f"`{d}()` (Python iteration)")
            elif isinstance(node.func, ast.Attribute) and node.func.attr in (
                "item",
                "tolist",
            ):
                if dev(node.func.value):
                    flag(node, f"`.{node.func.attr}()`")
        elif isinstance(node, ast.For):
            if dev(node.iter):
                flag(node, "Python `for` iteration")
        elif isinstance(
            node, (ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)
        ):
            for gen in node.generators:
                if dev(gen.iter):
                    flag(node, "comprehension iteration")
        elif isinstance(node, ast.Subscript):
            if (
                isinstance(node.slice, ast.Name)
                and node.slice.id in range_vars
                and dev(node.value)
            ):
                flag(node, "per-element indexing")
    return out
