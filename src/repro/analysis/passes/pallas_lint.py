"""pallas-lint pass — kernel constraints the TPU backend enforces late.

Pallas failures surface at trace/compile time (or only on real TPUs when
CI runs interpret mode), so the cheap structural mistakes are worth
catching statically:

* Python ``if``/``while`` on traced values inside a kernel body — refs
  and ``pl.program_id`` results are tracers; data-dependent Python
  control flow must go through ``pl.when``/``lax.cond``. Static config
  branches (keyword-only params bound via ``functools.partial``, e.g.
  ``if causal:``) are fine and not flagged.
* Grid sizes computed with a plain floor division and no guard — a
  non-divisible size silently drops the tail. Ceil-div (``-(-a // b)``
  or ``pl.cdiv``) or a matching ``assert x % b == 0`` in the same
  function makes the intent explicit.
* ``pl.pallas_call`` without an ``interpret=`` argument (or with it
  hardcoded ``False``) — every kernel must keep the off-TPU interpret
  fallback reachable, per the `ops._default_interpret` idiom.
* Fused-update completeness — the tick state lives in the dict returned
  by the paired ``<mode>_state0`` / ``<mode>_body`` functions
  (``core.sweep.jaxbody``). A key present in ``state0``'s dict but
  dropped from ``body``'s return dict is a state plane the fused update
  silently freezes at its initial value; no runtime error ever fires.

Rules
  PL501  Python control flow on a traced value inside a kernel
  PL502  grid size floor-divided without a ceil idiom or divisibility
         guard
  PL503  pallas_call without a reachable interpret fallback
  PL505  tick-state plane dropped from a fused body's return dict
"""
from __future__ import annotations

import ast

from repro.analysis.astutil import attr_chain, names_in
from repro.analysis.core import Finding, RepoContext, register_pass

RULES = (
    ("PL501", "data-dependent Python control flow in kernel"),
    ("PL502", "grid floor-division without ceil or divisibility guard"),
    ("PL503", "pallas_call without interpret fallback"),
    ("PL505", "tick-state plane dropped from fused body return"),
)

_KERNEL_SUFFIX = "_kernel"


def _tainted_names(fn: ast.FunctionDef) -> set[str]:
    """Names carrying traced values inside a kernel body.

    Seeds: positional params (the refs; keyword-only params are static
    config bound at partial time) and ``pl.program_id`` results. Then
    propagates through simple assignments to a fixpoint.
    """
    tainted = {a.arg for a in fn.args.args + fn.args.posonlyargs}
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            rhs_names = names_in(node.value)
            is_pid = any(
                isinstance(c, ast.Call)
                and attr_chain(c.func) in (["pl", "program_id"],
                                           ["pltpu", "program_id"])
                for c in ast.walk(node.value) if isinstance(c, ast.Call))
            if not (rhs_names & tainted or is_pid):
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id not in tainted:
                    tainted.add(tgt.id)
                    changed = True
    return tainted


def check_kernel_control_flow(tree: ast.Module, path: str) -> list[Finding]:
    out: list[Finding] = []
    for fn in ast.walk(tree):
        if (not isinstance(fn, ast.FunctionDef)
                or not fn.name.endswith(_KERNEL_SUFFIX)):
            continue
        tainted = _tainted_names(fn)
        for node in ast.walk(fn):
            if not isinstance(node, (ast.If, ast.While)):
                continue
            used = names_in(node.test) & tainted
            if used:
                kind = "while" if isinstance(node, ast.While) else "if"
                out.append(Finding(
                    path, node.lineno, "PL501",
                    f"Python `{kind}` on traced value(s) "
                    f"{sorted(used)} inside kernel '{fn.name}' — use "
                    "pl.when / lax.cond for data-dependent branches"))
    return out


def _is_ceil_div(node: ast.expr) -> bool:
    """``-(-a // b)`` or ``pl.cdiv(a, b)``."""
    if (isinstance(node, ast.Call)
            and attr_chain(node.func) == ["pl", "cdiv"]):
        return True
    return (isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub)
            and isinstance(node.operand, ast.BinOp)
            and isinstance(node.operand.op, ast.FloorDiv)
            and isinstance(node.operand.left, ast.UnaryOp)
            and isinstance(node.operand.left.op, ast.USub))


def _divisibility_guards(fn: ast.FunctionDef) -> set[tuple[str, str]]:
    """(numerator, divisor) name pairs asserted divisible in ``fn``
    (``assert a % b == 0`` — also inside chained/bool-op asserts)."""
    guards: set[tuple[str, str]] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assert):
            continue
        for cmp_ in ast.walk(node.test):
            if not isinstance(cmp_, ast.Compare):
                continue
            left = cmp_.left
            if (isinstance(left, ast.BinOp)
                    and isinstance(left.op, ast.Mod)
                    and any(isinstance(c, ast.Constant) and c.value == 0
                            for c in cmp_.comparators)):
                num = left.left.id if isinstance(left.left, ast.Name) else ""
                div = (left.right.id
                       if isinstance(left.right, ast.Name) else "")
                guards.add((num, div))
    return guards


def _local_ceil_names(fn: ast.FunctionDef) -> set[str]:
    """Names assigned from a ceil-div expression inside ``fn``."""
    names: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and _is_ceil_div(node.value):
            names.update(t.id for t in node.targets
                         if isinstance(t, ast.Name))
    return names


def _grid_elements(call: ast.Call, fn: ast.FunctionDef) -> list[ast.expr]:
    """Expressions making up the grid of a pallas_call, resolving a
    ``grid_spec=Name`` through a local ``PrefetchScalarGridSpec`` (or any
    ``*GridSpec``) assignment."""
    elems: list[ast.expr] = []

    def from_grid_kw(c: ast.Call):
        for kw in c.keywords:
            if kw.arg == "grid":
                v = kw.value
                elems.extend(v.elts if isinstance(v, ast.Tuple) else [v])

    from_grid_kw(call)
    for kw in call.keywords:
        if kw.arg != "grid_spec":
            continue
        v = kw.value
        if isinstance(v, ast.Call):
            from_grid_kw(v)
        elif isinstance(v, ast.Name):
            for node in ast.walk(fn):
                if (isinstance(node, ast.Assign)
                        and any(isinstance(t, ast.Name) and t.id == v.id
                                for t in node.targets)
                        and isinstance(node.value, ast.Call)):
                    from_grid_kw(node.value)
    return elems


def _floor_div_ok(expr: ast.expr, fn: ast.FunctionDef) -> bool:
    if _is_ceil_div(expr):
        return True
    if isinstance(expr, ast.Name):
        if expr.id in _local_ceil_names(fn):
            return True
        # resolve one level: name assigned from a floor-div expression,
        # including tuple unpacks like `nq, nk = sq // qb, skv // kb`
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == expr.id:
                    return _floor_div_ok(node.value, fn)
                if (isinstance(t, ast.Tuple)
                        and isinstance(node.value, ast.Tuple)):
                    for sub_t, sub_v in zip(t.elts, node.value.elts):
                        if (isinstance(sub_t, ast.Name)
                                and sub_t.id == expr.id):
                            return _floor_div_ok(sub_v, fn)
        return True  # opaque name (e.g. a parameter): not a floor-div
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.FloorDiv):
        guards = _divisibility_guards(fn)
        num = expr.left.id if isinstance(expr.left, ast.Name) else ""
        div = expr.right.id if isinstance(expr.right, ast.Name) else ""
        return (num, div) in guards
    return True  # constants, products, etc.


def check_grids(tree: ast.Module, path: str) -> list[Finding]:
    out: list[Finding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and attr_chain(node.func) == ["pl", "pallas_call"]):
                continue
            for elem in _grid_elements(node, fn):
                if not _floor_div_ok(elem, fn):
                    out.append(Finding(
                        path, elem.lineno, "PL502",
                        "grid size uses a plain floor division with no "
                        "ceil idiom (-(-a // b) / pl.cdiv) and no "
                        "`assert a % b == 0` guard — a non-divisible "
                        "size silently drops the tail tile"))
    return out


def check_interpret(tree: ast.Module, path: str) -> list[Finding]:
    out: list[Finding] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and attr_chain(node.func) == ["pl", "pallas_call"]):
            continue
        kw = next((k for k in node.keywords if k.arg == "interpret"), None)
        if kw is None:
            out.append(Finding(
                path, node.lineno, "PL503",
                "pallas_call without interpret= — off-TPU CI cannot run "
                "this kernel; thread an interpret flag through "
                "(auto-select with jax.default_backend() != 'tpu')"))
        elif (isinstance(kw.value, ast.Constant)
              and kw.value.value is False):
            out.append(Finding(
                path, kw.value.lineno, "PL503",
                "interpret=False is hardcoded at the call site — the "
                "off-TPU fallback is unreachable"))
    return out


_SWEEP_DIR = "src/repro/core/sweep"


def _own_returns(fn: ast.FunctionDef) -> list[ast.Return]:
    """Return statements of ``fn`` itself, not of nested functions
    (the open-mode body nests arrival helpers with their own dicts)."""
    out: list[ast.Return] = []
    stack: list[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Lambda)):
            continue
        if isinstance(node, ast.Return):
            out.append(node)
        stack.extend(ast.iter_child_nodes(node))
    return out


def _splat_keys(v: ast.expr) -> set[str] | None:
    """String keys of a ``**`` splat built of dict literals, such as
    ``**({"k": x} if cfg.flag else {})`` (a plane only some grids
    carry); None for any other splat."""
    if isinstance(v, ast.IfExp):
        a, b = _splat_keys(v.body), _splat_keys(v.orelse)
        return None if a is None or b is None else a | b
    if isinstance(v, ast.Dict) and all(
            isinstance(k, ast.Constant) and isinstance(k.value, str)
            for k in v.keys):
        return {k.value for k in v.keys}
    return None


def _returned_dict_keys(fn: ast.FunctionDef) -> set[str] | None:
    """Union of keyword names over every ``return dict(...)`` of ``fn``,
    with the keys of its dict-literal ``**`` splats; None when no return
    is such a ``dict(...)`` call (not a state function in the jaxbody
    idiom — nothing to check)."""
    keys: set[str] | None = None
    for ret in _own_returns(fn):
        v = ret.value
        if not (isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
                and v.func.id == "dict" and v.keywords):
            continue
        got = [{kw.arg} if kw.arg else _splat_keys(kw.value)
               for kw in v.keywords]
        if all(k is not None for k in got):
            keys = (keys or set()).union(*got)
    return keys


def check_state_keysets(tree: ast.Module, path: str) -> list[Finding]:
    """PL505 — every plane initialised by ``<mode>_state0`` must appear
    in the dict returned by the paired ``<mode>_body``; a dropped key is
    a state plane the fused tick update silently freezes."""
    fns = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}
    out: list[Finding] = []
    for name, s0 in fns.items():
        if not name.endswith("_state0"):
            continue
        body_fn = fns.get(name[: -len("_state0")] + "_body")
        if body_fn is None:
            continue
        s0_keys = _returned_dict_keys(s0)
        body_keys = _returned_dict_keys(body_fn)
        if s0_keys is None or body_keys is None:
            continue
        for key in sorted(s0_keys - body_keys):
            out.append(Finding(
                path, body_fn.lineno, "PL505",
                f"state plane '{key}' is initialised by {name} but "
                f"missing from {body_fn.name}'s returned dict — the "
                "fused tick loop would carry it frozen at its initial "
                "value with no runtime error"))
    return out


@register_pass("pallas-lint", rules=RULES)
def run(ctx: RepoContext) -> list[Finding]:
    """Lint every Pallas kernel module for traced control flow, grid
    divisibility and the interpret-mode fallback; lint the shared
    tick-state modules for fused-update completeness."""
    out: list[Finding] = []
    for rel in ctx.py_files(ctx.KERNELS_DIR):
        text = ctx.text(rel)
        if text is None or "pallas" not in text:
            continue
        tree = ctx.tree(rel)
        if tree is None:
            continue
        out.extend(check_kernel_control_flow(tree, rel))
        out.extend(check_grids(tree, rel))
        out.extend(check_interpret(tree, rel))
    for rel in ctx.py_files(_SWEEP_DIR):
        text = ctx.text(rel)
        if text is None or "_state0" not in text:
            continue
        tree = ctx.tree(rel)
        if tree is None:
            continue
        out.extend(check_state_keysets(tree, rel))
    return out
