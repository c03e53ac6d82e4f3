"""jaxlint rules: JAX/TPU-aware static checks over module ASTs.

Each rule targets a defect class that is cheap to catch at review time
and expensive to catch on a pod: a host sync buried in a jitted step
serializes every device behind a Python round-trip; a reused PRNG key
silently correlates augmentations; a Python branch on a traced value
either crashes at trace time or triggers a recompile storm; iterating a
``set`` while building a pytree gives different flattening orders on
different hosts (different collective layouts → hang or silent
corruption); a train step jitted without donation doubles the
parameter+optimizer HBM footprint; an implicit-dtype array on the wire
path quietly re-inflates the uint8 wire format to float64; a benchmark
that stops its timer without a device sync measures dispatch, not work;
a TensorBoard tag interpolating a step number mints a fresh series
every step until the dashboard (and the event file) drowns; a blocking
device→host fetch on an in-flight result inside the prefetched step
loop re-introduces the per-step sync the async dispatch pipeline
exists to avoid.

Detection is intra-module and intentionally conservative: a rule fires
only on patterns it can see whole (see docs/STATIC_ANALYSIS.md for the
known blind spots).  False positives are silenced per line with
``# jaxlint: disable=<rule>`` or grandfathered in
``analysis/baseline.json`` — both require a justification.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Callable, Iterator

# --------------------------------------------------------------------------
# Findings and the rule registry
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint hit, anchored to a source line.

    ``code`` is the stripped source line — the baseline fingerprint, so
    grandfathered entries survive unrelated line-number drift."""

    path: str
    line: int
    col: int
    rule: str
    message: str
    code: str = ""
    end_line: int = 0  # statement extent: suppressions anywhere on
    # [line, end_line] apply (multiline calls put the comment last)

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: " \
               f"{self.message}"


@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    doc: str
    check: Callable[["ModuleContext"], Iterator[Finding]]


RULES: dict[str, Rule] = {}


def rule(name: str, doc: str):
    def deco(fn):
        RULES[name] = Rule(name, doc, fn)
        return fn
    return deco


# --------------------------------------------------------------------------
# Shared AST machinery
# --------------------------------------------------------------------------


def _import_aliases(tree: ast.AST) -> dict[str, str]:
    """Local name → canonical dotted prefix, from the module's imports.

    ``import jax.numpy as jnp`` → ``jnp: jax.numpy``; ``from jax import
    random`` → ``random: jax.random``; ``import numpy as np`` →
    ``np: numpy``.  Unaliased ``import a.b`` binds only ``a``."""
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    aliases[a.asname] = a.name
                else:
                    root = a.name.split(".")[0]
                    aliases[root] = root
        elif isinstance(node, ast.ImportFrom) and node.module \
                and not node.level:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def _qualname(node: ast.AST, aliases: dict[str, str]) -> str | None:
    """Canonical dotted name of a Name/Attribute chain, or None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(aliases.get(node.id, node.id))
    return ".".join(reversed(parts))


def _iter_defs(tree: ast.AST) -> Iterator[ast.FunctionDef]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _own_body_walk(fn: ast.AST) -> Iterator[ast.AST]:
    """Walk a function's body EXCLUDING nested function/lambda bodies —
    the per-scope view the key-reuse and timer counting need."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _param_names(fn: ast.FunctionDef) -> set[str]:
    a = fn.args
    names = [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return {n for n in names if n not in ("self", "cls")}


_JIT_WRAPPERS = ("jax.jit", "jax.pmap")


def _is_jit_wrapper(qual: str | None) -> bool:
    return qual is not None and (
        qual in _JIT_WRAPPERS or qual.endswith(".shard_map")
        or qual == "shard_map")


def _wrapped_fn_name(call: ast.Call,
                     aliases: dict[str, str]) -> str | None:
    """The local function name a jit/shard_map/pmap call wraps, seeing
    through one ``functools.partial`` layer."""
    if not call.args:
        return None
    target = call.args[0]
    if isinstance(target, ast.Call) and _qualname(
            target.func, aliases) == "functools.partial" and target.args:
        target = target.args[0]
    if isinstance(target, ast.Name):
        return target.id
    return None


def _static_param_names(call: ast.Call,
                        fn: ast.FunctionDef) -> set[str]:
    """Parameter names a jit call marks static (static_argnames /
    static_argnums) — those arrive as Python values, not tracers, so
    host coercion and branching on them are sound."""
    names: set[str] = set()
    positional = [p.arg for p in (*fn.args.posonlyargs, *fn.args.args)]
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and \
                        isinstance(c.value, str):
                    names.add(c.value)
        elif kw.arg == "static_argnums":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and \
                        isinstance(c.value, int) and \
                        0 <= c.value < len(positional):
                    names.add(positional[c.value])
    return names


def _find_jit_bodies(
        tree: ast.AST, aliases: dict[str, str]
) -> list[tuple[ast.FunctionDef, set[str]]]:
    """(FunctionDef, static param names) pairs for bodies that trace
    under jit/pmap/shard_map.

    Marked when (a) decorated with ``jax.jit``/``jax.pmap`` (directly or
    via ``partial``), or (b) the def's name is passed to a
    jit/pmap/shard_map call anywhere in the module.  Name-based, so a
    function reassigned between definition and the jit call can be
    missed — acceptable for this codebase's builder idiom."""
    by_name: dict[str, list[ast.FunctionDef]] = {}
    marked: dict[int, tuple[ast.FunctionDef, set[str]]] = {}

    def mark(fn: ast.FunctionDef, static: set[str]) -> None:
        prev = marked.get(id(fn))
        if prev is None:
            marked[id(fn)] = (fn, set(static))
        else:
            prev[1].update(static)

    for fn in _iter_defs(tree):
        by_name.setdefault(fn.name, []).append(fn)
        for dec in fn.decorator_list:
            if _is_jit_wrapper(_qualname(dec, aliases)):
                mark(fn, set())
            elif isinstance(dec, ast.Call):
                dq = _qualname(dec.func, aliases)
                if _is_jit_wrapper(dq):
                    mark(fn, _static_param_names(dec, fn))
                elif dq == "functools.partial" and dec.args and \
                        _is_jit_wrapper(_qualname(dec.args[0], aliases)):
                    mark(fn, _static_param_names(dec, fn))
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                _is_jit_wrapper(_qualname(node.func, aliases)):
            name = _wrapped_fn_name(node, aliases)
            for fn in by_name.get(name, ()):
                mark(fn, _static_param_names(node, fn))
    return list(marked.values())


class ModuleContext:
    """Everything the rules need about one parsed module."""

    def __init__(self, rel_path: str, source: str, tree: ast.Module):
        self.rel_path = rel_path
        self.source = source
        self.tree = tree
        self.lines = source.splitlines()
        self.aliases = _import_aliases(tree)
        self.jit_bodies = _find_jit_bodies(tree, self.aliases)

    def qual(self, node: ast.AST) -> str | None:
        return _qualname(node, self.aliases)

    def scopes(self) -> Iterator[ast.AST]:
        """The module plus every function def — one per analysis scope."""
        yield self.tree
        yield from _iter_defs(self.tree)

    def finding(self, node: ast.AST, rule_name: str,
                message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        code = self.lines[line - 1].strip() if line <= len(self.lines) \
            else ""
        return Finding(self.rel_path, line, col, rule_name, message,
                       code, getattr(node, "end_lineno", None) or line)


# --------------------------------------------------------------------------
# Rule 1: host-sync-in-jit
# --------------------------------------------------------------------------

_HOST_FETCH_CALLS = {"numpy.asarray", "numpy.array"}
_HOST_FETCH_METHODS = {"item", "tolist"}
_TRACER_COERCIONS = {"float", "int", "bool"}


def _rooted_at_param(node: ast.AST, params: set[str]) -> bool:
    """Whether an expression chains straight off a traced parameter
    (tracer → host coercion).  Chains that pass through ``.shape`` are
    static Python ints under jit and stay legal."""
    while True:
        if isinstance(node, ast.Attribute):
            if node.attr == "shape":
                return False
            node = node.value
        elif isinstance(node, ast.Subscript):
            node = node.value
        else:
            break
    return isinstance(node, ast.Name) and node.id in params


@rule("host-sync-in-jit",
      "device→host fetch inside a jitted/shard_mapped body breaks "
      "tracing or forces a per-step sync")
def check_host_sync(ctx: ModuleContext) -> Iterator[Finding]:
    for fn, static in ctx.jit_bodies:
        params = _param_names(fn) - static
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            qual = ctx.qual(node.func)
            if qual in _HOST_FETCH_CALLS:
                yield ctx.finding(
                    node, "host-sync-in-jit",
                    f"{qual}() inside jitted `{fn.name}` materializes a "
                    "tracer on host; keep the value in jnp or move the "
                    "fetch outside the compiled step")
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _HOST_FETCH_METHODS:
                yield ctx.finding(
                    node, "host-sync-in-jit",
                    f".{node.func.attr}() inside jitted `{fn.name}` is a "
                    "device→host sync; under trace it fails, under "
                    "callback it serializes the step")
            elif isinstance(node.func, ast.Name) and \
                    node.func.id in _TRACER_COERCIONS and \
                    node.func.id not in ctx.aliases and node.args and \
                    _rooted_at_param(node.args[0], params):
                yield ctx.finding(
                    node, "host-sync-in-jit",
                    f"{node.func.id}() applied to traced argument of "
                    f"`{fn.name}` — a concretization error at trace "
                    "time; use jnp ops on the tracer instead")


# --------------------------------------------------------------------------
# Rule 2: prng-key-reuse
# --------------------------------------------------------------------------

# jax.random.* that make or derive keys rather than consume entropy.
# Deriving several children from one parent via distinct fold_in data
# (train.py's idiom) is sound; two *draws* from one key are correlated.
_KEY_MAKERS = {"key", "PRNGKey", "split", "fold_in", "clone", "key_data",
               "wrap_key_data", "key_impl", "default_prng_impl"}


def _assigned_names(node: ast.AST) -> Iterator[tuple[ast.AST, str]]:
    targets: list[ast.AST] = []
    if isinstance(node, ast.Assign):
        targets = list(node.targets)
    elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
        targets = [node.target]
    elif isinstance(node, ast.For):
        targets = [node.target]
    elif isinstance(node, ast.NamedExpr):
        targets = [node.target]
    elif isinstance(node, ast.withitem) and node.optional_vars:
        targets = [node.optional_vars]
    for t in targets:
        for sub in ast.walk(t):
            if isinstance(sub, ast.Name):
                yield t, sub.id


def _is_key_draw(node: ast.AST, ctx: ModuleContext) -> str | None:
    """The key variable name a ``jax.random.*`` draw consumes, if any."""
    if isinstance(node, ast.Call) and node.args and \
            isinstance(node.args[0], ast.Name):
        qual = ctx.qual(node.func)
        if qual and qual.startswith("jax.random.") and \
                qual.rsplit(".", 1)[1] not in _KEY_MAKERS:
            return node.args[0].id
    return None


@rule("prng-key-reuse",
      "drawing twice from one PRNG key correlates the draws — split or "
      "fold_in between uses")
def check_key_reuse(ctx: ModuleContext) -> Iterator[Finding]:
    """Branch-aware linear scan: mutually exclusive ``if``/``else``
    (and ternary) arms, and ``try`` vs its ``except`` handlers, each
    see a copy of the per-key draw counts and merge as the per-name
    max afterwards — one draw per arm is NOT reuse, a draw before the
    branch plus one inside (or one after) is.  Loop bodies are scanned
    twice, so a draw from a loop-invariant key (identical values every
    iteration — the correlated-inits classic) fires; a key rebound
    inside the body stays clean.  Rebinding (``split``/``fold_in``
    assignment) resets the count."""
    findings: list[Finding] = []

    def merge_max(counts: dict[str, int], *states: dict) -> None:
        for st in states:
            for name in st:
                counts[name] = max(counts.get(name, 0), st[name])

    def visit(node: ast.AST, counts: dict[str, int]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            return  # separate scope
        if isinstance(node, (ast.If, ast.IfExp)):
            visit(node.test, counts)
            body = node.body if isinstance(node.body, list) \
                else [node.body]
            orelse = node.orelse if isinstance(node.orelse, list) \
                else [node.orelse]
            after_body = dict(counts)
            after_else = dict(counts)
            for n in body:
                visit(n, after_body)
            for n in orelse:
                visit(n, after_else)
            counts.clear()
            merge_max(counts, after_body, after_else)
            return
        if isinstance(node, ast.Try):
            # A handler is an alternative path to the draw that raised:
            # try-draw + except-fallback-draw is one draw per run.
            pre = dict(counts)
            for n in (*node.body, *node.orelse):
                visit(n, counts)
            handler_states = []
            for h in node.handlers:
                hc = dict(pre)
                for n in h.body:
                    visit(n, hc)
                handler_states.append(hc)
            merge_max(counts, *handler_states)
            for n in node.finalbody:
                visit(n, counts)
            return
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign,
                             ast.NamedExpr)):
            if getattr(node, "value", None) is not None:
                visit(node.value, counts)
            for _t, name in _assigned_names(node):
                counts[name] = 0  # fresh binding
            return
        if isinstance(node, (ast.For, ast.While)):
            # Two passes over the body: a key consumed every iteration
            # without an in-body rebind reaches count 2 on the second
            # pass (the per-iteration reuse a single pass cannot see).
            if isinstance(node, ast.For):
                visit(node.iter, counts)
            else:
                visit(node.test, counts)
            for _pass in range(2):
                if isinstance(node, ast.For):
                    for _t, name in _assigned_names(node):
                        counts[name] = 0  # loop target: fresh each iter
                for n in node.body:
                    visit(n, counts)
            for n in node.orelse:
                visit(n, counts)
            return
        for child in ast.iter_child_nodes(node):
            visit(child, counts)
        name = _is_key_draw(node, ctx)
        if name is not None:
            counts[name] = counts.get(name, 0) + 1
            if counts[name] == 2:
                findings.append(ctx.finding(
                    node, "prng-key-reuse",
                    f"key `{name}` already consumed by an earlier "
                    "jax.random draw on this path; split/fold_in "
                    "before drawing again (reused keys correlate "
                    "augmentations/inits silently)"))

    for scope in ctx.scopes():
        counts: dict[str, int] = {}
        body = scope.body if isinstance(
            scope, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)) \
            else []
        for stmt in body:
            visit(stmt, counts)
    # The second loop-body pass can rediscover an in-body reuse at the
    # same node — report each site once.
    seen: set[tuple[int, int]] = set()
    for f_ in findings:
        if (f_.line, f_.col) not in seen:
            seen.add((f_.line, f_.col))
            yield f_


def _top_scope_walk(tree: ast.AST) -> Iterator[ast.AST]:
    """Module-level statements, excluding function/class bodies."""
    stack = list(ast.iter_child_nodes(tree))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


# --------------------------------------------------------------------------
# Rule 3: recompile-hazard
# --------------------------------------------------------------------------


def _names_outside_is_compare(test: ast.AST) -> Iterator[ast.Name]:
    """Name nodes in a test expression, skipping operands of pure
    ``is``/``is not`` comparisons (None-structure checks are static
    under jit and a legitimate branch)."""
    skip: set[int] = set()
    for node in ast.walk(test):
        if isinstance(node, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            for sub in ast.walk(node):
                skip.add(id(sub))
    for node in ast.walk(test):
        if isinstance(node, ast.Name) and id(node) not in skip:
            yield node


@rule("recompile-hazard",
      "Python control flow / formatting on traced values inside a jit "
      "body — trace error or a recompile per distinct value")
def check_recompile_hazard(ctx: ModuleContext) -> Iterator[Finding]:
    for fn, static in ctx.jit_bodies:
        params = _param_names(fn) - static
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                hit = next(
                    (n for n in _names_outside_is_compare(node.test)
                     if n.id in params), None)
                if hit is not None:
                    kind = "while" if isinstance(node, ast.While) \
                        else "if"
                    yield ctx.finding(
                        node, "recompile-hazard",
                        f"Python `{kind}` on traced argument "
                        f"`{hit.id}` of `{fn.name}`: branch with "
                        "lax.cond/jnp.where, or hoist the decision to "
                        "the builder")
                elif any(isinstance(n, ast.Attribute)
                         and n.attr == "shape"
                         for n in ast.walk(node.test)):
                    yield ctx.finding(
                        node, "recompile-hazard",
                        f"branching on `.shape` inside `{fn.name}` "
                        "specializes the compile per input geometry — "
                        "one recompile per distinct shape reaching "
                        "this step")
            elif isinstance(node, ast.JoinedStr):
                for fv in node.values:
                    if isinstance(fv, ast.FormattedValue) and any(
                            isinstance(n, ast.Name) and n.id in params
                            for n in ast.walk(fv.value)):
                        yield ctx.finding(
                            node, "recompile-hazard",
                            f"f-string formats traced argument inside "
                            f"`{fn.name}` — str(tracer) escapes the "
                            "trace (use jax.debug.print)")
                        break


# --------------------------------------------------------------------------
# Rule 4: nondeterministic-pytree-order
# --------------------------------------------------------------------------

_SET_METHODS = {"intersection", "union", "difference",
                "symmetric_difference"}
_SET_OPS = (ast.BitOr, ast.BitAnd, ast.Sub, ast.BitXor)


def _is_set_expr(node: ast.AST, ctx: ModuleContext,
                 set_vars: set[str]) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Name):
        return node.id in set_vars
    if isinstance(node, ast.Call):
        qual = ctx.qual(node.func)
        if qual in ("set", "frozenset"):
            return True
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _SET_METHODS:
            return True
    if isinstance(node, ast.BinOp) and isinstance(node.op, _SET_OPS):
        return _is_set_expr(node.left, ctx, set_vars) or \
            _is_set_expr(node.right, ctx, set_vars)
    return False


@rule("nondeterministic-pytree-order",
      "iterating a set while building a pytree/param dict gives "
      "per-host orders — divergent collective layouts at scale")
def check_set_iteration(ctx: ModuleContext) -> Iterator[Finding]:
    # Source-ordered scan per scope: an assignment updates which names
    # hold sets AT THAT POINT, so `s = set(x); s = sorted(s); for v in
    # s` is clean (the rebinding de-sets `s`) and iterating before the
    # set assignment never flags.
    for scope in ctx.scopes():
        walk_fn = (_own_body_walk if isinstance(
            scope, (ast.FunctionDef, ast.AsyncFunctionDef))
            else _top_scope_walk)
        events: list[tuple[int, int, str, ast.AST]] = []
        for node in walk_fn(scope):
            if isinstance(node, ast.Assign):
                events.append((node.lineno, node.col_offset,
                               "assign", node))
            elif isinstance(node, ast.For):
                events.append((node.iter.lineno, node.iter.col_offset,
                               "iter", node.iter))
                # The loop variable itself is an item, not a set.
                events.append((node.iter.lineno, node.iter.col_offset,
                               "unset", node))
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.DictComp, ast.GeneratorExp)):
                events.extend((g.iter.lineno, g.iter.col_offset,
                               "iter", g.iter)
                              for g in node.generators)
        events.sort(key=lambda e: (e[0], e[1]))
        set_vars: set[str] = set()
        for _ln, _col, kind, node in events:
            if kind == "assign":
                names = {name for _t, name in _assigned_names(node)}
                if _is_set_expr(node.value, ctx, set_vars):
                    set_vars |= names
                else:
                    set_vars -= names  # rebound to a non-set
            elif kind == "unset":
                set_vars -= {name for _t, name
                             in _assigned_names(node)}
            else:
                if isinstance(node, ast.Call) and \
                        ctx.qual(node.func) == "sorted":
                    continue  # sorted() fixes the order
                if _is_set_expr(node, ctx, set_vars):
                    yield ctx.finding(
                        node, "nondeterministic-pytree-order",
                        "iteration over a set: hash order is "
                        "per-process, so pytrees/param dicts built "
                        "from it flatten differently across hosts "
                        "(mismatched collectives hang the pod) — wrap "
                        "in sorted()")


# --------------------------------------------------------------------------
# Rule 5: missing-donation
# --------------------------------------------------------------------------


def _is_train_step_builder(name: str) -> bool:
    return "train_step" in name or (
        name.startswith("make_") and "step" in name
        and "eval" not in name)


@rule("missing-donation",
      "jitting a train step without donate_argnums doubles the "
      "params+optimizer HBM footprint")
def check_missing_donation(ctx: ModuleContext) -> Iterator[Finding]:
    for fn in _iter_defs(ctx.tree):
        if not _is_train_step_builder(fn.name):
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    ctx.qual(node.func) == "jax.jit" and not any(
                        kw.arg in ("donate_argnums", "donate_argnames")
                        for kw in node.keywords):
                yield ctx.finding(
                    node, "missing-donation",
                    f"jax.jit in train-step builder `{fn.name}` "
                    "without donate_argnums/donate_argnames: the old "
                    "TrainState stays live across the update — "
                    "2x params+opt memory, the difference between "
                    "fitting and OOM at scale")


# --------------------------------------------------------------------------
# Rule 6: dtype-contract
# --------------------------------------------------------------------------

# Creators whose dtype defaults (float64/int64) silently re-inflate the
# uint8 wire format; positional index at which dtype may appear.
_CREATOR_DTYPE_POS = {
    "numpy.zeros": 1, "numpy.ones": 1, "numpy.empty": 1,
    "numpy.full": 2, "numpy.asarray": 1, "numpy.array": 1,
    "jax.numpy.zeros": 1, "jax.numpy.ones": 1, "jax.numpy.empty": 1,
    "jax.numpy.full": 2, "jax.numpy.asarray": 1, "jax.numpy.array": 1,
}
_WIDE_CASTS = {"float64", "double"}


def _in_wire_scope(ctx: ModuleContext) -> bool:
    parts = ctx.rel_path.replace("\\", "/").split("/")
    return "data" in parts[:-1]


@rule("dtype-contract",
      "implicit array dtype on the wire-format path re-inflates the "
      "uint8 wire to float64 silently")
def check_dtype_contract(ctx: ModuleContext) -> Iterator[Finding]:
    scopes: list[ast.AST] = []
    if _in_wire_scope(ctx):
        scopes.append(ctx.tree)
    else:
        scopes.extend(fn for fn in _iter_defs(ctx.tree)
                      if fn.name == "make_input_prep")
    for scope in scopes:
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call):
                continue
            qual = ctx.qual(node.func)
            pos = _CREATOR_DTYPE_POS.get(qual or "")
            if pos is not None:
                has_dtype = len(node.args) > pos or any(
                    kw.arg == "dtype" for kw in node.keywords)
                if not has_dtype:
                    yield ctx.finding(
                        node, "dtype-contract",
                        f"{qual}() without an explicit dtype on the "
                        "wire-format path: the float64/int64 default "
                        "breaks the raw-uint8 wire contract "
                        "(data/pipeline.py::Batch) and inflates "
                        "IPC/H2D bytes 8x")
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "astype" and node.args:
                arg = node.args[0]
                tq = ctx.qual(arg) or ""
                lit = arg.value if isinstance(arg, ast.Constant) else ""
                if tq.rsplit(".", 1)[-1] in _WIDE_CASTS or \
                        lit in _WIDE_CASTS:
                    yield ctx.finding(
                        node, "dtype-contract",
                        "float64 cast on the wire-format path: 8 "
                        "bytes/value over IPC and H2D where the "
                        "contract is 1 (uint8)")


# --------------------------------------------------------------------------
# Rule 7: telemetry-tag-format
# --------------------------------------------------------------------------

_TB_WRITE_METHODS = {"add_scalar", "add_scalars", "add_histogram"}
# namespace/snake_case: lowercase segments separated by "/", each
# starting with a letter — what every telemetry series in the repo
# uses ("goodput/fraction", "steptime/p95_ms", "data/h2d_mb").
_TAG_RE = re.compile(r"^[a-z][a-z0-9_]*(/[a-z][a-z0-9_]*)*$")
# OpenMetrics family names (telemetry/export.py Exposition.family):
# strict snake_case, no slashes/colons. A call site is judged as a
# family declaration when its second argument is a literal metric
# type — the Exposition signature — so unrelated `.family(...)`
# methods elsewhere are never misjudged.
_OM_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
_OM_TYPES = {"gauge", "counter", "info", "histogram", "summary"}


@rule("telemetry-tag-format",
      "TB tags and exporter metric families must be snake_case "
      "literals; interpolating values (step numbers) into a name "
      "mints unbounded series")
def check_telemetry_tags(ctx: ModuleContext) -> Iterator[Finding]:
    """Conservative: only literal and f-string first arguments to the
    writer methods are judged (a variable tag is invisible here — the
    call sites that build tags dynamically must keep the family
    bounded, which is what the suppression justification documents).
    Exporter family declarations (``.family(name, "gauge", ...)``) get
    the same treatment with the OpenMetrics name grammar: a scraper's
    series set must be bounded and greppable, so family names are
    literal snake_case, never f-string-minted."""
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.args):
            continue
        if (node.func.attr == "family" and len(node.args) >= 2
                and isinstance(node.args[1], ast.Constant)
                and node.args[1].value in _OM_TYPES):
            name = node.args[0]
            if isinstance(name, ast.JoinedStr):
                if any(isinstance(v, ast.FormattedValue)
                       for v in name.values):
                    yield ctx.finding(
                        node, "telemetry-tag-format",
                        "f-string OpenMetrics family name in "
                        ".family(): every distinct interpolated value "
                        "mints a NEW metric family for the scraper — "
                        "put variables in LABELS (bounded), or "
                        "suppress with the justification that the "
                        "family set is bounded")
            elif isinstance(name, ast.Constant) \
                    and isinstance(name.value, str) \
                    and not _OM_NAME_RE.match(name.value):
                yield ctx.finding(
                    node, "telemetry-tag-format",
                    f"OpenMetrics family name {name.value!r} is not "
                    "snake_case (^[a-z][a-z0-9_]*$): scrapers and "
                    "recording rules expect the Prometheus naming "
                    "grammar (no slashes, no capitals)")
            continue
        if node.func.attr not in _TB_WRITE_METHODS:
            continue
        tag = node.args[0]
        if isinstance(tag, ast.JoinedStr):
            if any(isinstance(v, ast.FormattedValue)
                   for v in tag.values):
                yield ctx.finding(
                    node, "telemetry-tag-format",
                    f"f-string tag in {node.func.attr}(): every "
                    "distinct interpolated value mints a NEW "
                    "TensorBoard series (a step number in the tag = "
                    "one series per step) — put variables in the "
                    "step/value arguments, or suppress with the "
                    "justification that the family is bounded")
        elif isinstance(tag, ast.Constant) and isinstance(tag.value,
                                                          str):
            if not _TAG_RE.match(tag.value):
                yield ctx.finding(
                    node, "telemetry-tag-format",
                    f"tag {tag.value!r} is not namespace/snake_case "
                    "(^[a-z][a-z0-9_]*(/segment)*$): mixed-case and "
                    "ad-hoc tags scatter related series across the "
                    "TB sidebar instead of grouping under one "
                    "namespace")


# --------------------------------------------------------------------------
# Rule 8: untimed-block
# --------------------------------------------------------------------------

_TIMER_CALLS = {"time.time", "time.perf_counter", "time.monotonic"}
# np.asarray / device_get are accepted as syncs next to
# block_until_ready: a device->host fetch of a result cannot return
# before the device has produced it, and several of the repo's
# benchmarks sync by fetching a reduction.
_SYNC_CALLS = {"jax.block_until_ready", "jax.device_get",
               "numpy.asarray", "numpy.array"}


def _in_bench_scope(ctx: ModuleContext) -> bool:
    parts = ctx.rel_path.replace("\\", "/").split("/")
    return "benchmarks" in parts[:-1] or \
        parts[-1].startswith("bench")


@rule("untimed-block",
      "timing device work without a sync measures async dispatch, not "
      "the computation")
def check_untimed_block(ctx: ModuleContext) -> Iterator[Finding]:
    if not _in_bench_scope(ctx):
        return
    if not any(a == "jax" or a.startswith("jax.")
               for a in ctx.aliases.values()):
        return  # no device work to mistime
    for scope in ctx.scopes():
        own = (_own_body_walk(scope) if isinstance(
            scope, (ast.FunctionDef, ast.AsyncFunctionDef))
            else _top_scope_walk(scope))
        timers = sorted(
            (n for n in own if isinstance(n, ast.Call)
             and ctx.qual(n.func) in _TIMER_CALLS),
            key=lambda n: (n.lineno, n.col_offset))
        if len(timers) < 2:
            continue
        # A sync counts only at/after the first timer: a warmup-only
        # sync BEFORE the timed region still leaves the measurement
        # bracketing nothing but async dispatch.
        start = (timers[0].lineno, timers[0].col_offset)
        synced = any(
            isinstance(n, ast.Call) and (
                ctx.qual(n.func) in _SYNC_CALLS
                or (isinstance(n.func, ast.Attribute)
                    and n.func.attr == "block_until_ready"))
            and (n.lineno, n.col_offset) > start
            for n in ast.walk(scope))
        if not synced:
            name = getattr(scope, "name", "<module>")
            yield ctx.finding(
                timers[1], "untimed-block",
                f"`{name}` brackets work with timers but never syncs "
                "the device (block_until_ready / device_get / hard "
                "np.asarray fetch): jax dispatch is async, so the "
                "measured time is queueing, not compute")


# --------------------------------------------------------------------------
# Rule 9: blocking-call-in-step-loop
# --------------------------------------------------------------------------

# The prefetched step loop's invariant (engine.py): the loop body
# dispatches asynchronously and NOTHING in it blocks on an in-flight
# step result — metrics are consumed by a frontier lagged _GUARD_LAG
# steps behind the dispatch (already retired → the fetch is free).
_STEP_LOOP_SOURCES = {"device_prefetch", "Prefetcher"}
_BLOCKING_FETCH_CALLS = {"numpy.asarray", "numpy.array",
                         "jax.device_get", "jax.block_until_ready"}
_BLOCKING_FETCH_METHODS = {"item", "tolist", "block_until_ready",
                           # Chip-accountant APIs (ISSUE 19): compile
                           # analyses and allocator stats are host
                           # syncs too — capture belongs at step-build
                           # time (telemetry/chipacct.py), never in
                           # the step loop.
                           "memory_stats", "cost_analysis",
                           "memory_analysis"}
_LAG_SENTINEL = "_GUARD_LAG"


def _has_step_source_call(node: ast.AST, ctx: ModuleContext,
                          loop_vars: set[str]) -> bool:
    """Whether an expression contains a device_prefetch/Prefetcher call
    or references a name bound from one."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            qual = ctx.qual(sub.func) or ""
            if qual.rsplit(".", 1)[-1] in _STEP_LOOP_SOURCES:
                return True
        elif isinstance(sub, ast.Name) and sub.id in loop_vars:
            return True
    return False


@rule("blocking-call-in-step-loop",
      "blocking device→host fetch on an in-flight step result inside a "
      "prefetched step loop — re-introduces the per-step sync; read "
      "from the _GUARD_LAG-lagged frontier instead")
def check_blocking_in_step_loop(ctx: ModuleContext) -> Iterator[Finding]:
    """Fires on ``np.asarray``/``np.array``/``jax.device_get``/
    ``jax.block_until_ready`` calls and ``.item()``/``.tolist()``/
    ``.block_until_ready()`` — plus the chip-accountant surfaces
    ``.memory_stats()``/``.cost_analysis()``/``.memory_analysis()``
    (startup-capture-only APIs) — methods inside the body of a ``for`` loop
    that iterates ``device_prefetch(...)``/``Prefetcher(...)`` (or a
    name assigned from one, tracked in source order) — the engine's
    step loops.  Exemption: a statement whose subtree references
    ``_GUARD_LAG`` reads the lagged frontier — that step has already
    retired, so the fetch is a free D2H, not a drain.  Blind spot
    (documented in docs/STATIC_ANALYSIS.md): a prefetcher that reaches
    the loop only as a function parameter is invisible; keep the
    engine's builder idiom (assign from the constructor expression)."""
    for scope in ctx.scopes():
        walk_fn = (_own_body_walk if isinstance(
            scope, (ast.FunctionDef, ast.AsyncFunctionDef))
            else _top_scope_walk)
        nodes = sorted(
            (n for n in walk_fn(scope)
             if isinstance(n, (ast.Assign, ast.NamedExpr, ast.For))),
            key=lambda n: (n.lineno, n.col_offset))
        loop_vars: set[str] = set()
        step_loops: list[ast.For] = []
        for node in nodes:
            if isinstance(node, (ast.Assign, ast.NamedExpr)):
                names = {name for _t, name in _assigned_names(node)}
                if _has_step_source_call(node.value, ctx, loop_vars):
                    loop_vars |= names
                else:
                    loop_vars -= names  # rebound to something else
            elif _has_step_source_call(node.iter, ctx, loop_vars):
                step_loops.append(node)
        for loop in step_loops:
            for stmt in loop.body:
                lagged = any(isinstance(n, ast.Name)
                             and n.id == _LAG_SENTINEL
                             for n in ast.walk(stmt))
                if lagged:
                    continue
                for node in ast.walk(stmt):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef,
                                         ast.Lambda)):
                        continue
                    if not isinstance(node, ast.Call):
                        continue
                    qual = ctx.qual(node.func)
                    if qual in _BLOCKING_FETCH_CALLS:
                        yield ctx.finding(
                            node, "blocking-call-in-step-loop",
                            f"{qual}() inside the prefetched step loop "
                            "blocks on an in-flight step result — the "
                            "per-step sync the async dispatch pipeline "
                            "exists to avoid; consume from a frontier "
                            f"lagged {_LAG_SENTINEL} steps behind the "
                            "dispatch (engine._LaggedMetrics), or "
                            "suppress with justification")
                    elif isinstance(node.func, ast.Attribute) and \
                            node.func.attr in _BLOCKING_FETCH_METHODS:
                        yield ctx.finding(
                            node, "blocking-call-in-step-loop",
                            f".{node.func.attr}() inside the prefetched "
                            "step loop is a device→host sync on an "
                            "in-flight result — it drains the dispatch "
                            "pipeline every step; read the "
                            f"{_LAG_SENTINEL}-lagged frontier instead, "
                            "or suppress with justification")
