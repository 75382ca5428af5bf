"""The rule registry: JAX/TPU-specific lint rules over module ASTs.

Every rule is a heuristic tuned for this tree — precision over recall:
a rule that cries wolf gets suppressed wholesale and protects nothing.
Each entry documents the failure mode it guards and the idiom it wants.

Shared machinery:

- :class:`JitIndex` — which function/lambda bodies are traced scope
  (decorated with jit/pjit, passed to ``jax.jit``/``pjit``, or a
  ``lax.scan`` body).  Host syncs and impure calls are only findings
  *inside* traced scope; the host-side driver loops in rollout/ are
  full of legitimate ``device_get``/``np.asarray``.
- the stateful rules (PRNG reuse, donated-arg reuse, bench timing)
  walk statements in source order via :func:`_header_exprs` /
  :func:`_child_blocks`; loop bodies are visited twice so "same key
  every iteration" bugs fire, and branches that end in return/raise
  don't leak state past the ``if`` (guard clauses are not reuse).
"""

from __future__ import annotations

import ast
import os
from typing import Callable, Dict, Iterable, List, Optional, Set, Tuple

from orion_tpu.analysis.engine import Finding, ModuleContext, is_test_path

RULES: List["Rule"] = []


class Rule:
    #: "file" rules see one ModuleContext; "project" rules (defined in
    #: analysis/project.py) see the whole parsed tree at once.
    kind = "file"

    def __init__(self, rule_id: str, description: str,
                 checker: Callable[[ModuleContext], Iterable[Finding]]):
        self.id = rule_id
        self.description = description
        self._checker = checker

    def check(self, ctx: ModuleContext) -> List[Finding]:
        return list(self._checker(ctx))


def rule(rule_id: str, description: str):
    def deco(fn):
        RULES.append(Rule(rule_id, description, fn))
        return fn
    return deco


# ---------------------------------------------------------------------------
# shared: traced-scope index
# ---------------------------------------------------------------------------

_JIT_WRAPPERS = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}
# lax control-flow primitives -> positions of their traced callables:
# scan(body, init, xs); fori_loop(lo, hi, body, init);
# while_loop(cond, body, init); cond(pred, true_fn, false_fn)
_SCAN_BODY_ARGS = {
    "jax.lax.scan": (0,),
    "jax.lax.fori_loop": (2,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.cond": (1, 2),
}


def _is_jit_wrapper(ctx: ModuleContext, node: ast.AST) -> bool:
    d = ctx.dotted(node)
    return d in _JIT_WRAPPERS


class JitIndex:
    """Set of AST nodes whose bodies execute under a jax trace."""

    def __init__(self, ctx: ModuleContext):
        self.ctx = ctx
        # Lexical scoping for name->def resolution: each def records
        # the chain of enclosing function scopes, so jax.jit(body)
        # marks the ``body`` visible from the call site — not every
        # same-named def in the module (``body``/``step`` are reused
        # constantly in this tree).
        self._scope_of: Dict[int, tuple] = {}
        defs: Dict[str, List[ast.AST]] = {}

        def index(node, chain):
            for child in ast.iter_child_nodes(node):
                if isinstance(child,
                              (ast.FunctionDef, ast.AsyncFunctionDef)):
                    defs.setdefault(child.name, []).append(child)
                    self._scope_of[id(child)] = chain
                    index(child, chain + (id(child),))
                else:
                    self._scope_of[id(child)] = chain
                    index(child, chain)

        index(ctx.tree, ())
        roots: Set[ast.AST] = set()

        for node in ctx.walk():
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if self._decorator_is_jit(dec):
                        roots.add(node)
            elif isinstance(node, ast.Call):
                body_args = ()
                if _is_jit_wrapper(ctx, node.func):
                    body_args = (0,)
                else:
                    body_args = _SCAN_BODY_ARGS.get(
                        ctx.dotted(node.func) or "", ())
                for i in body_args:
                    if i < len(node.args):
                        self._mark(node.args[i], node, defs, roots)

        # traced scope = every node under a root
        self.traced: Set[int] = set()
        for root in roots:
            for sub in ast.walk(root):
                self.traced.add(id(sub))
        self.roots = roots

    def _decorator_is_jit(self, dec: ast.AST) -> bool:
        if _is_jit_wrapper(self.ctx, dec):
            return True
        if isinstance(dec, ast.Call):
            if _is_jit_wrapper(self.ctx, dec.func):
                return True  # @jax.jit(...)
            if self.ctx.dotted(dec.func) == "functools.partial" and \
                    dec.args and _is_jit_wrapper(self.ctx, dec.args[0]):
                return True  # @partial(jax.jit, static_argnums=...)
        return False

    def _mark(self, target: Optional[ast.AST], call: ast.Call,
              defs: Dict[str, List[ast.AST]], roots: Set[ast.AST]) -> None:
        if target is None:
            return
        if isinstance(target, ast.Lambda):
            roots.add(target)
        elif isinstance(target, ast.Name):
            # lexical resolution: among same-named defs, only those
            # visible from the call site, preferring the closest scope
            call_chain = self._scope_of.get(id(call), ())
            visible = [
                d for d in defs.get(target.id, ())
                if call_chain[:len(self._scope_of.get(id(d), ()))]
                == self._scope_of.get(id(d), ())
            ]
            if visible:
                deepest = max(len(self._scope_of.get(id(d), ()))
                              for d in visible)
                for d in visible:
                    if len(self._scope_of.get(id(d), ())) == deepest:
                        roots.add(d)
        elif isinstance(target, ast.Attribute):
            # jax.jit(self._update_fn) marks the method by name
            for d in defs.get(target.attr, ()):
                roots.add(d)
        elif isinstance(target, ast.Call) and \
                self.ctx.dotted(target.func) == "functools.partial" and \
                target.args:
            self._mark(target.args[0], call, defs, roots)

    def in_trace(self, node: ast.AST) -> bool:
        return id(node) in self.traced


def _jit_index(ctx: ModuleContext) -> JitIndex:
    """One JitIndex per module, shared by every traced-scope rule —
    building it walks the whole tree, so rules must not each rebuild
    it."""
    idx = getattr(ctx, "_jit_index_cache", None)
    if idx is None:
        idx = JitIndex(ctx)
        ctx._jit_index_cache = idx
    return idx


def _walk_traced(ctx: ModuleContext, jit: JitIndex):
    """Yield every AST node inside traced scope, once."""
    seen: Set[int] = set()
    for root in jit.roots:
        for node in ast.walk(root):
            if id(node) not in seen:
                seen.add(id(node))
                yield node


# ---------------------------------------------------------------------------
# rule: compat-import — one import site for shard_map / axis_size
# ---------------------------------------------------------------------------

_SHIM_HINT = ("use orion_tpu.utils.platform.shard_map / axis_size — "
              "the one place that maps this repo's keyword spelling "
              "onto jax.shard_map / lax.axis_size")


@rule("compat-import",
      "direct jax.shard_map / lax.axis_size use that bypasses the "
      "utils/platform.py forwards (one import site per jax API)")
def _check_compat_import(ctx: ModuleContext):
    if ctx.path.replace(os.sep, "/").endswith("utils/platform.py"):
        return  # the shim itself
    for node in ctx.walk():
        if isinstance(node, ast.ImportFrom) and node.module:
            mod = node.module
            for a in node.names:
                if mod.startswith("jax") and \
                        (a.name == "shard_map"
                         or mod.endswith("shard_map")):
                    yield Finding("compat-import", ctx.path, node.lineno,
                                  f"direct import of shard_map from "
                                  f"{mod!r}", hint=_SHIM_HINT)
                if mod in ("jax.lax", "lax") and a.name == "axis_size":
                    yield Finding("compat-import", ctx.path, node.lineno,
                                  "direct import of lax.axis_size",
                                  hint=_SHIM_HINT)
        elif isinstance(node, (ast.Attribute, ast.Name)):
            d = ctx.dotted(node)
            if d == "jax.shard_map" or \
                    (d and d.endswith(".shard_map")
                     and d.startswith("jax.")):
                yield Finding("compat-import", ctx.path, node.lineno,
                              f"use of {d}", hint=_SHIM_HINT)
            elif d in ("jax.lax.axis_size", "lax.axis_size"):
                yield Finding("compat-import", ctx.path, node.lineno,
                              f"use of {d}", hint=_SHIM_HINT)


# ---------------------------------------------------------------------------
# rule: host-sync-in-jit
# ---------------------------------------------------------------------------

_AGG_METHODS = {"sum", "mean", "max", "min", "any", "all", "prod"}


def _is_arrayish_call(ctx: ModuleContext, node: ast.AST) -> bool:
    """Heuristic: expression is (probably) a device array — a call into
    jax.* / jax.numpy.* / jax.lax.*, or an aggregation method call."""
    if not isinstance(node, ast.Call):
        return False
    d = ctx.dotted(node.func)
    if d and (d.startswith("jax.") or d.startswith("jnp.")):
        return True
    return (isinstance(node.func, ast.Attribute)
            and node.func.attr in _AGG_METHODS)


@rule("host-sync-in-jit",
      "host synchronization (.item(), float()/int() on arrays, "
      "np.asarray, jax.device_get, .block_until_ready) inside traced "
      "scope")
def _check_host_sync(ctx: ModuleContext):
    jit = _jit_index(ctx)
    for node in _walk_traced(ctx, jit):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr == "item" and \
                not node.args:
            yield Finding("host-sync-in-jit", ctx.path, node.lineno,
                          ".item() inside traced scope forces a "
                          "device->host sync per step",
                          hint="return the array and .item() outside "
                               "the jitted fn")
        elif isinstance(fn, ast.Attribute) and \
                fn.attr == "block_until_ready":
            yield Finding("host-sync-in-jit", ctx.path, node.lineno,
                          ".block_until_ready() inside traced scope",
                          hint="block on the OUTPUT outside the jitted "
                               "fn; inside a trace it is meaningless")
        else:
            d = ctx.dotted(fn)
            if d == "jax.device_get":
                yield Finding("host-sync-in-jit", ctx.path, node.lineno,
                              "jax.device_get inside traced scope",
                              hint="fetch outside the jitted fn")
            elif d in ("numpy.asarray", "numpy.array"):
                yield Finding("host-sync-in-jit", ctx.path, node.lineno,
                              f"{d} inside traced scope pulls the "
                              "array to host",
                              hint="use jnp.asarray, or hoist the host "
                                   "conversion out of the jitted fn")
            elif d in ("float", "int") and node.args and \
                    _is_arrayish_call(ctx, node.args[0]):
                yield Finding("host-sync-in-jit", ctx.path, node.lineno,
                              f"{d}() on an array value inside traced "
                              "scope",
                              hint="keep it an array; convert outside "
                                   "the jitted fn")


# ---------------------------------------------------------------------------
# rule: impure-in-jit
# ---------------------------------------------------------------------------

_IMPURE_CALLS = {
    "time.time": "wall-clock reads trace to a constant; hoist timing "
                 "out of the jitted fn",
    "time.perf_counter": "wall-clock reads trace to a constant; hoist "
                         "timing out of the jitted fn",
    "time.monotonic": "wall-clock reads trace to a constant; hoist "
                      "timing out of the jitted fn",
    "print": "print() fires at trace time only; use jax.debug.print "
             "for per-step output",
}


@rule("impure-in-jit",
      "impure call (time.*, np.random.*, print, stdlib random) inside "
      "traced scope — runs at trace time, not per step")
def _check_impure(ctx: ModuleContext):
    jit = _jit_index(ctx)
    for node in _walk_traced(ctx, jit):
        if not isinstance(node, ast.Call):
            continue
        d = ctx.dotted(node.func)
        if d in _IMPURE_CALLS:
            yield Finding("impure-in-jit", ctx.path, node.lineno,
                          f"{d}() inside traced scope",
                          hint=_IMPURE_CALLS[d])
        elif d and (d.startswith("numpy.random.")
                    or d in ("random.random", "random.randint",
                             "random.uniform", "random.choice",
                             "random.shuffle")):
            yield Finding("impure-in-jit", ctx.path, node.lineno,
                          f"{d}() inside traced scope bakes one sample "
                          "into the compiled program",
                          hint="thread a jax.random key through the "
                               "jitted fn instead")


# ---------------------------------------------------------------------------
# rule: traced-branch
# ---------------------------------------------------------------------------


@rule("traced-branch",
      "Python if/while branching on a traced array value inside traced "
      "scope (TracerBoolConversionError or silent recompiles)")
def _check_traced_branch(ctx: ModuleContext):
    jit = _jit_index(ctx)

    def arrayish_test(test: ast.AST) -> bool:
        for sub in ast.walk(test):
            if _is_arrayish_call(ctx, sub):
                return True
        return False

    for node in _walk_traced(ctx, jit):
        if isinstance(node, (ast.If, ast.While)) and \
                arrayish_test(node.test):
            kw = "if" if isinstance(node, ast.If) else "while"
            yield Finding("traced-branch", ctx.path, node.lineno,
                          f"Python {kw} on an array-valued condition "
                          "in traced scope",
                          hint="use jnp.where / lax.cond / lax.select "
                               "on the traced value")


# ---------------------------------------------------------------------------
# rule: prng-reuse
# ---------------------------------------------------------------------------

_KEY_SOURCES = {"jax.random.key", "jax.random.PRNGKey", "jax.random.split",
                "jax.random.fold_in", "jax.random.clone",
                "jax.random.wrap_key_data"}
_KEY_MANAGERS = {"split", "fold_in", "key", "PRNGKey", "wrap_key_data",
                 "key_data", "clone", "key_impl"}
_RNG_PARAM_NAMES = {"rng", "key", "prng", "prng_key", "rng_key"}


def _terminates(stmts: List[ast.stmt]) -> bool:
    """A block whose last statement leaves the enclosing scope/loop."""
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))


def _assigned_names(stmt: ast.stmt) -> Set[str]:
    out: Set[str] = set()
    targets: List[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign, ast.For)):
        targets = [stmt.target]
    elif isinstance(stmt, ast.With):
        targets = [i.optional_vars for i in stmt.items
                   if i.optional_vars is not None]
    for t in targets:
        for sub in ast.walk(t):
            if isinstance(sub, ast.Name):
                out.add(sub.id)
    return out


@rule("prng-reuse",
      "the same PRNG key passed to two or more jax.random consumers "
      "without an intervening split/fold_in (correlated samples)")
def _check_prng_reuse(ctx: ModuleContext):
    findings: List[Finding] = []

    def consumer(call: ast.Call) -> bool:
        d = ctx.dotted(call.func)
        return bool(d and d.startswith("jax.random.")
                    and d.rsplit(".", 1)[1] not in _KEY_MANAGERS)

    def scan_fn(fn_node) -> None:
        keyvars: Dict[str, int] = {}
        if isinstance(fn_node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for a in (fn_node.args.posonlyargs + fn_node.args.args
                      + fn_node.args.kwonlyargs):
                if a.arg in _RNG_PARAM_NAMES:
                    keyvars[a.arg] = 0

        def visit_expr(e: ast.AST) -> None:
            for sub in ast.walk(e):
                if isinstance(sub, ast.Call) and consumer(sub):
                    for arg in list(sub.args) + \
                            [kw.value for kw in sub.keywords]:
                        if isinstance(arg, ast.Name) and \
                                arg.id in keyvars:
                            keyvars[arg.id] += 1
                            if keyvars[arg.id] == 2:
                                findings.append(Finding(
                                    "prng-reuse", ctx.path, sub.lineno,
                                    f"PRNG key {arg.id!r} reused by a "
                                    "second jax.random consumer "
                                    "without split/fold_in",
                                    hint="key, sub = jax.random.split("
                                         "key) before each consumer"))

        def visit_block(stmts: List[ast.stmt],
                        state: Dict[str, int]) -> None:
            nonlocal keyvars
            for stmt in stmts:
                keyvars = state
                if isinstance(stmt,
                              (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                    continue  # nested scopes get their own scan
                for e in ast.iter_child_nodes(stmt):
                    if isinstance(e, ast.expr):
                        visit_expr(e)
                if isinstance(stmt, (ast.If,)):
                    before = dict(state)
                    visit_block(stmt.body, state)
                    after_body = dict(state)
                    other = dict(before)
                    visit_block(stmt.orelse, other)
                    # a branch that ends in return/raise never reaches
                    # the code after the if — guard-clause dispatch on
                    # the same key is NOT reuse
                    body_exits = _terminates(stmt.body)
                    else_exits = _terminates(stmt.orelse)
                    if body_exits and not else_exits:
                        state.clear()
                        state.update(other)
                    elif else_exits and not body_exits:
                        state.clear()
                        state.update(after_body)
                    else:
                        for k in set(after_body) | set(other):
                            state[k] = max(after_body.get(k, 0),
                                           other.get(k, 0))
                elif isinstance(stmt, (ast.For, ast.While)):
                    # two passes: a key consumed once per iteration
                    # without reassignment is reuse across iterations
                    visit_block(stmt.body, state)
                    visit_block(stmt.body, state)
                    visit_block(stmt.orelse, state)
                elif isinstance(stmt, (ast.With, ast.Try)):
                    for blk in (getattr(stmt, "body", []),
                                getattr(stmt, "orelse", []),
                                getattr(stmt, "finalbody", [])):
                        visit_block(blk, state)
                    for h in getattr(stmt, "handlers", []):
                        visit_block(h.body, state)
                assigned = _assigned_names(stmt)
                for name in assigned:
                    src_is_key = False
                    if isinstance(stmt, ast.Assign) and \
                            isinstance(stmt.value, ast.Call) and \
                            ctx.dotted(stmt.value.func) in _KEY_SOURCES:
                        src_is_key = True
                    if src_is_key:
                        state[name] = 0
                    elif name in state:
                        del state[name]

        body = fn_node.body if hasattr(fn_node, "body") else []
        if isinstance(body, list):
            visit_block(body, keyvars)

    for node in ctx.walk():
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_fn(node)
    # module top level too
    scan_fn(ctx.tree)
    # de-dup (two-pass loops can record the same line twice)
    seen: Set[Tuple[int, str]] = set()
    for f in findings:
        if (f.line, f.message) not in seen:
            seen.add((f.line, f.message))
            yield f


# ---------------------------------------------------------------------------
# rule: mutable-default
# ---------------------------------------------------------------------------


def _mutable_literal(node: ast.AST, ctx: ModuleContext) -> bool:
    if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                         ast.DictComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        return ctx.dotted(node.func) in ("list", "dict", "set") and \
            not node.args and not node.keywords
    return False


@rule("mutable-default",
      "mutable default argument / dataclass field (shared across calls "
      "or instances)")
def _check_mutable_default(ctx: ModuleContext):
    for node in ctx.walk():
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            defaults = list(node.args.defaults) + \
                [d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                if _mutable_literal(d, ctx):
                    name = getattr(node, "name", "<lambda>")
                    yield Finding("mutable-default", ctx.path, d.lineno,
                                  f"mutable default argument in "
                                  f"{name}()",
                                  hint="default to None and create "
                                       "inside, or use "
                                       "dataclasses.field("
                                       "default_factory=...)")
        elif isinstance(node, ast.ClassDef):
            for stmt in node.body:
                val = None
                if isinstance(stmt, ast.AnnAssign):
                    val = stmt.value
                elif isinstance(stmt, ast.Assign):
                    val = stmt.value
                if val is not None and _mutable_literal(val, ctx):
                    yield Finding("mutable-default", ctx.path,
                                  val.lineno,
                                  f"mutable class-level default in "
                                  f"{node.name}",
                                  hint="use dataclasses.field("
                                       "default_factory=...) or set it "
                                       "in __init__ / __post_init__")


# ---------------------------------------------------------------------------
# rule: donated-reuse
# ---------------------------------------------------------------------------


def _donating_jits(ctx: ModuleContext) -> Dict[str, ast.Call]:
    """dotted name of a jitted callable -> the jax.jit(...) call that
    created it with donate_argnums.  Tracks ``x = jax.jit(f,
    donate_argnums=...)`` and ``self.x = jax.jit(...)`` assignments."""
    out: Dict[str, ast.Call] = {}
    for node in ctx.walk():
        if not isinstance(node, ast.Assign):
            continue
        v = node.value
        if isinstance(v, ast.Call) and _is_jit_wrapper(ctx, v.func) and \
                any(kw.arg == "donate_argnums" for kw in v.keywords):
            for t in node.targets:
                d = ctx.dotted(t)
                if d:
                    out[d] = v
    return out


def _donated_indices(jit_call: ast.Call) -> List[int]:
    for kw in jit_call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return [v.value]
            if isinstance(v, (ast.Tuple, ast.List)):
                return [e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int)]
    return []


def _header_exprs(stmt: ast.stmt) -> List[ast.expr]:
    """The expressions a compound statement evaluates BEFORE its nested
    blocks run; for simple statements, every expression.  Lets the
    stateful rules visit code in source order without double-walking
    nested bodies."""
    if isinstance(stmt, (ast.If, ast.While)):
        return [stmt.test]
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [stmt.iter]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        return [i.context_expr for i in stmt.items]
    if isinstance(stmt, ast.Try):
        return []
    return [e for e in ast.iter_child_nodes(stmt)
            if isinstance(e, ast.expr)]


def _child_blocks(stmt: ast.stmt) -> List[List[ast.stmt]]:
    blocks = []
    for attr in ("body", "orelse", "finalbody"):
        blk = getattr(stmt, attr, None)
        if isinstance(blk, list) and blk and \
                isinstance(blk[0], ast.stmt):
            blocks.append(blk)
    for h in getattr(stmt, "handlers", []):
        blocks.append(h.body)
    return blocks


@rule("donated-reuse",
      "argument donated to a jitted call (donate_argnums) read again "
      "after the call — the buffer is dead")
def _check_donated_reuse(ctx: ModuleContext):
    donors = _donating_jits(ctx)
    if not donors:
        return

    findings: List[Finding] = []

    def scan_fn(fn_node) -> None:
        dead: Dict[str, int] = {}  # dotted name -> line donated

        def _inside_donating_call(exprs, target) -> bool:
            """True if ``target`` is an argument of the donating call
            itself (the donation site, not a later read)."""
            for e in exprs:
                for sub in ast.walk(e):
                    if isinstance(sub, ast.Call) and \
                            ctx.dotted(sub.func) in donors:
                        for a in sub.args:
                            if target in ast.walk(a):
                                return True
            return False

        def visit_block(stmts: List[ast.stmt]) -> None:
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.ClassDef)):
                    continue
                exprs = _header_exprs(stmt)
                # reads of dead names BEFORE this statement's own
                # donation bookkeeping
                for e in exprs:
                    for sub in ast.walk(e):
                        if isinstance(sub, (ast.Name, ast.Attribute)) \
                                and isinstance(
                                    getattr(sub, "ctx", None), ast.Load):
                            d = ctx.dotted(sub)
                            if d in dead and not _inside_donating_call(
                                    exprs, sub):
                                findings.append(Finding(
                                    "donated-reuse", ctx.path,
                                    sub.lineno,
                                    f"{d!r} was donated on line "
                                    f"{dead[d]} and read again",
                                    hint="reassign the result "
                                         "(x = f(x)) or drop "
                                         "donate_argnums for this arg"))
                                del dead[d]
                # new donations in this statement
                for e in exprs:
                    for sub in ast.walk(e):
                        if isinstance(sub, ast.Call):
                            d = ctx.dotted(sub.func)
                            if d in donors:
                                for i in _donated_indices(donors[d]):
                                    if i < len(sub.args):
                                        nm = ctx.dotted(sub.args[i])
                                        if nm:
                                            dead[nm] = sub.lineno
                for blk in _child_blocks(stmt):
                    visit_block(blk)
                # assignments revive names (incl. tuple / attribute
                # targets: ``self.state, stats = jit_fn(self.state)``)
                if isinstance(stmt, ast.Assign):
                    for t in stmt.targets:
                        for sub in ast.walk(t):
                            d = ctx.dotted(sub)
                            if d:
                                dead.pop(d, None)
                for name in _assigned_names(stmt):
                    dead.pop(name, None)

        if isinstance(getattr(fn_node, "body", None), list):
            visit_block(fn_node.body)

    for node in ctx.walk():
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_fn(node)
    seen: Set[Tuple[int, str]] = set()
    for f in findings:
        if (f.line, f.message) not in seen:
            seen.add((f.line, f.message))
            yield f


# ---------------------------------------------------------------------------
# rule: bench-no-block
# ---------------------------------------------------------------------------

_TIME_READS = {"time.time", "time.perf_counter", "time.monotonic"}
# Anything that forces the timed computation to finish counts: an
# explicit block, a device_get, or a host materialization.
_BLOCKERS = {"jax.block_until_ready", "jax.device_get",
             "numpy.asarray", "numpy.array"}
_BLOCKER_METHODS = {"block_until_ready", "item"}


def _bench_file(path: str) -> bool:
    base = os.path.basename(path)
    return base.startswith(("bench", "profile")) or \
        "/scripts/bench" in path.replace(os.sep, "/")


@rule("bench-no-block",
      "benchmark timing window with no block_until_ready — it measures "
      "the async dispatch, not the computation (bench files only)")
def _check_bench_no_block(ctx: ModuleContext):
    if not _bench_file(ctx.path):
        return

    findings: List[Finding] = []

    def scan_scope(body: List[ast.stmt]) -> None:
        window_open = False
        saw_call = False
        saw_block = False

        def classify(stmt: ast.stmt) -> None:
            nonlocal window_open, saw_call, saw_block
            # ast.walk is breadth-first; the TIME/CALL/BLOCK sequencing
            # below needs source order.
            calls = sorted(
                (sub for sub in ast.walk(stmt)
                 if isinstance(sub, ast.Call)),
                key=lambda c: (c.lineno, c.col_offset))
            for sub in calls:
                d = ctx.dotted(sub.func)
                if d in _TIME_READS:
                    if window_open and saw_call and not saw_block:
                        findings.append(Finding(
                            "bench-no-block", ctx.path, sub.lineno,
                            "timing window closes without "
                            "block_until_ready on the timed result",
                            hint="jax.block_until_ready(out) before "
                                 "reading the clock"))
                    window_open, saw_call, saw_block = True, False, False
                elif d in _BLOCKERS or (
                        isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in _BLOCKER_METHODS):
                    saw_block = True
                else:
                    saw_call = True

        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan_scope(stmt.body)
            elif isinstance(stmt, ast.ClassDef):
                for s in stmt.body:
                    if isinstance(s, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                        scan_scope(s.body)
            else:
                classify(stmt)

    scan_scope(ctx.tree.body)
    for f in findings:
        yield f


# ---------------------------------------------------------------------------
# rule: unsupervised-thread
# ---------------------------------------------------------------------------


@rule("unsupervised-thread",
      "threading.Thread started in orion_tpu/ library code without "
      "watchdog registration — a crashed or stalled worker is "
      "invisible to the supervisor")
def _check_unsupervised_thread(ctx: ModuleContext):
    # Library code only: tests/ and scripts/ spawn throwaway threads
    # whose lifetime the test harness already bounds.
    p = ctx.path.replace(os.sep, "/")
    if "orion_tpu/" not in p:
        return

    # innermost enclosing function for every node (ast.walk is BFS, so
    # outer functions are visited first and inner assignments win)
    scope_of: Dict[int, Optional[ast.AST]] = {}
    functions = [n for n in ctx.walk()
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for fn in functions:
        for sub in ast.walk(fn):
            scope_of[id(sub)] = fn

    def supervised(scope: Optional[ast.AST]) -> bool:
        """The scope (or, for module level, the module's top-level
        statements) contains a watchdog-flavored call — e.g.
        ``self.watchdog.register(...)`` / ``Watchdog().register``."""
        if scope is None:
            nodes = [n for n in ctx.walk()
                     if scope_of.get(id(n)) is None]
        else:
            nodes = list(ast.walk(scope))
        for sub in nodes:
            if isinstance(sub, ast.Call):
                d = ctx.dotted(sub.func)
                if d and "watchdog" in d.lower():
                    return True
        return False

    for node in ctx.walk():
        if not isinstance(node, ast.Call):
            continue
        if ctx.dotted(node.func) != "threading.Thread":
            continue
        if supervised(scope_of.get(id(node))):
            continue
        yield Finding(
            "unsupervised-thread", ctx.path, node.lineno,
            "threading.Thread started without watchdog registration "
            "in its scope",
            hint="register a heartbeat with orion_tpu.resilience."
                 "Watchdog in the spawning function (see "
                 "AsyncOrchestrator._spawn_worker), or justify with "
                 "# orion: ignore[unsupervised-thread]")


# ---------------------------------------------------------------------------
# rule: naked-timer
# ---------------------------------------------------------------------------

_TIMER_CALLS = {"time.time", "time.monotonic", "time.perf_counter"}


def _scope_walk(root: ast.AST):
    """Walk one function scope (or the module top level) WITHOUT
    descending into nested function/class bodies — each nested def
    gets its own independent scan, so timer variables never leak
    across scopes."""
    stack = list(ast.iter_child_nodes(root))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef, ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


@rule("naked-timer",
      "wall-clock delta (time.time/monotonic/perf_counter subtraction) "
      "used for timing outside orion_tpu/obs — invisible to the span "
      "timeline (deadline comparisons are exempt)")
def _check_naked_timer(ctx: ModuleContext):
    # obs IS the timing layer; tests time freely (their scaffolding is
    # not the product's observability surface).
    p = ctx.path.replace(os.sep, "/")
    if "orion_tpu/obs/" in p or is_test_path(ctx.path):
        return

    def is_timer_call(node: ast.AST) -> bool:
        return isinstance(node, ast.Call) and \
            ctx.dotted(node.func) in _TIMER_CALLS

    findings: List[Finding] = []

    def scan_scope(root: ast.AST) -> None:
        tainted: Set[str] = set()
        for node in _scope_walk(root):
            # taint only PURE timer assignments (x = time.monotonic());
            # `deadline = time.monotonic() + timeout` is a deadline,
            # not a timestamp, and stays clean.
            if isinstance(node, ast.Assign) and is_timer_call(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        tainted.add(t.id)
        exempt: Set[int] = set()
        for node in _scope_walk(root):
            if isinstance(node, ast.Compare):
                # `now - start > timeout` is a deadline/stall CHECK,
                # not a measurement — every Sub under a Compare is
                # exempt.
                for sub in ast.walk(node):
                    if isinstance(sub, ast.BinOp) and \
                            isinstance(sub.op, ast.Sub):
                        exempt.add(id(sub))

        def timer_read(e: ast.AST) -> bool:
            return is_timer_call(e) or (isinstance(e, ast.Name)
                                        and e.id in tainted)

        for node in _scope_walk(root):
            if isinstance(node, ast.BinOp) and \
                    isinstance(node.op, ast.Sub) and \
                    id(node) not in exempt and \
                    timer_read(node.left) and timer_read(node.right):
                findings.append(Finding(
                    "naked-timer", ctx.path, node.lineno,
                    "raw timer delta used for timing",
                    hint="route through orion_tpu.obs spans — `with "
                         "obs.timed(name) as sp: ...; sp.duration` "
                         "measures even with tracing off AND lands the "
                         "scope on the Perfetto timeline; benches that "
                         "deliberately time wall windows justify with "
                         "# orion: ignore[naked-timer]"))

    for node in ctx.walk():
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_scope(node)
    scan_scope(ctx.tree)
    seen: Set[Tuple[int, str]] = set()
    for f in findings:
        if (f.line, f.message) not in seen:
            seen.add((f.line, f.message))
            yield f


# ---------------------------------------------------------------------------
# rule: raw-socket
# ---------------------------------------------------------------------------

_SOCKET_CALLS = {"socket.socket", "socket.create_connection"}


@rule("raw-socket",
      "raw socket construction outside orchestration/remote.py — "
      "cross-process IO must ride the hardened PyTreeChannel "
      "(keepalive, framed protocol, fault points)")
def _check_raw_socket(ctx: ModuleContext):
    # remote.py IS the hardened channel: the one module allowed to
    # touch sockets directly.
    if ctx.path.replace(os.sep, "/").endswith("orchestration/remote.py"):
        return
    for node in ctx.walk():
        if not isinstance(node, ast.Call):
            continue
        d = ctx.dotted(node.func)
        if d in _SOCKET_CALLS:
            yield Finding(
                "raw-socket", ctx.path, node.lineno,
                f"{d}() outside orchestration/remote.py — unframed, "
                "no keepalive, invisible to the channel fault points",
                hint="use orion_tpu.orchestration.remote.PyTreeChannel"
                     " / WorkerPool; a non-IO use (free-port probe) "
                     "can justify # orion: ignore[raw-socket]")


# ---------------------------------------------------------------------------
# rule: unused-suppression (engine-evaluated)
# ---------------------------------------------------------------------------


def _unused_suppression_stub(ctx: ModuleContext):
    """The real check lives in the engine: a suppression can only be
    judged against the rules that actually RAN on its line, across
    BOTH phases (a stale ``# orion: ignore[lock-discipline]`` needs the
    project phase's verdict).  This stub registers the id so
    ``--rule`` / ``--list-rules`` / the fixture-coverage test see it."""
    return ()


RULES.append(Rule(
    "unused-suppression",
    "an '# orion: ignore[rule-id]' comment whose rule no longer fires "
    "on that line (ruff unused-noqa semantics) — a dead ignore hides "
    "the next real finding there",
    _unused_suppression_stub))


# Project rules (analysis/project.py phase 2, analysis/callgraph.py
# phase 3 — importing callgraph registers its rules into PROJECT_RULES)
# share this registry so the CLI lists one table; the engine dispatches
# them by Rule.kind.
from orion_tpu.analysis import callgraph  # noqa: E402,F401
from orion_tpu.analysis.project import PROJECT_RULES  # noqa: E402

RULES.extend(PROJECT_RULES)
