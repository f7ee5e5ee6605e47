"""Shared AST analysis helpers for graftlint.

Everything here is *static*: no imports of the linted code, no execution.
Three capabilities the rules lean on:

* **alias resolution** — map local names back to canonical dotted paths
  (``import jax.numpy as jnp`` makes ``jnp.asarray`` resolve to
  ``jax.numpy.asarray``; ``from functools import partial`` makes
  ``partial`` resolve to ``functools.partial``), so rules match semantics
  instead of spellings;
* **parent links + enclosure queries** — ``ast`` has no parent pointers;
  :func:`add_parents` threads them so rules can ask "am I inside a host
  loop?" / "what function owns this node?";
* **traced-function closure** — the set of function nodes whose bodies
  execute under a JAX trace (jit/pjit/shard_map/lax control flow/pallas),
  computed as a worklist closure over decorators, transform call sites,
  lexical nesting, and the same-file call graph.  This is what lets the
  hot-path rules fire only where a host sync actually poisons a compiled
  program.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple, Union

FunctionNode = Union[ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda]

_FUNC_TYPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
_SCOPE_TYPES = _FUNC_TYPES + (ast.ClassDef,)

#: dotted names whose call-or-decorator makes the wrapped function traced.
TRACING_TRANSFORMS = {
    "jax.jit", "jax.pjit", "jax.experimental.pjit.pjit",
    "jax.vmap", "jax.pmap", "jax.grad", "jax.value_and_grad",
    "jax.jacfwd", "jax.jacrev", "jax.hessian", "jax.linearize",
    "jax.checkpoint", "jax.remat", "jax.custom_jvp", "jax.custom_vjp",
    "jax.shard_map", "jax.experimental.shard_map.shard_map",
    "jax.experimental.pallas.pallas_call",
}

#: callables whose *function-valued arguments* run under the caller's trace
#: (position indices of the function args; None = every argument).
HOF_TRANSFORMS: Dict[str, Optional[Tuple[int, ...]]] = {
    "jax.lax.scan": (0,),
    "jax.lax.fori_loop": (2,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.cond": (1, 2),
    "jax.lax.switch": None,
    "jax.lax.map": (0,),
    "jax.lax.associative_scan": (0,),
}


def parse_file(path: str) -> Tuple[ast.Module, str]:
    with open(path, "r", encoding="utf-8") as f:
        source = f.read()
    tree = ast.parse(source, filename=path)
    add_parents(tree)
    return tree, source


def add_parents(tree: ast.AST) -> None:
    """Thread ``node.parent`` through the whole tree (root's parent None)."""
    tree.parent = None  # type: ignore[attr-defined]
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child.parent = node  # type: ignore[attr-defined]


def ancestors(node: ast.AST) -> Iterator[ast.AST]:
    cur = getattr(node, "parent", None)
    while cur is not None:
        yield cur
        cur = getattr(cur, "parent", None)


def enclosing_function(node: ast.AST) -> Optional[FunctionNode]:
    for anc in ancestors(node):
        if isinstance(anc, _FUNC_TYPES):
            return anc
    return None


def enclosing_class(node: ast.AST) -> Optional[ast.ClassDef]:
    for anc in ancestors(node):
        if isinstance(anc, ast.ClassDef):
            return anc
    return None


def in_host_loop(node: ast.AST) -> Optional[ast.AST]:
    """The nearest ``for``/``while`` ancestor within the same function
    scope (the walk stops at def/lambda boundaries: a loop around a *def*
    doesn't put the def's body in that loop at runtime).  Loop iterables /
    while tests themselves don't count as "inside"."""
    prev = node
    for anc in ancestors(node):
        if isinstance(anc, _FUNC_TYPES):
            return None
        if isinstance(anc, (ast.For, ast.While)):
            # only the *body/orelse* executes per-iteration
            in_body = any(prev in getattr(anc, part, [])
                          for part in ("body", "orelse"))
            if in_body:
                return anc
        prev = anc
    return None


def qualname(node: ast.AST) -> str:
    """Dotted path of the enclosing defs/classes, e.g.
    ``PSService._dispatch_loop.body`` — used for baseline matching (stable
    under line drift) and finding display."""
    parts: List[str] = []
    target: Optional[ast.AST] = node
    if not isinstance(node, _SCOPE_TYPES):
        target = None
        for anc in ancestors(node):
            if isinstance(anc, _SCOPE_TYPES):
                target = anc
                break
    cur = target
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            parts.append(cur.name)
        elif isinstance(cur, ast.Lambda):
            parts.append("<lambda>")
        cur = next((a for a in ancestors(cur)
                    if isinstance(a, _SCOPE_TYPES)), None)
    return ".".join(reversed(parts)) or "<module>"


# ---------------------------------------------------------------------------
# Import-alias resolution
# ---------------------------------------------------------------------------
def collect_aliases(tree: ast.Module) -> Dict[str, str]:
    """Local name -> canonical dotted prefix, from import statements.

    ``import jax.numpy as jnp``            -> {"jnp": "jax.numpy"}
    ``import numpy as np``                 -> {"np": "numpy"}
    ``from jax import jit``                -> {"jit": "jax.jit"}
    ``from functools import partial as P`` -> {"P": "functools.partial"}
    ``import threading``                   -> {"threading": "threading"}
    """
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                local = a.asname or a.name.split(".")[0]
                aliases[local] = a.name if a.asname else a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.level == 0:
            for a in node.names:
                if a.name == "*":
                    continue
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def resolve_name(node: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    """Canonical dotted name of a Name/Attribute chain, through aliases.
    ``jnp.asarray`` -> ``jax.numpy.asarray``; non-chains return None."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if not isinstance(cur, ast.Name):
        return None
    head = aliases.get(cur.id, cur.id)
    return ".".join([head] + list(reversed(parts)))


def _call_resolves_to(node: ast.expr, aliases: Dict[str, str],
                      names: Set[str]) -> bool:
    """True if the expression is (a call of / a reference to) one of
    ``names``, unwrapping ``functools.partial(target, ...)``."""
    if isinstance(node, ast.Call):
        fn = resolve_name(node.func, aliases)
        if fn in names:
            return True
        if fn == "functools.partial" and node.args:
            return _call_resolves_to(node.args[0], aliases, names)
        return False
    return resolve_name(node, aliases) in names


# ---------------------------------------------------------------------------
# Traced-function closure
# ---------------------------------------------------------------------------
def _local_functions(tree: ast.Module) -> Dict[str, List[FunctionNode]]:
    """name -> function nodes, for same-file call resolution.  Methods are
    additionally keyed ``ClassName.name`` so ``self.m()`` can resolve."""
    table: Dict[str, List[FunctionNode]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            table.setdefault(node.name, []).append(node)
            cls = enclosing_class(node)
            if cls is not None:
                table.setdefault(f"{cls.name}.{node.name}", []).append(node)
    return table


def _returned_functions(fn: FunctionNode) -> List[FunctionNode]:
    """Nested defs/lambdas a builder function returns — the repo's
    dominant pattern is ``def build_x_step(...): def step(...): ...;
    return jax.jit(step)`` / ``return step``; the returned body is what
    actually runs under the caller's trace."""
    if isinstance(fn, ast.Lambda):
        return []
    nested = {n.name: n for n in ast.walk(fn)
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
              and n is not fn and enclosing_function(n) is fn}
    out: List[FunctionNode] = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Return) or node.value is None:
            continue
        if enclosing_function(node) is not fn:
            continue
        val = node.value
        if isinstance(val, ast.Call):       # return jax.jit(step, ...)
            for a in val.args:
                if isinstance(a, ast.Name) and a.id in nested:
                    out.append(nested[a.id])
                elif isinstance(a, ast.Lambda):
                    out.append(a)
        elif isinstance(val, ast.Name) and val.id in nested:
            out.append(nested[val.id])
        elif isinstance(val, ast.Lambda):
            out.append(val)
    return out


def _immediate_scope(node: ast.AST) -> Optional[ast.AST]:
    for anc in ancestors(node):
        if isinstance(anc, _SCOPE_TYPES):
            return anc
    return None


def _assigns_name(fn: FunctionNode, name: str) -> bool:
    """Does ``fn`` bind ``name`` through a parameter or assignment-like
    statement (excluding nested defs)?  Used for shadow detection:
    ``_, predict = get_objective(...)`` means a later ``jit(predict)``
    does NOT refer to a module-level/method ``predict``."""
    args = fn.args
    for a in (list(args.args) + list(args.posonlyargs)
              + list(args.kwonlyargs)
              + ([args.vararg] if args.vararg else [])
              + ([args.kwarg] if args.kwarg else [])):
        if a.arg == name:
            return True

    def targets(t: ast.expr) -> Iterator[str]:
        if isinstance(t, ast.Name):
            yield t.id
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                yield from targets(e)
        elif isinstance(t, ast.Starred):
            yield from targets(t.value)

    for sub in ast.walk(fn):
        if sub is not fn and isinstance(sub, _FUNC_TYPES):
            continue    # ast.walk still descends, accept the noise
        if enclosing_function(sub) is not fn:
            continue
        if isinstance(sub, ast.Assign):
            for t in sub.targets:
                if name in targets(t):
                    return True
        elif isinstance(sub, (ast.AugAssign, ast.AnnAssign,
                              ast.NamedExpr)):
            if name in targets(sub.target):
                return True
        elif isinstance(sub, ast.For):
            if name in targets(sub.target):
                return True
        elif isinstance(sub, ast.With):
            for item in sub.items:
                if item.optional_vars is not None and \
                        name in targets(item.optional_vars):
                    return True
    return False


def _visible_functions(name: str, site: ast.AST,
                       local: Dict[str, List[FunctionNode]]
                       ) -> List[FunctionNode]:
    """The defs a bare-name reference at ``site`` can actually mean,
    honoring lexical scoping: innermost visible defs win, a non-def
    binding shadows everything outer, and class-scoped methods are never
    reachable by bare name from inside a method body."""
    cands = local.get(name, [])
    if not cands:
        return []
    chain: List[Optional[ast.AST]] = []
    fn = enclosing_function(site)
    while fn is not None:
        chain.append(fn)
        fn = enclosing_function(fn)
    chain.append(None)      # module scope
    for scope in chain:
        here = [c for c in cands
                if enclosing_function(c) is scope
                and not isinstance(_immediate_scope(c), ast.ClassDef)]
        if here:
            return here
        if scope is not None and _assigns_name(scope, name):
            return []       # shadowed by a local binding
    return []


def _funcs_named_in(node: ast.expr,
                    local: Dict[str, List[FunctionNode]],
                    site: Optional[ast.AST]) -> List[FunctionNode]:
    """Function nodes an argument expression may refer to: a bare name of
    a visible def, an inline lambda, a partial() around either, or the
    step fn returned by a builder call (``jit(make_step(...))``)."""
    site = site if site is not None else node
    if isinstance(node, ast.Lambda):
        return [node]
    if isinstance(node, ast.Name):
        return _visible_functions(node.id, site, local)
    if isinstance(node, ast.Call):        # partial(f, ...) / jit(f)(...)
        out: List[FunctionNode] = []
        if isinstance(node.func, ast.Name):     # builder(...) -> step
            for builder in _visible_functions(node.func.id, site, local):
                out.extend(_returned_functions(builder))
        for a in node.args:
            out.extend(_funcs_named_in(a, local, site))
        return out
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        # self.method passed as a callback
        cls = enclosing_class(node)
        if cls is not None:
            return local.get(f"{cls.name}.{node.attr}", [])
        return []
    return []


def traced_functions(tree: ast.Module,
                     aliases: Dict[str, str]) -> Set[FunctionNode]:
    """Fixed point of "this function body runs under a JAX trace".

    Seeds: decorated with / passed into a tracing transform, or passed as
    a body to a lax control-flow HOF.  Closure: lexical nesting (a def
    inside a traced def executes at trace time) and same-file calls (a
    traced body calling helper ``g``/``self.m`` drags the callee in).
    """
    local = _local_functions(tree)
    traced: Set[FunctionNode] = set()

    def mark(fn: FunctionNode) -> None:
        traced.add(fn)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _call_resolves_to(dec, aliases, TRACING_TRANSFORMS):
                    mark(node)
        elif isinstance(node, ast.Call):
            fn_name = resolve_name(node.func, aliases)
            if fn_name in TRACING_TRANSFORMS or (
                    fn_name == "functools.partial" and node.args and
                    _call_resolves_to(node.args[0], aliases,
                                      TRACING_TRANSFORMS)):
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    for f in _funcs_named_in(arg, local, node):
                        mark(f)
            elif fn_name in HOF_TRANSFORMS:
                positions = HOF_TRANSFORMS[fn_name]
                args = (node.args if positions is None else
                        [node.args[i] for i in positions
                         if i < len(node.args)])
                for arg in args:
                    for f in _funcs_named_in(arg, local, node):
                        mark(f)

    # closure over lexical nesting + same-file calls
    changed = True
    while changed:
        changed = False
        for fn in list(traced):
            for node in ast.walk(fn):
                if node is fn:
                    continue
                if isinstance(node, _FUNC_TYPES) and node not in traced:
                    traced.add(node)
                    changed = True
                if isinstance(node, ast.Call):
                    callees: List[FunctionNode] = []
                    if isinstance(node.func, ast.Name):
                        callees = _visible_functions(node.func.id, node,
                                                     local)
                    elif isinstance(node.func, ast.Attribute) and \
                            isinstance(node.func.value, ast.Name) and \
                            node.func.value.id == "self":
                        cls = enclosing_class(fn)
                        key = (f"{cls.name}.{node.func.attr}"
                               if cls is not None else node.func.attr)
                        callees = local.get(key, [])
                    for c in callees:
                        if c not in traced:
                            traced.add(c)
                            changed = True
    return traced


def is_traced_context(node: ast.AST, traced: Set[FunctionNode]) -> bool:
    fn = enclosing_function(node)
    while fn is not None:
        if fn in traced:
            return True
        fn = enclosing_function(fn)
    return False
