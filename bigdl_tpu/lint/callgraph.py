"""Module AST index: import aliases, functions, trace entries, call graph.

The rules need one question answered precisely: *can this statement
execute under a jax trace?* A function is a trace **entry** when it is
decorated with (or passed to) one of the tracing combinators — ``jax.jit``,
``pjit``, ``pmap``, ``shard_map``, ``lax.scan``/``while_loop``/``cond``,
``vmap``/``grad``/``checkpoint`` — and **traced** when it is an entry, is
lexically nested inside a traced function, or is reachable from one
through the intra-module call graph (bare-name calls resolved lexically,
``self.method()`` calls resolved against the enclosing class).

Everything here is stdlib ``ast`` — the linter never imports jax, so it
runs anywhere the source does.
"""

from __future__ import annotations

import ast

# canonical dotted names whose function-valued arguments are traced
TRACING_CALLERS = frozenset({
    "jax.jit", "jax.pjit", "jax.pmap", "jax.vmap", "jax.grad",
    "jax.value_and_grad", "jax.vjp", "jax.jvp", "jax.linearize",
    "jax.checkpoint", "jax.remat", "jax.experimental.pjit.pjit",
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
    "jax.shard_map",
    # pallas kernel bodies trace like any other staged function: the
    # rules (span-in-jit, host-sync, np-vs-jnp) apply to them verbatim
    "jax.experimental.pallas.pallas_call",
})

# bare names accepted even when import resolution can't see their origin
# (e.g. a shim re-export the alias table doesn't know about)
TRACING_BARE = frozenset({"jit", "pjit", "pmap", "shard_map"})

JIT_CALLERS = frozenset({
    "jax.jit", "jax.pjit", "jax.pmap", "jax.experimental.pjit.pjit",
    "jit", "pjit", "pmap",
})


def dotted_parts(expr):
    """``a.b.c`` -> ["a", "b", "c"]; None for anything not a plain chain."""
    parts = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
        return parts[::-1]
    return None


class FunctionInfo:
    """One ``def``/``lambda`` with its lexical context and call edges."""

    __slots__ = ("node", "name", "qualname", "parent", "class_name",
                 "children", "calls", "self_calls", "traced", "entry_reason",
                 "arg_names")

    def __init__(self, node, name, qualname, parent, class_name):
        self.node = node
        self.name = name
        self.qualname = qualname
        self.parent = parent          # FunctionInfo | None (module/class top)
        self.class_name = class_name  # nearest enclosing class, if any
        self.children = {}            # name -> [FunctionInfo]
        self.calls = set()            # bare names called in this scope
        self.self_calls = set()       # self.<name>() calls
        self.traced = False
        self.entry_reason = None
        if isinstance(node, ast.Lambda):
            self.arg_names = [a.arg for a in node.args.args]
        else:
            self.arg_names = [a.arg for a in (node.args.posonlyargs
                                              + node.args.args)]

    def __repr__(self):
        return f"FunctionInfo({self.qualname})"


def scope_walk(fn_node):
    """Yield the nodes of a function's (or module's) own scope, NOT
    descending into nested ``def``/``lambda`` scopes (those are separate
    FunctionInfos). Class bodies are transparent: their statements run in
    the enclosing scope."""
    if isinstance(fn_node, ast.Lambda):
        roots = [fn_node.body]
    else:
        roots = list(fn_node.body)
    stack = list(roots)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue  # separate scope
        stack.extend(ast.iter_child_nodes(node))


class ModuleIndex:
    """Aliases + functions + trace reachability for one parsed module."""

    def __init__(self, tree):
        self.tree = tree
        self.aliases = {}             # local name -> canonical dotted prefix
        self.functions = []           # every FunctionInfo, any nesting
        self.by_node = {}             # id(ast node) -> FunctionInfo
        self.module_defs = {}         # top-level name -> [FunctionInfo]
        self.class_methods = {}       # class name -> {method -> [FunctionInfo]}
        self._fn_aliases = {}         # id(scope) -> {var name -> FunctionInfo}
        self._collect_imports(tree)
        self._collect_functions(tree)
        self._detect_entries()
        self._propagate()

    # ------------------------------------------------------------ imports --
    def _collect_imports(self, tree):
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        self.aliases[a.asname] = a.name
                    else:
                        root = a.name.split(".")[0]
                        self.aliases.setdefault(root, root)
            elif isinstance(node, ast.ImportFrom) and node.module:
                prefix = ("." * node.level) + node.module
                for a in node.names:
                    self.aliases[a.asname or a.name] = f"{prefix}.{a.name}"

    def resolve(self, expr):
        """Canonical dotted name of an attribute chain, through the import
        alias table (``np.asarray`` -> ``numpy.asarray``)."""
        parts = dotted_parts(expr)
        if not parts:
            return None
        root = self.aliases.get(parts[0], parts[0])
        return ".".join([root] + parts[1:])

    # ---------------------------------------------------------- functions --
    def _collect_functions(self, tree):
        def visit(node, parent_fn, class_name, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    info = self._add_fn(child, child.name, parent_fn,
                                        class_name, prefix)
                    visit(child, info, class_name, info.qualname + ".")
                elif isinstance(child, ast.Lambda):
                    info = self._add_fn(child, "<lambda>", parent_fn,
                                        class_name, prefix)
                    visit(child, info, class_name, info.qualname + ".")
                elif isinstance(child, ast.ClassDef):
                    visit(child, parent_fn, child.name,
                          f"{prefix}{child.name}." if prefix else
                          f"{child.name}.")
                else:
                    visit(child, parent_fn, class_name, prefix)

        visit(tree, None, None, "")
        for info in self.functions:
            self._collect_calls(info)

    def _add_fn(self, node, name, parent_fn, class_name, prefix):
        info = FunctionInfo(node, name, f"{prefix}{name}", parent_fn,
                            class_name)
        self.functions.append(info)
        self.by_node[id(node)] = info
        if parent_fn is None:
            self.module_defs.setdefault(name, []).append(info)
            if class_name is not None:
                self.class_methods.setdefault(class_name, {}) \
                    .setdefault(name, []).append(info)
        else:
            parent_fn.children.setdefault(name, []).append(info)
        return info

    def _collect_calls(self, info):
        for node in scope_walk(info.node):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Name):
                info.calls.add(node.func.id)
            elif (isinstance(node.func, ast.Attribute)
                  and isinstance(node.func.value, ast.Name)
                  and node.func.value.id == "self"):
                info.self_calls.add(node.func.attr)

    # ------------------------------------------------------------ lookups --
    def lookup(self, name, scope):
        """Lexical lookup of a function (or a jit/shard_map-wrapped alias
        of one) named ``name`` from inside ``scope`` (FunctionInfo|None)."""
        s = scope
        while s is not None:
            if name in s.children:
                return s.children[name][0]
            alias = self._fn_aliases.get(id(s), {}).get(name)
            if alias is not None:
                return alias
            s = s.parent
        if name in self.module_defs:
            return self.module_defs[name][0]
        return self._fn_aliases.get(None, {}).get(name)

    def owner(self, node):
        """FunctionInfo whose scope lexically contains ``node``'s scope
        registration — used by rules that iterate per-function."""
        return self.by_node.get(id(node))

    # ------------------------------------------------------------ entries --
    def is_tracing_caller(self, call):
        """Canonical name if ``call.func`` is a tracing combinator (unwraps
        ``functools.partial(jax.jit, ...)``), else None."""
        r = self.resolve(call.func)
        if r in TRACING_CALLERS or (r is not None
                                    and r.split(".")[-1] in TRACING_BARE
                                    and "." not in r):
            return r
        if r in ("functools.partial", "partial") and call.args:
            inner = self.resolve(call.args[0])
            if inner in TRACING_CALLERS:
                return inner
        return None

    def _detect_entries(self):
        # 1. decorators
        for info in self.functions:
            node = info.node
            if isinstance(node, ast.Lambda):
                continue
            for dec in node.decorator_list:
                r = self.resolve(dec)
                if r is None and isinstance(dec, ast.Call):
                    r = self.is_tracing_caller(dec)
                if r in TRACING_CALLERS:
                    info.traced = True
                    info.entry_reason = f"@{r}"
        # 2a. ``name = shard_map(f, ...)`` / ``name = jax.jit(f)`` aliases,
        #     registered first so a later ``jax.jit(name)`` in any scope
        #     resolves through them; ``name = functools.partial(f, ...)``
        #     registers the same way — calling the partial calls ``f``,
        #     and the pallas idiom binds kernel statics exactly so
        #     (``kernel = partial(_kernel, ...); pl.pallas_call(kernel)``)
        for scope_node, scope_info in self._iter_scopes():
            for stmt in scope_walk(scope_node):
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name) \
                        and isinstance(stmt.value, ast.Call):
                    wrapped = self._wrapped_function(stmt.value, scope_info) \
                        or self._partial_target(stmt.value, scope_info)
                    if wrapped is not None:
                        self._fn_aliases.setdefault(
                            id(scope_info) if scope_info else None,
                            {})[stmt.targets[0].id] = wrapped
        # 2b. functions/lambdas passed to tracing combinators
        for scope_node, scope_info in self._iter_scopes():
            for stmt in scope_walk(scope_node):
                if isinstance(stmt, ast.Call):
                    self._mark_call_args(stmt, scope_info)

    def _iter_scopes(self):
        """(scope ast node, FunctionInfo|None for module scope) pairs."""
        yield self.tree, None
        for info in self.functions:
            yield info.node, info

    def _wrapped_function(self, call, scope_info):
        """FunctionInfo wrapped by a jit/shard_map call expression."""
        if self.is_tracing_caller(call) is None:
            return None
        for arg in call.args:
            if isinstance(arg, ast.Name):
                fn = self.lookup(arg.id, scope_info)
                if fn is not None:
                    return fn
            elif isinstance(arg, ast.Lambda):
                return self.by_node.get(id(arg))
        return None

    def _partial_target(self, call, scope_info):
        """FunctionInfo behind ``functools.partial(f, ...)``, else None."""
        if not isinstance(call, ast.Call):
            return None
        if self.resolve(call.func) not in ("functools.partial", "partial"):
            return None
        if not call.args:
            return None
        inner = call.args[0]
        if isinstance(inner, ast.Name):
            return self.lookup(inner.id, scope_info)
        if isinstance(inner, ast.Lambda):
            return self.by_node.get(id(inner))
        return None

    def _mark_call_args(self, call, scope_info):
        reason = self.is_tracing_caller(call)
        if reason is None:
            return
        args = list(call.args) + [kw.value for kw in call.keywords]
        for arg in args:
            target = None
            if isinstance(arg, ast.Name):
                target = self.lookup(arg.id, scope_info)
            elif isinstance(arg, ast.Lambda):
                target = self.by_node.get(id(arg))
            elif isinstance(arg, ast.Call):
                # inline ``functools.partial(f, ...)`` argument
                target = self._partial_target(arg, scope_info)
            if target is not None and not target.traced:
                target.traced = True
                target.entry_reason = f"passed to {reason}"

    # -------------------------------------------------------- propagation --
    def _propagate(self):
        changed = True
        while changed:
            changed = False
            for info in self.functions:
                if not info.traced:
                    continue
                for callee in self._callees(info):
                    if not callee.traced:
                        callee.traced = True
                        callee.entry_reason = (f"called from traced "
                                               f"{info.qualname}")
                        changed = True
                # lexically nested defs execute (or are staged) in-trace
                for kids in info.children.values():
                    for kid in kids:
                        if not kid.traced:
                            kid.traced = True
                            kid.entry_reason = (f"defined inside traced "
                                                f"{info.qualname}")
                            changed = True

    def _callees(self, info):
        out = []
        for name in info.calls:
            fn = self.lookup(name, info)
            if fn is not None:
                out.append(fn)
        if info.class_name is not None:
            methods = self.class_methods.get(info.class_name, {})
            for name in info.self_calls:
                out.extend(methods.get(name, []))
        return out

    def traced_functions(self):
        return [f for f in self.functions if f.traced]
