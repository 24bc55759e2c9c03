"""Mesh/sharding consistency analysis (jaxlint v3).

GSPMD turns sharding into an annotation problem — which means a typo in
an annotation is a *silent* wrong placement: an axis name that no mesh
declares simply replicates the tensor (or inserts a reshard collective)
instead of failing. These rules close that gap statically.

:class:`ShardingIndex` symbolically evaluates the axis-name universe of
one lint run:

- **axis-field defaults** — ``SpecLayout``-style frozen dataclasses
  whose ``*_axis: str = "name"`` fields both declare the canonical axis
  names and give ``self.tp_axis`` / ``spec.tp_axis`` attribute
  references a resolvable value;
- **mesh constructions** — ``jax.sharding.Mesh(devs, ("data", "tp"))``
  axis tuples (positional or ``axis_names=``), including entries spelled
  through axis fields (``Mesh(arr, (spec.tp_axis,))``), plus
  ``axes = {"data": n}`` dict-literal bindings feeding a Mesh;
- **axis parameters** — a function parameter named ``axis`` /
  ``axis_name`` / ``*_axis`` with a string default *parameterizes* the
  axis name, so its default is a declaration too.

Consumption sites — ``PartitionSpec`` entries, collective ``axis_name``s
(resolved through parameter defaults and local constant bindings),
``shard_map`` spec tuples, jit sharding kwargs, ``ModelLayout.fit``
fallback call sites — are then checked against that universe. Everything
is stdlib ``ast``; jax is never imported.
"""

from __future__ import annotations

import ast

from bigdl_tpu.lint.callgraph import JIT_CALLERS, scope_walk
from bigdl_tpu.lint.project import ProjectRule

PARTITION_SPEC_CTORS = frozenset({
    "jax.sharding.PartitionSpec",
})

MESH_CTORS = frozenset({
    "jax.sharding.Mesh",
})

SHARD_MAP_FNS = frozenset({
    "jax.shard_map", "shard_map",
})

# canonical name -> positional index of the axis-name argument
COLLECTIVES = {
    "jax.lax.psum": 1, "jax.lax.pmean": 1, "jax.lax.pmax": 1,
    "jax.lax.pmin": 1, "jax.lax.psum_scatter": 1,
    "jax.lax.all_gather": 1, "jax.lax.all_to_all": 1,
    "jax.lax.ppermute": 1, "jax.lax.pshuffle": 1,
    "jax.lax.axis_index": 0, "jax.lax.axis_size": 0,
}


def _is_axis_param(name):
    return name in ("axis", "axis_name") or name.endswith("_axis")


def _param_string_defaults(fn_node):
    """param name -> string default, for a def/lambda node."""
    args = fn_node.args
    out = {}
    pos = list(args.posonlyargs) + list(args.args) \
        if not isinstance(fn_node, ast.Lambda) \
        else list(args.posonlyargs) + list(args.args)
    for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
        if isinstance(d, ast.Constant) and isinstance(d.value, str):
            out[a.arg] = d.value
    for a, d in zip(args.kwonlyargs, args.kw_defaults):
        if isinstance(d, ast.Constant) and isinstance(d.value, str):
            out[a.arg] = d.value
    return out


def _scope_string_env(scope_node):
    """name -> string constant for simple local bindings of a scope
    (``ax = "data"``), plus the scope's own parameter defaults. Names
    rebound to anything non-constant are dropped (conservative)."""
    env = {}
    if isinstance(scope_node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
        env.update(_param_string_defaults(scope_node))
    poisoned = set()
    for stmt in scope_walk(scope_node):
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            name = stmt.targets[0].id
            if isinstance(stmt.value, ast.Constant) \
                    and isinstance(stmt.value.value, str):
                env[name] = stmt.value.value
            else:
                poisoned.add(name)
    for name in poisoned:
        env.pop(name, None)
    return env


class ShardingIndex:
    """The declared-axis universe of one lint run, with symbolic
    evaluation of axis-field attribute references."""

    def __init__(self, project):
        self.project = project
        self.declared = {}     # axis name -> list[(relpath, lineno)]
        self.axis_fields = {}  # field name ("tp_axis") -> default string
        for mctx in project.modules:
            self._collect_module(mctx)

    # ----------------------------------------------------- declarations --
    def _declare(self, name, mctx, node):
        self.declared.setdefault(name, []).append(
            (mctx.relpath, getattr(node, "lineno", 1)))

    def _collect_module(self, mctx):
        idx = mctx.index
        for node in ast.walk(mctx.tree):
            if isinstance(node, ast.ClassDef):
                for stmt in node.body:
                    if isinstance(stmt, ast.AnnAssign) \
                            and isinstance(stmt.target, ast.Name) \
                            and stmt.target.id.endswith("_axis") \
                            and isinstance(stmt.value, ast.Constant) \
                            and isinstance(stmt.value.value, str):
                        self.axis_fields[stmt.target.id] = stmt.value.value
                        self._declare(stmt.value.value, mctx, stmt)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Lambda)):
                for pname, default in \
                        _param_string_defaults(node).items():
                    if _is_axis_param(pname):
                        self._declare(default, mctx, node)
            elif isinstance(node, ast.Call) \
                    and idx.resolve(node.func) in MESH_CTORS:
                self._collect_mesh(node, mctx)
            elif isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id in ("axes", "axis_names") \
                    and isinstance(node.value, ast.Dict):
                for key in node.value.keys:
                    if isinstance(key, ast.Constant) \
                            and isinstance(key.value, str):
                        self._declare(key.value, mctx, node)

    def _collect_mesh(self, call, mctx):
        names_expr = None
        if len(call.args) >= 2:
            names_expr = call.args[1]
        for kw in call.keywords:
            if kw.arg == "axis_names":
                names_expr = kw.value
        if names_expr is None:
            return
        elts = names_expr.elts \
            if isinstance(names_expr, (ast.Tuple, ast.List)) else [names_expr]
        for e in elts:
            value = self.axis_value(e)
            if value is not None:
                self._declare(value, mctx, call)

    # ------------------------------------------------------- resolution --
    def axis_value(self, expr, env=None):
        """Best-effort string value of an axis expression: a constant,
        an axis-field attribute (``spec.tp_axis``), or a name bound to a
        string in ``env``. None when unresolvable."""
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return expr.value
        if isinstance(expr, ast.Attribute) \
                and expr.attr in self.axis_fields:
            return self.axis_fields[expr.attr]
        if isinstance(expr, ast.Name) and env is not None:
            return env.get(expr.id)
        return None

    def is_declared(self, name):
        return name in self.declared


def sharding_index(project):
    """Memoized per-run :class:`ShardingIndex`."""
    return project.analysis("sharding-index", ShardingIndex)


def _iter_scope_calls(mctx):
    """(scope env-lazy, call node) pairs for every call in the module,
    with the enclosing scope known — env is built once per scope on
    first use."""
    idx = mctx.index
    for scope_node, scope_info in idx._iter_scopes():
        env = None
        for node in scope_walk(scope_node):
            if not isinstance(node, ast.Call):
                continue
            if env is None:
                env = _scope_string_env(scope_node)
            yield scope_node, scope_info, env, node


# --------------------------------------------------------------------------
class SpecAxisNotInMesh(ProjectRule):
    """A string axis name in a PartitionSpec that no mesh declares."""

    name = "spec-axis-not-in-mesh"
    summary = ("a ``PartitionSpec``/``P(...)`` entry names an axis that "
               "no mesh construction, SpecLayout axis field, or axis "
               "parameter in the linted tree declares — GSPMD silently "
               "replicates that dimension instead of sharding it")

    def check(self, project):
        shx = sharding_index(project)
        for mctx in project.modules:
            idx = mctx.index
            for _scope, _info, env, call in _iter_scope_calls(mctx):
                if idx.resolve(call.func) not in PARTITION_SPEC_CTORS:
                    continue
                for arg in call.args:
                    entries = arg.elts \
                        if isinstance(arg, ast.Tuple) else [arg]
                    for e in entries:
                        value = shx.axis_value(e, env)
                        if value is not None \
                                and not shx.is_declared(value):
                            yield self.finding(
                                mctx, e if hasattr(e, "lineno") else call,
                                f"PartitionSpec axis {value!r} is not "
                                f"declared by any mesh or axis field in "
                                f"this tree (declared: "
                                f"{sorted(shx.declared) or 'none'}); a "
                                f"typo here silently replicates the "
                                f"dimension")


class CollectiveAxisUndeclared(ProjectRule):
    """psum/all_gather/... over an axis name nothing declares."""

    name = "collective-axis-undeclared"
    summary = ("``lax.psum``/``all_gather``/``axis_index``/... names a "
               "mapped axis that no mesh, SpecLayout field, or axis "
               "parameter declares — the collective can only fail at "
               "trace time on the device, or bind to the wrong axis")

    def check(self, project):
        shx = sharding_index(project)
        for mctx in project.modules:
            idx = mctx.index
            for _scope, _info, env, call in _iter_scope_calls(mctx):
                r = idx.resolve(call.func)
                if r not in COLLECTIVES:
                    continue
                axis_expr = None
                for kw in call.keywords:
                    if kw.arg == "axis_name":
                        axis_expr = kw.value
                if axis_expr is None:
                    pos = COLLECTIVES[r]
                    if len(call.args) > pos:
                        axis_expr = call.args[pos]
                if axis_expr is None:
                    continue
                entries = axis_expr.elts \
                    if isinstance(axis_expr, (ast.Tuple, ast.List)) \
                    else [axis_expr]
                for e in entries:
                    value = shx.axis_value(e, env)
                    if value is not None and not shx.is_declared(value):
                        yield self.finding(
                            mctx, call,
                            f"{r.split('.')[-1]}() reduces over axis "
                            f"{value!r}, which no mesh or axis "
                            f"declaration in this tree provides "
                            f"(declared: {sorted(shx.declared) or 'none'})")


class ShardMapSpecMismatch(ProjectRule):
    """shard_map in_specs tuple length vs the wrapped callable."""

    name = "shardmap-spec-mismatch"
    summary = ("a literal ``shard_map(..., in_specs=(...))`` tuple whose "
               "length cannot match the wrapped function's positional "
               "signature — the call fails only when first dispatched, "
               "far from the spec that is wrong")

    def check(self, project):
        for mctx in project.modules:
            idx = mctx.index
            for _scope, scope_info, env, call in _iter_scope_calls(mctx):
                if idx.resolve(call.func) not in SHARD_MAP_FNS \
                        or not call.args:
                    continue
                specs_expr = None
                for kw in call.keywords:
                    if kw.arg == "in_specs":
                        specs_expr = kw.value
                if not isinstance(specs_expr, (ast.Tuple, ast.List)):
                    continue  # prefix/pytree specs: not statically sized
                n_specs = len(specs_expr.elts)
                counted = self._target_arity(call.args[0], idx,
                                             scope_info)
                if counted is None:
                    continue
                required, accepted, label = counted
                if not required <= n_specs <= accepted:
                    want = (f"{required}" if required == accepted
                            else f"{required}..{accepted}")
                    yield self.finding(
                        mctx, call,
                        f"shard_map in_specs has {n_specs} spec(s) but "
                        f"{label} takes {want} positional argument(s)")

    @staticmethod
    def _target_arity(fn_expr, idx, scope_info):
        """(required, accepted, label) positional-arg counts of the
        mapped callable, following ``functools.partial`` and lambdas.
        None when the target can't be resolved statically."""
        bound = 0
        target = None
        if isinstance(fn_expr, ast.Call):
            target = idx._partial_target(fn_expr, scope_info)
            if target is not None:
                bound = len(fn_expr.args) - 1
        elif isinstance(fn_expr, ast.Lambda):
            target = idx.by_node.get(id(fn_expr))
        elif isinstance(fn_expr, ast.Name):
            target = idx.lookup(fn_expr.id, scope_info)
        if target is None:
            return None
        node = target.node
        args = node.args
        if args.vararg is not None:
            return None
        pos = len(args.posonlyargs) + len(args.args)
        accepted = pos - bound
        required = accepted - len(args.defaults)
        if accepted < 0 or required < 0:
            return None
        return max(required, 0), accepted, f"{target.name}()"


class JitMissingOutShardings(ProjectRule):
    """jit with sharded inputs but unconstrained outputs."""

    name = "jit-missing-out-shardings"
    summary = ("``jax.jit(..., in_shardings=...)`` without "
               "``out_shardings`` leaves output placement to propagation "
               "— donated-buffer reuse and layout stability silently "
               "depend on what XLA happens to infer")

    def check(self, project):
        for mctx in project.modules:
            idx = mctx.index
            for _scope, _info, _env, call in _iter_scope_calls(mctx):
                if idx.resolve(call.func) not in JIT_CALLERS:
                    continue
                kws = {kw.arg for kw in call.keywords}
                if "in_shardings" in kws and "out_shardings" not in kws:
                    yield self.finding(
                        mctx, call,
                        "jit call pins in_shardings but not "
                        "out_shardings; pass out_shardings so donated "
                        "outputs keep their placement instead of "
                        "depending on propagation")


class SilentReplicateFallback(ProjectRule):
    """ModelLayout.fit()'s indivisible-dimension fallback used without
    the explicit marker."""

    name = "silent-replicate"
    summary = ("``ModelLayout.fit()``/``.sharding(spec, shape)`` fits a "
               "spec to a shape without stating ``allow_replicate=`` — "
               "an indivisible dimension would silently replicate (the "
               "exact failure ``validate_heads`` exists to prevent); "
               "pass ``allow_replicate=False`` to make it an error, or "
               "``=True`` to accept the fallback knowingly")

    LAYOUT_NAMES = frozenset({"layout", "_layout", "lay"})
    METHODS = frozenset({"fit", "sharding"})

    def check(self, project):
        for mctx in project.modules:
            for _scope, _info, _env, call in _iter_scope_calls(mctx):
                func = call.func
                if not isinstance(func, ast.Attribute) \
                        or func.attr not in self.METHODS:
                    continue
                recv = func.value
                tail = recv.attr if isinstance(recv, ast.Attribute) \
                    else recv.id if isinstance(recv, ast.Name) else None
                if tail == "self":
                    continue  # the layout's own helpers
                if tail not in self.LAYOUT_NAMES:
                    continue
                kws = {kw.arg for kw in call.keywords}
                has_shape = len(call.args) >= 2 or "shape" in kws
                if not has_shape:
                    continue  # no shape, no fit fallback engaged
                if "allow_replicate" in kws:
                    continue
                yield self.finding(
                    mctx, call,
                    f".{func.attr}(spec, shape) engages the indivisible-"
                    f"dimension replicate fallback without the explicit "
                    f"marker; pass allow_replicate=False (validated "
                    f"shapes) or allow_replicate=True (fallback "
                    f"accepted)")


SHARDING_RULES = (SpecAxisNotInMesh(), CollectiveAxisUndeclared(),
                  ShardMapSpecMismatch(), JitMissingOutShardings(),
                  SilentReplicateFallback())
