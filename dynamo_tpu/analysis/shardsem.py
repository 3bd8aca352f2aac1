"""Sharding-semantics layer for dynalint: the shard-site inventory the
DL3xx rules share.

ROADMAP item 1 moves serving onto real meshes (TP×PP×DP), and the mesh
code is exactly where Python can't help: a ``shard_map`` body is traced
once per shard, its collectives name mesh axes as *strings*, and its
in/out ``PartitionSpec``\\ s are checked against the wrapped function
only at trace time — on a multi-host pod, often only at deploy time.
The contracts the DL3xx rules enforce:

- a **host sync inside a shard body** serializes every device in the
  mesh, not one (DL301);
- a collective's ``axis_name`` must be among the enclosing shard
  site's **declared axes** (DL302);
- **donating** a buffer whose sharding differs from the jit site's
  declared sharding inserts a resharding copy that silently defeats
  the donation, and donating from inside a shard body frees per-shard
  views the other shards still alias (DL303);
- literal ``in_specs``/``out_specs`` must match the wrapped function's
  **arity** and the declared **axis set** (DL304).

This module builds, once per program pass, the inventory those rules
check against: every ``jax.shard_map``, ``pjit``/sharded-``jit``, and
``with_sharding_constraint`` site inside a function body, with

- the **wrapped callable** resolved to a call-graph qualname where
  possible (nested closures included — the house style wraps a local
  ``def``);
- the declared **manual axis set**: a literal ``axis_names=`` set, the
  complement of a literal ``auto=`` set against a statically-known
  mesh, or *all mesh axes* when neither is given (fully-manual
  shard_map);
- literal ``in_specs``/``out_specs`` parsed to per-argument
  PartitionSpec shapes, resolving ``P(...)`` bound to frame locals and
  module-level constants;
- per-function maps of ``x = with_sharding_constraint(x, P(...))``
  bindings, and jit/pjit sites that combine ``donate_argnums`` with
  literal ``in_shardings`` (the DL303 comparison endpoints).

Anything dynamic — a computed axis tuple, a spec built in a helper, a
mesh only a caller knows — degrades to a **counted miss** (the
``dynamic`` tally surfaced by ``--stats``), never a guessed value: the
jaxsem discipline, because a wrong axis index would make every DL3xx
finding suspect.

The inventory and the body-reachability map are memoized on the
:class:`LintProgram` instance so the four rules share one build.
Cache correctness is free: this file lives in the analysis package,
whose source bytes are folded into the rule-set signature
(``cache._package_hash``).
"""

from __future__ import annotations

import ast
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, FrozenSet, List, Optional, Tuple

from dynamo_tpu.analysis.astutil import dotted_name, walk_in_scope
from dynamo_tpu.analysis.callgraph import (
    SAME_CONTEXT,
    CallGraph,
    FunctionInfo,
    resolve_name,
)
from dynamo_tpu.analysis.jaxsem import _argnums, _resolves_to

# a spec/axis construct the parser could not reduce to literals —
# recorded as a counted miss, never guessed at
DYNAMIC = "<dynamic>"

_SHARD_MAP = ("jax.shard_map",)
_PJIT = ("jax.experimental.pjit.pjit", "jax.pjit")
_JIT = ("jax.jit",)
_CONSTRAINT = (
    "jax.lax.with_sharding_constraint",
    "jax.experimental.pjit.with_sharding_constraint",
)
_PSPEC = (
    "jax.sharding.PartitionSpec",
    "jax.experimental.pjit.PartitionSpec",
)
_MESH = ("jax.sharding.Mesh", "jax.experimental.maps.Mesh")

# collective -> positional index of its axis-name argument
COLLECTIVES: Dict[str, int] = {
    "jax.lax.psum": 1,
    "jax.lax.pmean": 1,
    "jax.lax.pmax": 1,
    "jax.lax.pmin": 1,
    "jax.lax.psum_scatter": 1,
    "jax.lax.all_gather": 1,
    "jax.lax.all_to_all": 1,
    "jax.lax.ppermute": 1,
    "jax.lax.pshuffle": 1,
    "jax.lax.axis_index": 0,
    "jax.lax.pcast": 1,
    "jax.lax.pbroadcast": 1,
    "jax.lax.pvary": 1,
}


def _matches(imports: Dict[str, str], name: str, targets) -> bool:
    return any(_resolves_to(imports, name, t) for t in targets)


def collective_axis_arg(
    imports: Dict[str, str], call: ast.Call
) -> Optional[Tuple[str, Optional[ast.AST]]]:
    """(collective name, axis-argument expression) when ``call`` is a
    recognized mesh collective, else None.  The axis expression is None
    when the call omits it (defaults to the enclosing binder)."""
    name = dotted_name(call.func)
    if name is None:
        return None
    for full, pos in COLLECTIVES.items():
        if _resolves_to(imports, name, full):
            axis: Optional[ast.AST] = None
            if len(call.args) > pos:
                axis = call.args[pos]
            for k in call.keywords:
                if k.arg in ("axis_name", "axis_names", "axis_index_groups"):
                    if k.arg != "axis_index_groups":
                        axis = k.value
            return full.rsplit(".", 1)[-1], axis
    return None


def parse_axis_set(node: Optional[ast.AST]) -> Optional[FrozenSet[str]]:
    """``{"pp"}`` / ``("ep", "tp")`` / ``"tp"`` / ``frozenset({...})``
    literal -> frozenset of axis names; None when dynamic."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return frozenset({node.value})
    if isinstance(node, (ast.Set, ast.Tuple, ast.List)):
        out = set()
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.add(el.value)
            else:
                return None
        return frozenset(out)
    if isinstance(node, ast.Call):
        fn = dotted_name(node.func)
        if fn in ("frozenset", "set", "tuple") and len(node.args) == 1:
            return parse_axis_set(node.args[0])
    return None


# -- PartitionSpec parsing -------------------------------------------------


def _spec_entry(node: ast.AST):
    """One P(...) argument: None | "axis" | ("a", "b") | DYNAMIC."""
    if isinstance(node, ast.Constant):
        if node.value is None or isinstance(node.value, str):
            return node.value
        return DYNAMIC
    if isinstance(node, (ast.Tuple, ast.List)):
        sub = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                sub.append(el.value)
            else:
                return DYNAMIC
        return tuple(sub)
    return DYNAMIC


def parse_partition_spec(
    node: ast.AST, imports: Dict[str, str]
) -> Optional[Tuple]:
    """``P("dp", None, ("ep", "tp"))`` -> parsed entry tuple; None when
    ``node`` is not a recognizable PartitionSpec constructor."""
    if not isinstance(node, ast.Call):
        return None
    name = dotted_name(node.func)
    if name is None or not _matches(imports, name, _PSPEC):
        return None
    return tuple(_spec_entry(a) for a in node.args)


def spec_axes(spec: Optional[Tuple]) -> FrozenSet[str]:
    """Literal axis names a parsed spec mentions (DYNAMIC entries
    contribute nothing — only what we can read gets checked)."""
    out = set()
    for entry in spec or ():
        if isinstance(entry, str) and entry != DYNAMIC:
            out.add(entry)
        elif isinstance(entry, tuple):
            out.update(entry)
    return frozenset(out)


# -- sites -----------------------------------------------------------------


@dataclass
class ShardSite:
    """One shard_map / sharded-jit / with_sharding_constraint site."""

    key: str  # "owner-qualname::<lineno>"
    path: str
    lineno: int
    kind: str  # "shard_map" | "jit-sharded" | "constraint"
    owner: str  # qualname of the function containing the site
    wrapped: Optional[str] = None  # wrapped callable's qualname
    axes: Optional[FrozenSet[str]] = None  # declared manual axes
    all_manual: bool = False  # no axis_names=: every mesh axis is manual
    mesh_axes: Optional[FrozenSet[str]] = None
    # literal tuple forms only; entries are parsed specs or DYNAMIC
    in_specs: Optional[Tuple] = None
    out_specs: Optional[Tuple] = None
    donate: Tuple[int, ...] = ()
    in_shardings: Optional[Tuple] = None
    spec_axes: FrozenSet[str] = frozenset()  # axes the specs mention
    dynamic: int = 0  # constructs that degraded to a counted miss
    node: Optional[ast.AST] = None  # the site call (finding anchor)

    @property
    def label(self) -> str:
        if self.wrapped:
            return self.wrapped.rsplit(".", 1)[-1].rsplit(":", 1)[-1]
        return f"{self.kind}@{self.lineno}"

    def declared_axes(self) -> Optional[FrozenSet[str]]:
        """The axis names collectives inside this site's body may use;
        None when not statically known (fully-manual with an opaque
        mesh, or a dynamic axis_names= value)."""
        if self.axes is not None:
            return self.axes
        if self.all_manual:
            return self.mesh_axes  # all of them — when we know them
        return None


@dataclass
class ShardInventory:
    sites: List[ShardSite] = field(default_factory=list)
    # wrapped-body qualname -> shard_map site (first site wins)
    body_sites: Dict[str, ShardSite] = field(default_factory=dict)
    # fn qualname -> {local name -> constrained spec} from
    # ``x = with_sharding_constraint(x, P(...))`` bindings
    constraints: Dict[str, Dict[str, Tuple]] = field(default_factory=dict)
    # donate+in_shardings jit/pjit sites, by binding
    jit_by_local: Dict[Tuple[str, str], ShardSite] = field(
        default_factory=dict
    )
    jit_by_qualname: Dict[str, ShardSite] = field(default_factory=dict)

    def stats(self) -> Dict[str, int]:
        kinds = {"shard_map": 0, "jit-sharded": 0, "constraint": 0}
        for s in self.sites:
            kinds[s.kind] = kinds.get(s.kind, 0) + 1
        return {
            "shard_map_sites": kinds["shard_map"],
            "jit_sharded_sites": kinds["jit-sharded"],
            "constraint_sites": kinds["constraint"],
            "resolved_bodies": len(self.body_sites),
            "dynamic_misses": sum(s.dynamic for s in self.sites),
        }


# -- build -----------------------------------------------------------------


def _module_consts(tree: ast.Module) -> Dict[str, ast.AST]:
    """Top-level ``NAME = <expr>`` bindings (module constants like the
    pipeline's ``_PP_ONLY_CACHE_SPEC``)."""
    out: Dict[str, ast.AST] = {}
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
            t = stmt.targets[0]
            if isinstance(t, ast.Name):
                out[t.id] = stmt.value
    return out


def _frame_resolver(
    fn: FunctionInfo, consts: Dict[str, ast.AST]
) -> Callable[[str], Optional[ast.AST]]:
    """name -> the expression assigned to it in this frame (last
    assignment wins) or at module top level."""
    local: Dict[str, ast.AST] = {}
    mutated = set()
    for node in walk_in_scope(fn.node):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name):
                # a rebound name is ambiguous — refuse, don't guess
                if t.id in local:
                    mutated.add(t.id)
                local[t.id] = node.value
        elif isinstance(node, ast.AugAssign) and isinstance(
            node.target, ast.Name
        ):
            # `specs += (...)`: the literal we saw is not the value
            # the site receives (llama's conditional scale specs)
            mutated.add(node.target.id)

    def resolve(name: str) -> Optional[ast.AST]:
        if name in mutated:
            return None
        return local.get(name, consts.get(name))

    return resolve


def _deref(
    expr: ast.AST, resolver: Callable[[str], Optional[ast.AST]]
) -> ast.AST:
    """Follow Name bindings a few hops so ``spec = P(...)`` and
    ``mesh = Mesh(...)`` locals resolve to their constructors."""
    for _ in range(4):
        if not isinstance(expr, ast.Name):
            break
        nxt = resolver(expr.id)
        if nxt is None or nxt is expr:
            break
        expr = nxt
    return expr


def _mesh_axes(
    expr: Optional[ast.AST],
    resolver: Callable[[str], Optional[ast.AST]],
    imports: Dict[str, str],
) -> Optional[FrozenSet[str]]:
    """Axis names of a ``Mesh(devices, ("dp", "tp"))`` constructor the
    site's mesh= argument resolves to; None when the mesh is opaque
    (a parameter, a method call — the common case)."""
    if expr is None:
        return None
    expr = _deref(expr, resolver)
    if not isinstance(expr, ast.Call):
        return None
    name = dotted_name(expr.func)
    if name is None or not _matches(imports, name, _MESH):
        return None
    cand: Optional[ast.AST] = None
    if len(expr.args) > 1:
        cand = expr.args[1]
    for k in expr.keywords:
        if k.arg == "axis_names":
            cand = k.value
    return parse_axis_set(cand)


def _specs_field(
    node: Optional[ast.AST],
    resolver: Callable[[str], Optional[ast.AST]],
    imports: Dict[str, str],
) -> Tuple[Optional[Tuple], FrozenSet[str], int]:
    """Parse an ``in_specs=``/``out_specs=`` value.

    Returns ``(literal_tuple, axes_mentioned, dynamic_misses)``:
    ``literal_tuple`` is the per-argument parse (entries: parsed spec
    or DYNAMIC) when the value is a literal Tuple/List — the only form
    whose arity is checkable — else None.  A single bare spec still
    contributes its axes; anything else is a counted miss."""
    if node is None:
        return None, frozenset(), 0
    node = _deref(node, resolver)
    misses = 0
    axes: set = set()
    if isinstance(node, (ast.Tuple, ast.List)):
        entries = []
        for el in node.elts:
            spec = parse_partition_spec(_deref(el, resolver), imports)
            if spec is None:
                entries.append(DYNAMIC)
                misses += 1
            else:
                entries.append(spec)
                axes.update(spec_axes(spec))
                if DYNAMIC in spec:
                    misses += 1
        return tuple(entries), frozenset(axes), misses
    spec = parse_partition_spec(_deref(node, resolver), imports)
    if spec is None:
        return None, frozenset(), 1
    return None, spec_axes(spec), (1 if DYNAMIC in spec else 0)


def _resolve_wrapped(
    graph: CallGraph, fn: FunctionInfo, expr: Optional[ast.AST]
) -> Optional[str]:
    if expr is None or isinstance(expr, ast.Lambda):
        return None
    name = dotted_name(expr)
    if name is None:
        return None
    return resolve_name(graph, fn, name)


def _shard_map_site(
    call: ast.Call,
    fn: FunctionInfo,
    graph: CallGraph,
    resolver,
    imports: Dict[str, str],
) -> ShardSite:
    kw = {k.arg: k.value for k in call.keywords if k.arg}
    dynamic = 0

    wrapped_expr = call.args[0] if call.args else kw.get("f")
    wrapped = _resolve_wrapped(graph, fn, wrapped_expr)
    if wrapped_expr is not None and wrapped is None:
        dynamic += 1

    mesh_axes = _mesh_axes(kw.get("mesh"), resolver, imports)

    axes: Optional[FrozenSet[str]] = None
    all_manual = False
    ax_node = kw.get("axis_names")
    auto_node = kw.get("auto")
    if ax_node is not None and not (
        isinstance(ax_node, ast.Constant) and ax_node.value is None
    ):
        axes = parse_axis_set(ax_node)
        if axes is None:
            dynamic += 1
    elif auto_node is not None:
        auto = parse_axis_set(auto_node)
        if auto is not None and mesh_axes is not None:
            axes = mesh_axes - auto
        else:
            dynamic += 1
    else:
        all_manual = True

    in_specs, in_axes, m_in = _specs_field(
        kw.get("in_specs"), resolver, imports
    )
    out_specs, out_axes, m_out = _specs_field(
        kw.get("out_specs"), resolver, imports
    )
    dynamic += m_in + m_out

    return ShardSite(
        key=f"{fn.qualname}::{call.lineno}",
        path=fn.path,
        lineno=call.lineno,
        node=call,
        kind="shard_map",
        owner=fn.qualname,
        wrapped=wrapped,
        axes=axes,
        all_manual=all_manual,
        mesh_axes=mesh_axes,
        in_specs=in_specs,
        out_specs=out_specs,
        spec_axes=in_axes | out_axes,
        dynamic=dynamic,
    )


def _jit_sharded_site(
    call: ast.Call,
    fn: FunctionInfo,
    graph: CallGraph,
    resolver,
    imports: Dict[str, str],
) -> Optional[ShardSite]:
    """A ``pjit``/``jax.jit`` call that declares ``in_shardings`` (the
    DL303 comparison endpoint); None when it declares no shardings."""
    name = dotted_name(call.func)
    if name is None or not _matches(imports, name, _PJIT + _JIT):
        return None
    kw = {k.arg: k.value for k in call.keywords if k.arg}
    if "in_shardings" not in kw:
        return None
    in_shardings, _, misses = _specs_field(
        kw.get("in_shardings"), resolver, imports
    )
    return ShardSite(
        key=f"{fn.qualname}::{call.lineno}",
        path=fn.path,
        lineno=call.lineno,
        node=call,
        kind="jit-sharded",
        owner=fn.qualname,
        wrapped=_resolve_wrapped(
            graph, fn, call.args[0] if call.args else None
        ),
        donate=_argnums(kw.get("donate_argnums")),
        in_shardings=in_shardings,
        dynamic=misses,
    )


def build_inventory(program) -> ShardInventory:
    inv = ShardInventory()
    graph: CallGraph = program.graph
    consts_by_path: Dict[str, Dict[str, ast.AST]] = {}
    for path, mod in program.modules.items():
        consts_by_path[path] = _module_consts(mod.tree)

    for qn, fn in graph.functions.items():
        imports = graph.imports.get(fn.module, {})
        consts = consts_by_path.get(fn.path, {})
        resolver = _frame_resolver(fn, consts)

        # decorator-form sharded jit (`@pjit(... in_shardings=...)`)
        for deco in getattr(fn.node, "decorator_list", []):
            if isinstance(deco, ast.Call):
                site = _jit_sharded_site(deco, fn, graph, resolver, imports)
                if site is not None:
                    site.wrapped = qn
                    inv.sites.append(site)
                    inv.jit_by_qualname[qn] = site

        for node in walk_in_scope(fn.node):
            if isinstance(node, ast.Assign):
                val = node.value
                if not isinstance(val, ast.Call):
                    continue
                vname = dotted_name(val.func) or ""
                if _matches(imports, vname, _CONSTRAINT):
                    # x = with_sharding_constraint(x, P(...)) binding
                    if len(node.targets) == 1 and isinstance(
                        node.targets[0], ast.Name
                    ) and len(val.args) >= 2:
                        spec = parse_partition_spec(
                            _deref(val.args[1], resolver), imports
                        )
                        if spec is not None:
                            inv.constraints.setdefault(qn, {})[
                                node.targets[0].id
                            ] = spec
                else:
                    site = _jit_sharded_site(
                        val, fn, graph, resolver, imports
                    )
                    if site is not None:
                        inv.sites.append(site)
                        if site.wrapped:
                            inv.jit_by_qualname.setdefault(
                                site.wrapped, site
                            )
                        for t in node.targets:
                            tn = dotted_name(t)
                            if tn and "." not in tn:
                                inv.jit_by_local[(qn, tn)] = site
                continue
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name is None:
                continue
            if _matches(imports, name, _SHARD_MAP):
                site = _shard_map_site(node, fn, graph, resolver, imports)
                inv.sites.append(site)
                if site.wrapped:
                    inv.body_sites.setdefault(site.wrapped, site)
            elif _matches(imports, name, _CONSTRAINT):
                inv.sites.append(
                    ShardSite(
                        key=f"{qn}::{node.lineno}",
                        path=fn.path,
                        lineno=node.lineno,
                        node=node,
                        kind="constraint",
                        owner=qn,
                    )
                )
    return inv


def inventory_of(program) -> ShardInventory:
    """The program's shard-site inventory, built once and memoized on
    the LintProgram instance (the four DL3xx rules share it)."""
    inv = getattr(program, "_shardsem_inventory", None)
    if inv is None:
        inv = build_inventory(program)
        program._shardsem_inventory = inv
    return inv


# -- body reachability -----------------------------------------------------


def in_closure_tree(root: str, qualname: str) -> bool:
    return qualname == root or qualname.startswith(root + ".<locals>.")


def body_reach(program) -> Dict[str, List[Tuple[ShardSite, List[str]]]]:
    """fn qualname -> [(shard site whose body reaches it, call chain
    from the wrapped body root)].  The wrapped function and its nested
    closures are depth 0; ordinary same-context calls extend the
    chain — what executes *per shard, inside the trace*.  Memoized
    alongside the inventory."""
    reach = getattr(program, "_shardsem_reach", None)
    if reach is not None:
        return reach
    inv = inventory_of(program)
    graph: CallGraph = program.graph
    reach = {}
    for root, site in sorted(inv.body_sites.items()):
        seen: Dict[str, List[str]] = {root: [root]}
        work = deque([root])
        # seed the closure tree: nested defs belong to the body frame
        for qn in graph.functions:
            if in_closure_tree(root, qn) and qn not in seen:
                seen[qn] = [root, qn] if qn != root else [root]
                work.append(qn)
        while work:
            cur = work.popleft()
            for e in graph.out_edges(cur):
                if e.kind not in SAME_CONTEXT or e.callee in seen:
                    continue
                if e.callee not in graph.functions:
                    continue
                seen[e.callee] = seen[cur] + [e.callee]
                work.append(e.callee)
        for qn, chain in seen.items():
            reach.setdefault(qn, []).append((site, chain))
    program._shardsem_reach = reach
    return reach
