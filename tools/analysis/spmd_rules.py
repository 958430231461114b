"""SPMD/sharding rules: the partitioning discipline mxlint was blind to.

The hand-written ``shard_map``/collective surface (``parallel/step.py``
grad reduction, ``quantize.py``'s int8 exchange, ``pipeline.py``,
``sequence.py``) is about to multiply under tensor-parallel serving
(ROADMAP item 1) — and SPMD bugs compile *fine* and fail only as silent
numerics or byte blowups at scale: a typo'd axis name surfaces as a deep
JAX error (or worse, a different reduction), an unsound
``out_specs=PartitionSpec()`` replication claim silently serves one
shard's values as "the" result, and a collective issued per Python loop
iteration unrolls into per-layer latency the compiler cannot fuse —
exactly the cost class *EQuARX* (arXiv:2506.17615) shows dominates
sharded decode.  These rules make that discipline mechanical:

``spmd-axis-unknown``       an axis-consuming primitive
                            (``lax.psum``/``pmean``/``all_gather``/
                            ``all_to_all``/``ppermute``/``axis_index``)
                            whose LITERAL axis name is not bound by the
                            enclosing ``shard_map``'s statically-known
                            mesh/spec axes — or is used with no
                            enclosing ``shard_map``/``pmap`` at all
``spmd-spec-arity``         ``in_specs``/``out_specs`` tuple length vs
                            the wrapped callable's positional arity, and
                            a literal ``PartitionSpec`` with more
                            entries than a statically-known argument
                            rank
``spmd-replication-claim``  an ``out_specs`` entry of
                            ``PartitionSpec()`` (replicated claim) on an
                            output with no ``psum``/``pmean``/
                            ``all_gather`` producer on its dataflow path
                            — the statically checkable core of
                            ``check_vma``
``spmd-collective-in-loop`` collectives issued inside Python
                            ``for``/``while`` bodies (one collective per
                            unrolled iteration instead of one fused /
                            scanned reduction)

Soundness stance (matches the rest of the engine): the rules only claim
an axis is *unbound* or a claim *unsound* when they can resolve every
relevant literal — a spec built by ``tree_map``, a mesh arriving through
``self.mesh``, or an axis passed as a parameter makes the binding OPEN
and the site is skipped, never guessed.  The runtime twin
(``parallel.mesh.shard_map``'s call-time axis validation) covers what
static resolution cannot.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .core import Rule, dotted_name, last_component, assigned_names
from .dataflow import (INLINE_DEPTH, ModuleFunctions, bind_args, iter_calls,
                       iter_scope_nodes, resolve_mesh_axes,
                       resolve_spec_axes, scope_assignments)

#: axis-consuming primitive -> positional slot of its axis_name argument
_AXIS_ARG = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "all_gather": 1,
    "all_to_all": 1, "ppermute": 1, "pshuffle": 1, "psum_scatter": 1,
    "pbroadcast": 1, "axis_index": 0, "axis_size": 0,
}

#: the subset that moves bytes over the interconnect (axis_index /
#: axis_size read a register — axis-consuming but free)
_COMM = frozenset(_AXIS_ARG) - {"axis_index", "axis_size"}

#: reducers whose result is identical on every participant — the
#: producers that make a ``PartitionSpec()`` replication claim honest
_REPLICATING = frozenset({"psum", "pmean", "pmax", "pmin", "all_gather"})

#: dotted roots TRANSPARENT to the replication walk: ``jnp.sum(x)``
#: transforms a device-varying value, it never launders it
_TRANSPARENT_ROOTS = frozenset({"jnp", "jax", "lax", "np", "numpy",
                                "math", "functools"})

#: builtins that are transparent the same way (``sum(leaves)`` varies
#: when its argument does); any OTHER unresolved bare-name call is an
#: import whose replication behavior is unknown
_TRANSPARENT_BUILTINS = frozenset({
    "sum", "min", "max", "abs", "float", "int", "bool", "list", "tuple",
    "zip", "enumerate", "sorted", "reversed", "map", "len", "range",
})

#: transforms that bind an ``axis_name=`` themselves (a psum under pmap
#: is bound by the pmap, not a shard_map)
_AXIS_BINDERS = {"pmap", "vmap", "xmap"}


def _collective_callee(call: ast.Call) -> Optional[str]:
    """The axis-consuming primitive a call invokes, or None.  Dotted
    receivers must be jax/lax-rooted (``self.all_gather(...)`` on a comm
    class is not ``lax.all_gather``); bare names are accepted (``from
    jax.lax import psum``)."""
    name = last_component(call.func)
    if name not in _AXIS_ARG:
        return None
    if isinstance(call.func, ast.Attribute):
        dn = dotted_name(call.func)
        root = dn.split(".")[0] if dn else None
        if root not in ("jax", "lax"):
            return None
    return name


def _axis_expr(call: ast.Call, name: str):
    """The axis_name argument expression of an axis-consuming call."""
    for k in call.keywords:
        if k.arg == "axis_name":
            return k.value
    pos = _AXIS_ARG[name]
    if len(call.args) > pos:
        return call.args[pos]
    return None


def _literal_axes(expr, bindings: Dict[str, str]) -> Optional[Set[str]]:
    """Axis names when the expression is a string literal, a tuple of
    them, or a parameter bound to a literal at an inlined call site;
    None when not statically known."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return {expr.value}
    if isinstance(expr, (ast.Tuple, ast.List)):
        out: Set[str] = set()
        for el in expr.elts:
            sub = _literal_axes(el, bindings)
            if sub is None:
                return None
            out |= sub
        return out
    if isinstance(expr, ast.Name) and expr.id in bindings:
        return {bindings[expr.id]}
    return None


# --------------------------------------------------------------------------
# shard_map region discovery
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Region:
    """One ``shard_map``-wrapped body (or ``pmap(..., axis_name=...)``):
    the statically-known axis binding the body's collectives run
    under."""
    fn: Optional[ast.FunctionDef]    # wrapped body, when resolvable
    anchor: ast.AST                  # the wrapping call (finding anchor)
    axes: Set[str]                   # known bound axis names
    closed: bool                     # True = `axes` is the FULL set
    mesh_axes: Optional[Set[str]]    # mesh axes when the mesh is literal
    in_specs: Optional[ast.AST] = None
    out_specs: Optional[ast.AST] = None
    apply_call: Optional[ast.Call] = None   # shard_map(f, ...)(a, b)
    assigns: Dict[str, ast.AST] = dataclasses.field(default_factory=dict)


def _shard_map_aliases(tree: ast.Module) -> Set[str]:
    out = {"shard_map"}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name == "shard_map":
                    out.add(a.asname or a.name)
    return out


def _wrapper_call(node, aliases: Set[str]) -> Optional[ast.Call]:
    """The config-carrying Call of a shard_map wrapper: ``shard_map(...)``
    itself or ``functools.partial(shard_map, ...)``."""
    if not isinstance(node, ast.Call):
        return None
    if last_component(node.func) in aliases:
        return node
    if last_component(node.func) == "partial" and node.args \
            and last_component(node.args[0]) in aliases:
        return node
    return None


def _axis_binder_call(node) -> Optional[Tuple[ast.Call, Optional[str]]]:
    """``(call, axis_name literal | None)`` for pmap/vmap/xmap wrappers
    carrying an ``axis_name=`` binding."""
    if not isinstance(node, ast.Call):
        return None
    target = node
    if last_component(node.func) == "partial" and node.args \
            and last_component(node.args[0]) in _AXIS_BINDERS:
        pass
    elif last_component(node.func) not in _AXIS_BINDERS:
        return None
    for k in target.keywords:
        if k.arg == "axis_name":
            if isinstance(k.value, ast.Constant) \
                    and isinstance(k.value.value, str):
                return node, k.value.value
            return node, None
    return node, None


def _sm_kwargs(call: ast.Call):
    kw = {k.arg: k.value for k in call.keywords if k.arg}
    return kw.get("mesh"), kw.get("in_specs"), kw.get("out_specs")


def _parent_functions(tree: ast.Module) -> Dict[int, ast.AST]:
    """id(FunctionDef) -> innermost enclosing FunctionDef | module."""
    out: Dict[int, ast.AST] = {}

    def walk(node, owner):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out[id(child)] = owner
                walk(child, child)
            else:
                walk(child, owner)

    walk(tree, tree)
    return out


def _region_axes(mesh_expr, in_specs, out_specs, assigns):
    """``(axes, closed, mesh_axes)``: ONLY a literal mesh closes the
    axis set — a mesh axis may legitimately be reduced over without
    appearing in any spec (the mixed-axis TP-over-dp shape), so spec
    literals must never close the binding on their own.  With a
    non-literal mesh the binding is OPEN: collectives inside are not
    judged, and the runtime ``validate_specs`` covers the spec-typo
    class at call time."""
    if mesh_expr is not None:
        axes, closed = resolve_mesh_axes(mesh_expr, assigns)
        if closed:
            return set(axes), True, set(axes)
    return set(), False, None


#: per-tree region memo: three of the four rules need the regions of
#: the same module, and discovery walks the whole AST — compute once.
#: Keyed by id() with a strong reference to the tree held in the value
#: (so the id cannot be reused while the entry lives); bounded.
_REGION_MEMO: Dict[int, Tuple[ast.Module, List["Region"]]] = {}


def find_regions(tree: ast.Module) -> List[Region]:
    hit = _REGION_MEMO.get(id(tree))
    if hit is not None and hit[0] is tree:
        return hit[1]
    regions = _find_regions(tree)
    if len(_REGION_MEMO) > 64:
        _REGION_MEMO.clear()
    _REGION_MEMO[id(tree)] = (tree, regions)
    return regions


def _find_regions(tree: ast.Module) -> List[Region]:
    aliases = _shard_map_aliases(tree)
    defs: Dict[str, List[ast.FunctionDef]] = {}
    for n in ast.walk(tree):
        if isinstance(n, ast.FunctionDef):
            defs.setdefault(n.name, []).append(n)
    parents = _parent_functions(tree)
    regions: List[Region] = []
    seen_calls: Dict[int, Region] = {}

    def resolve_fn(name: Optional[str]) -> Optional[ast.FunctionDef]:
        cands = defs.get(name or "", [])
        return cands[0] if len(cands) == 1 else None

    def make_region(call, fn, scope, apply_call=None):
        assigns = scope_assignments(
            scope if isinstance(scope, ast.FunctionDef) else None, tree)
        mesh_expr, in_specs, out_specs = _sm_kwargs(call)
        axes, closed, mesh_axes = _region_axes(mesh_expr, in_specs,
                                               out_specs, assigns)
        reg = Region(fn=fn, anchor=call, axes=axes, closed=closed,
                     mesh_axes=mesh_axes, in_specs=in_specs,
                     out_specs=out_specs, apply_call=apply_call,
                     assigns=assigns)
        regions.append(reg)
        seen_calls[id(call)] = reg
        return reg

    # decorator form: @shard_map(...) / @functools.partial(shard_map, ...)
    # (the pipeline.py idiom) — and pmap-style axis binders
    for fns in defs.values():
        for fn in fns:
            scope = parents.get(id(fn), tree)
            for d in fn.decorator_list:
                call = _wrapper_call(d, aliases)
                if call is not None:
                    make_region(call, fn, scope)
                    continue
                binder = _axis_binder_call(d)
                if binder is not None:
                    call, axis = binder
                    regions.append(Region(
                        fn=fn, anchor=call,
                        axes={axis} if axis else set(),
                        closed=axis is not None, mesh_axes=None))

    # call form: shard_map(body, mesh=..., ...) — possibly applied
    # immediately — scanned scope by scope so spec names resolve where
    # the call is written
    scopes = [tree] + [n for n in ast.walk(tree)
                       if isinstance(n, ast.FunctionDef)]
    for scope in scopes:
        scope_binds = scope_assignments(
            scope if isinstance(scope, ast.FunctionDef) else None, tree)
        for node in iter_scope_nodes(scope):
            if not isinstance(node, ast.Call):
                continue
            inner = node.func if isinstance(node.func, ast.Call) else None
            if inner is not None and _wrapper_call(inner, aliases) \
                    is not None and id(inner) not in seen_calls:
                # immediate application: shard_map(f, ...)(a, b)
                fn = None
                if inner.args and isinstance(inner.args[0], ast.Name) \
                        and last_component(inner.args[0]) not in aliases:
                    fn = resolve_fn(inner.args[0].id)
                make_region(inner, fn, scope, apply_call=node)
            elif _wrapper_call(node, aliases) is not None \
                    and id(node) not in seen_calls:
                fn = None
                first = node.args[0] if node.args else None
                if isinstance(first, ast.Name) \
                        and first.id not in aliases:
                    fn = resolve_fn(first.id)
                if fn is not None or node.keywords:
                    make_region(node, fn, scope)
            elif isinstance(node.func, ast.Name) and _wrapper_call(
                    scope_binds.get(node.func.id), aliases) is not None:
                # stored-curried form (the serving builder idiom):
                #   wrap = functools.partial(shard_map, mesh=..., ...)
                #   ...
                #   wrap(body, in_specs=..., out_specs=...)
                # The application names the body and carries the specs;
                # the stored partial carries the mesh.  Same-scope
                # single-assignment only (scope_assignments) — a wrap
                # that crosses a function boundary stays an OPEN-mesh
                # anchor region, judged by runtime validate_specs.
                curried = _wrapper_call(scope_binds[node.func.id], aliases)
                if last_component(curried.func) == "partial":
                    fn = None
                    first = node.args[0] if node.args else None
                    if isinstance(first, ast.Name) \
                            and first.id not in aliases:
                        fn = resolve_fn(first.id)
                    if fn is not None or node.keywords:
                        p_mesh, p_in, p_out = _sm_kwargs(curried)
                        a_mesh, a_in, a_out = _sm_kwargs(node)
                        mesh_expr = a_mesh if a_mesh is not None else p_mesh
                        in_specs = a_in if a_in is not None else p_in
                        out_specs = a_out if a_out is not None else p_out
                        axes, closed, mesh_axes = _region_axes(
                            mesh_expr, in_specs, out_specs, scope_binds)
                        regions.append(Region(
                            fn=fn, anchor=node, axes=axes, closed=closed,
                            mesh_axes=mesh_axes, in_specs=in_specs,
                            out_specs=out_specs, assigns=scope_binds))
            elif isinstance(node.func, ast.Name) or \
                    isinstance(node.func, ast.Attribute):
                binder = _axis_binder_call(node)
                if binder is not None and node.args \
                        and isinstance(node.args[0], ast.Name):
                    call, axis = binder
                    fn = resolve_fn(node.args[0].id)
                    if fn is not None:
                        regions.append(Region(
                            fn=fn, anchor=call,
                            axes={axis} if axis else set(),
                            closed=axis is not None, mesh_axes=None))
    return regions


def _own_and_nested(fn) -> List[ast.AST]:
    """``fn`` plus every def/lambda lexically nested in it — a
    ``lax.scan`` body (or inline lambda) defined inside a shard_map
    body runs under the same axis binding."""
    out = [fn]
    for n in ast.walk(fn):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)) and n is not fn:
            out.append(n)
    return out


# --------------------------------------------------------------------------
# spmd-axis-unknown
# --------------------------------------------------------------------------

class SpmdAxisUnknownRule(Rule):
    id = "spmd-axis-unknown"
    default_severity = "error"
    description = ("collective/axis_index over an axis name not bound by "
                   "the enclosing shard_map's mesh or specs")

    def check_module(self, mod):
        funcs = ModuleFunctions(mod.tree)
        regions = find_regions(mod.tree)
        region_fns = {id(r.fn) for r in regions if r.fn is not None}
        covered: Set[int] = set()
        findings: List = []
        seen_visits: Set[tuple] = set()
        # bodies a wrapper NAMES but the module cannot uniquely resolve
        # (two same-named defs) are still covered — never guessed at
        aliases = _shard_map_aliases(mod.tree)
        defs: Dict[str, List[ast.FunctionDef]] = {}
        for n in ast.walk(mod.tree):
            if isinstance(n, ast.FunctionDef):
                defs.setdefault(n.name, []).append(n)
        for call in ast.walk(mod.tree):
            if not isinstance(call, ast.Call):
                continue
            if _wrapper_call(call, aliases) is None \
                    and _axis_binder_call(call) is None:
                continue
            first = call.args[0] if call.args else None
            if isinstance(first, ast.Name) and first.id not in aliases:
                for fn in defs.get(first.id, ()):
                    for sub in _own_and_nested(fn):
                        covered.add(id(sub))
            elif isinstance(first, ast.Lambda):
                # a shard_map-wrapped lambda body: inside a binder, but
                # its axis set is not judged (a lambda has no name to
                # resolve) — covered, never swept as unbound
                for sub in _own_and_nested(first):
                    covered.add(id(sub))
        for reg in regions:
            # a spec literal naming an axis outside a LITERAL mesh is
            # the same typo class, caught at the wrapper itself
            if reg.mesh_axes is not None:
                for spec in (reg.in_specs, reg.out_specs):
                    if spec is None:
                        continue
                    axes, closed = resolve_spec_axes(spec, reg.assigns)
                    for a in sorted(axes - reg.mesh_axes):
                        findings.append(self.finding(
                            mod, spec,
                            f"spec names axis '{a}' but the shard_map "
                            f"mesh only defines "
                            f"{sorted(reg.mesh_axes)} — a typo'd spec "
                            f"axis fails deep inside jax (or silently "
                            f"changes the partitioning)"))
            if reg.fn is None:
                continue
            self._visit(mod, funcs, reg.fn, reg, {}, (), covered,
                        seen_visits, findings, INLINE_DEPTH, region_fns)
        # the outside sweep: literal-axis primitives with NO enclosing
        # binder at all (lambda bodies included — a collective hidden
        # in a lambda escapes no contract)
        for fn in (n for n in ast.walk(mod.tree)
                   if isinstance(n, (ast.FunctionDef, ast.Lambda))):
            if id(fn) in covered:
                continue
            for call in iter_calls(fn):
                name = _collective_callee(call)
                if name is None:
                    continue
                axes = _literal_axes(_axis_expr(call, name), {})
                if not axes:
                    continue
                findings.append(self.finding(
                    mod, call,
                    f"lax.{name} over axis {sorted(axes)} inside "
                    f"'{getattr(fn, 'name', '<lambda>')}': no "
                    f"enclosing shard_map/pmap binds this axis — the "
                    f"call only compiles (and only means anything) "
                    f"under a mesh context that defines it; wrap the "
                    f"body in mesh.shard_map / jax.shard_map with the "
                    f"axis in its mesh"))
        return findings

    def _visit(self, mod, funcs, fn, reg, bindings, chain, covered,
               seen, findings, depth, region_fns=frozenset()):
        key = (id(fn), id(reg.anchor), frozenset(bindings.items()))
        if key in seen:
            return
        seen.add(key)
        for sub in _own_and_nested(fn):
            covered.add(id(sub))
        via = f" (reached via {' -> '.join(chain)})" if chain else ""
        for sub in _own_and_nested(fn):
            if sub is not fn and id(sub) in region_fns:
                # a NESTED shard_map body carries its own axis binding
                # (the TP-inside-dp shape): judged by its own region's
                # visit, never against this one's axes
                continue
            for call in iter_calls(sub):
                name = _collective_callee(call)
                if name is not None and reg.closed:
                    axes = _literal_axes(_axis_expr(call, name), bindings)
                    if axes:
                        for a in sorted(axes - reg.axes):
                            findings.append(self.finding(
                                mod, call,
                                f"lax.{name} over axis '{a}' inside "
                                f"shard_map body "
                                f"'{getattr(sub, 'name', '<lambda>')}'"
                                f"{via}, but "
                                f"the enclosing shard_map only binds "
                                f"axes {sorted(reg.axes)} — an unbound "
                                f"axis name fails deep inside jax (or, "
                                f"if it exists on an OUTER transform, "
                                f"reduces over the wrong devices)"))
                if depth > 0 and name is None:
                    callee = funcs.resolve_call(sub, call)
                    if callee is None or id(callee) in region_fns:
                        continue
                    new_bind = {}
                    params = [a.arg for a in callee.args.posonlyargs
                              + callee.args.args]
                    offset = 1 if params[:1] == ["self"] \
                        and isinstance(call.func, ast.Attribute) else 0
                    for i, a in enumerate(call.args):
                        idx = i + offset
                        if isinstance(a, ast.Constant) \
                                and isinstance(a.value, str) \
                                and idx < len(params):
                            new_bind[params[idx]] = a.value
                    for k in call.keywords:
                        if k.arg and isinstance(k.value, ast.Constant) \
                                and isinstance(k.value.value, str):
                            new_bind[k.arg] = k.value.value
                    self._visit(mod, funcs, callee, reg, new_bind,
                                chain + (getattr(sub, "name",
                                                 "<lambda>"),),
                                covered, seen, findings, depth - 1,
                                region_fns)


# --------------------------------------------------------------------------
# spmd-spec-arity
# --------------------------------------------------------------------------

class SpmdSpecArityRule(Rule):
    id = "spmd-spec-arity"
    default_severity = "error"
    description = ("in_specs/out_specs arity vs the wrapped callable, "
                   "and PartitionSpec rank vs statically-known argument "
                   "rank")

    def check_module(self, mod):
        for reg in find_regions(mod.tree):
            if reg.in_specs is None and reg.out_specs is None:
                continue
            yield from self._check_in_arity(mod, reg)
            yield from self._check_out_arity(mod, reg)
            yield from self._check_ranks(mod, reg)

    # -- in_specs length vs positional arity ---------------------------
    def _check_in_arity(self, mod, reg):
        if reg.fn is None or not isinstance(reg.in_specs, ast.Tuple):
            return
        n = len(reg.in_specs.elts)
        if any(isinstance(e, ast.Starred) for e in reg.in_specs.elts):
            return
        args = reg.fn.args
        params = [a.arg for a in args.posonlyargs + args.args]
        required = len(params) - len(args.defaults)
        if args.vararg is None and n > len(params):
            yield self.finding(
                mod, reg.in_specs,
                f"in_specs has {n} entries but '{reg.fn.name}' takes at "
                f"most {len(params)} positional argument(s) — shard_map "
                f"zips specs with arguments one-to-one, so the extra "
                f"spec(s) raise (or shift every later binding by one)")
        elif n < required:
            yield self.finding(
                mod, reg.in_specs,
                f"in_specs has {n} entries but '{reg.fn.name}' requires "
                f"at least {required} positional argument(s) — each "
                f"argument needs its own spec")

    # -- out_specs length vs returned-tuple length ----------------------
    def _check_out_arity(self, mod, reg):
        if reg.fn is None or not isinstance(reg.out_specs, ast.Tuple):
            return
        lengths = set()
        for node in iter_scope_nodes(reg.fn):
            if isinstance(node, ast.Return) and node.value is not None:
                if not isinstance(node.value, ast.Tuple):
                    return          # tuple-valued name: cannot align
                if any(isinstance(e, ast.Starred)
                       for e in node.value.elts):
                    return
                lengths.add(len(node.value.elts))
        if len(lengths) != 1:
            return
        r = lengths.pop()
        s = len(reg.out_specs.elts)
        if s != r:
            yield self.finding(
                mod, reg.out_specs,
                f"out_specs has {s} entries but '{reg.fn.name}' returns "
                f"{r} value(s) — the output pytree and its specs must "
                f"match element-for-element")

    # -- PartitionSpec entry count vs known argument rank ----------------
    def _check_ranks(self, mod, reg):
        if reg.apply_call is None or not isinstance(reg.in_specs,
                                                    ast.Tuple):
            return
        ranks = self._static_ranks(reg)
        for i, arg in enumerate(reg.apply_call.args):
            if isinstance(arg, ast.Starred):
                break   # the star expands to an unknown count: every
                        # later AST index is misaligned with its spec
            if i >= len(reg.in_specs.elts):
                break
            if not isinstance(arg, ast.Name) or arg.id not in ranks:
                continue
            spec = reg.in_specs.elts[i]
            if isinstance(spec, ast.Name):
                spec = reg.assigns.get(spec.id, spec)
            if not (isinstance(spec, ast.Call)
                    and last_component(spec.func) in ("PartitionSpec",
                                                      "P")):
                continue
            entries = len(spec.args)
            rank = ranks[arg.id]
            if entries > rank:
                yield self.finding(
                    mod, reg.apply_call.args[i],
                    f"in_specs[{i}] is a PartitionSpec with {entries} "
                    f"entries but '{arg.id}' has rank {rank} — a spec "
                    f"longer than the array rank raises at trace time")

    @staticmethod
    def _static_ranks(reg) -> Dict[str, int]:
        """Names whose array rank is statically evident from their
        single assignment (``x = jnp.zeros((4, 8))`` and friends)."""
        ranks: Dict[str, int] = {}
        for name, value in reg.assigns.items():
            if not isinstance(value, ast.Call):
                continue
            lc = last_component(value.func)
            if lc in ("zeros", "ones", "empty", "full") and value.args \
                    and isinstance(value.args[0], ast.Tuple):
                ranks[name] = len(value.args[0].elts)
            elif lc == "arange":
                ranks[name] = 1
            elif lc == "reshape":
                if len(value.args) == 1 \
                        and isinstance(value.args[0], ast.Tuple):
                    ranks[name] = len(value.args[0].elts)
                elif value.args and all(
                        isinstance(a, (ast.Constant, ast.Name,
                                       ast.UnaryOp))
                        for a in value.args) and len(value.args) > 1:
                    ranks[name] = len(value.args)
        return ranks


# --------------------------------------------------------------------------
# spmd-replication-claim
# --------------------------------------------------------------------------

_CLEAN, _UNKNOWN, _DIRTY = "clean", "unknown", "dirty"


class SpmdReplicationClaimRule(Rule):
    id = "spmd-replication-claim"
    default_severity = "error"
    description = ("out_specs replication claim (PartitionSpec()) with "
                   "no psum/pmean/all_gather on the output's dataflow "
                   "path")

    def check_module(self, mod):
        funcs = ModuleFunctions(mod.tree)
        self._fn_memo: Dict[tuple, str] = {}
        for reg in find_regions(mod.tree):
            if reg.fn is None or reg.out_specs is None:
                continue
            claims = self._claims(reg)
            if claims is None:
                continue
            varying = self._varying_params(reg)
            closure = self._closure(reg.fn, varying, funcs,
                                    INLINE_DEPTH)
            for ret in iter_scope_nodes(reg.fn):
                if not isinstance(ret, ast.Return) or ret.value is None:
                    continue
                yield from self._check_return(mod, funcs, reg, claims,
                                              closure, ret)

    # ------------------------------------------------------------------
    def _claims(self, reg):
        """``"all"`` | set of claimed output positions | None (no
        literal replication claim to judge)."""
        spec = reg.out_specs
        if isinstance(spec, ast.Name):
            spec = reg.assigns.get(spec.id, spec)
        if self._is_empty_pspec(spec, reg):
            return "all"
        if isinstance(spec, ast.Tuple):
            claimed = {i for i, el in enumerate(spec.elts)
                       if self._is_empty_pspec(el, reg)}
            return claimed or None
        return None

    @staticmethod
    def _is_empty_pspec(expr, reg) -> bool:
        if isinstance(expr, ast.Name):
            expr = reg.assigns.get(expr.id, expr)
        return (isinstance(expr, ast.Call)
                and last_component(expr.func) in ("PartitionSpec", "P")
                and not expr.args and not expr.keywords)

    def _varying_params(self, reg) -> Set[str]:
        """Parameters whose per-device values can differ: sharded (spec
        with axes) or unresolvable specs.  ``in_specs=PartitionSpec()``
        (jax's pytree-prefix "everything replicated" form) makes NO
        parameter varying; with no alignable literal in_specs at all,
        EVERY parameter is assumed varying — the rule then only passes
        outputs that carry a reducer (or launder through an
        unresolvable call)."""
        args = reg.fn.args
        params = [a.arg for a in args.posonlyargs + args.args
                  if a.arg != "self"]
        if args.vararg is not None:
            params.append(args.vararg.arg)
        spec = reg.in_specs
        if isinstance(spec, ast.Name):
            spec = reg.assigns.get(spec.id, spec)
        if self._is_empty_pspec(spec, reg):
            return set()
        if not isinstance(spec, ast.Tuple):
            return set(params)
        varying = set()
        elts = spec.elts
        for i, p in enumerate(params):
            if i >= len(elts):
                varying.add(p)       # *leaves tail: sharded batch data
                continue
            axes, closed = resolve_spec_axes(elts[i], reg.assigns)
            if axes or not closed:
                varying.add(p)
        return varying

    # ------------------------------------------------------------------
    def _check_return(self, mod, funcs, reg, claims, closure, ret):
        if claims == "all":
            targets = [(None, ret.value)]
        else:
            if not isinstance(ret.value, ast.Tuple) \
                    or len(ret.value.elts) != len(reg.out_specs.elts):
                return
            targets = [(i, ret.value.elts[i]) for i in sorted(claims)]
        for pos, expr in targets:
            verdict = self._verdict(expr, closure, funcs, reg.fn,
                                    INLINE_DEPTH)
            if verdict == _DIRTY:
                where = "the output" if pos is None \
                    else f"output {pos}"
                yield self.finding(
                    mod, expr,
                    f"out_specs claims {where} of '{reg.fn.name}' is "
                    f"replicated (PartitionSpec()), but its value "
                    f"derives from per-device inputs with no psum/"
                    f"pmean/all_gather on the dataflow path — the "
                    f"claim is unsound: devices hold DIFFERENT values "
                    f"and jax will either reject it (check_vma) or "
                    f"silently serve one shard's answer; reduce before "
                    f"claiming replication, or shard the output spec")

    def _verdict(self, expr, varying, funcs, owner, depth) -> str:
        flags: Set[str] = set()
        self._scan(expr, varying, funcs, owner, depth, flags)
        if _CLEAN in flags:
            return _CLEAN
        if _UNKNOWN in flags:
            return _UNKNOWN
        if _DIRTY in flags:
            return _DIRTY
        return _CLEAN       # constants / replicated-only: identical

    @staticmethod
    def _ifexp_callees(func) -> Set[str]:
        """Possible callee names of a conditionally-dispatched call —
        ``(lax.pmean if mean else lax.psum)(x, "dp")``, the step.py
        loss-reduction idiom."""
        if isinstance(func, ast.IfExp):
            return (SpmdReplicationClaimRule._ifexp_callees(func.body)
                    | SpmdReplicationClaimRule._ifexp_callees(
                        func.orelse))
        name = last_component(func)
        return {name} if name else {"<unknown>"}

    def _scan(self, expr, varying, funcs, owner, depth, flags):
        if isinstance(expr, ast.Call):
            if isinstance(expr.func, ast.IfExp):
                names = self._ifexp_callees(expr.func)
                if names <= _REPLICATING:
                    flags.add(_CLEAN)     # every branch reduces
                else:
                    # mixed or unknown dispatch: never claim unsound
                    flags.add(_UNKNOWN)
                return
            name = _collective_callee(expr)
            if name in _REPLICATING:
                flags.add(_CLEAN)
                return
            if name in ("axis_index",):
                flags.add(_DIRTY)
                return
            callee = funcs.resolve_call(owner, expr) \
                if isinstance(owner, ast.FunctionDef) else None
            if callee is not None and depth > 0:
                seed = bind_args(
                    callee, expr,
                    lambda e: self._verdict(e, varying, funcs, owner,
                                            depth) == _DIRTY)
                flags.add(self._fn_verdict(callee, frozenset(seed),
                                           funcs, depth - 1))
                return
            if callee is None and isinstance(expr.func, ast.Attribute):
                dn = dotted_name(expr.func)
                root = dn.split(".")[0] if dn else None
                if root not in _TRANSPARENT_ROOTS:
                    # method call: transparent when the receiver itself
                    # is a device-varying array expression
                    # (``(x / s).astype(...)`` chains deviceness) or a
                    # reduced one (``psum(x).reshape(...)`` stays
                    # identical); anything else — a foreign object, a
                    # cross-module helper like
                    # ``_quantize.reduce_gradients`` — has unknown
                    # replication behavior and must never be claimed
                    # unsound
                    rflags: Set[str] = set()
                    self._scan(expr.func.value, varying, funcs, owner,
                               depth, rflags)
                    if _CLEAN in rflags:
                        flags.add(_CLEAN)
                        return
                    if _DIRTY in rflags and _UNKNOWN not in rflags:
                        flags.add(_DIRTY)
                        for a in list(expr.args) \
                                + [k.value for k in expr.keywords]:
                            self._scan(a, varying, funcs, owner, depth,
                                       flags)
                        return
                    flags.add(_UNKNOWN)
                    return
            if callee is None and isinstance(expr.func, ast.Name) \
                    and expr.func.id not in _TRANSPARENT_BUILTINS:
                # unresolved bare-name call (an import from another
                # module): it may itself reduce — unknown, not dirty
                flags.add(_UNKNOWN)
                return
            for a in list(expr.args) + [k.value for k in expr.keywords]:
                self._scan(a, varying, funcs, owner, depth, flags)
            return
        if isinstance(expr, ast.Name) and isinstance(expr.ctx, ast.Load) \
                and expr.id in varying:
            flags.add(_DIRTY)
        for child in ast.iter_child_nodes(expr):
            self._scan(child, varying, funcs, owner, depth, flags)

    def _fn_verdict(self, fn, seed: frozenset, funcs, depth) -> str:
        key = (id(fn), seed, depth)
        if key in self._fn_memo:
            return self._fn_memo[key]
        self._fn_memo[key] = _UNKNOWN      # cycle guard
        closure = self._closure(fn, set(seed), funcs, depth)
        flags: Set[str] = set()
        for node in iter_scope_nodes(fn):
            if isinstance(node, ast.Return) and node.value is not None:
                flags.add(self._verdict(node.value, closure, funcs, fn,
                                        depth))
        out = (_DIRTY if _DIRTY in flags else
               _UNKNOWN if _UNKNOWN in flags else _CLEAN)
        self._fn_memo[key] = out
        return out

    def _closure(self, fn, seed: Set[str], funcs, depth) -> Set[str]:
        """Names whose values can differ per device, closed over the
        function's assignments (a ``psum`` on the right-hand side stops
        the propagation — its result is identical everywhere)."""
        varying = set(seed)
        for _ in range(3):
            before = len(varying)
            for node in iter_scope_nodes(fn):
                if isinstance(node, (ast.Assign, ast.AnnAssign,
                                     ast.AugAssign, ast.NamedExpr)):
                    value = node.value
                    if value is None:
                        continue
                    if self._verdict(value, varying, funcs, fn,
                                     depth) == _DIRTY:
                        targets = node.targets \
                            if isinstance(node, ast.Assign) \
                            else [node.target]
                        for t in targets:
                            varying |= assigned_names(t)
                elif isinstance(node, (ast.For, ast.comprehension)):
                    if self._verdict(node.iter, varying, funcs, fn,
                                     depth) == _DIRTY:
                        varying |= assigned_names(node.target)
            if len(varying) == before:
                break
        return varying


# --------------------------------------------------------------------------
# spmd-collective-in-loop
# --------------------------------------------------------------------------

class SpmdCollectiveInLoopRule(Rule):
    id = "spmd-collective-in-loop"
    default_severity = "error"
    description = ("collective issued inside a Python for/while body — "
                   "one collective per unrolled iteration instead of a "
                   "fused/scanned reduction")

    def check_module(self, mod):
        fns = [n for n in ast.walk(mod.tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for fn in fns:
            for node in iter_scope_nodes(fn):
                if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                    roots = list(node.body)
                    if isinstance(node, ast.While):
                        roots.append(node.test)
                    yield from self._flag(mod, roots, "a Python loop")
                elif isinstance(node, (ast.ListComp, ast.SetComp,
                                       ast.DictComp, ast.GeneratorExp)):
                    roots = [node.key, node.value] \
                        if isinstance(node, ast.DictComp) else [node.elt]
                    for gen in node.generators:
                        roots.extend(gen.ifs)
                    yield from self._flag(mod, roots, "a comprehension")

    def _flag(self, mod, roots, where):
        for root in roots:
            for call in iter_calls(root):
                name = _collective_callee(call)
                if name is None or name not in _COMM:
                    continue
                # one-argument lookalikes (mx.distributed.all_gather)
                # never carry an axis_name
                if len(call.args) + len(call.keywords) < 2 \
                        and not any(k.arg == "axis_name"
                                    for k in call.keywords):
                    continue
                yield self.finding(
                    mod, call,
                    f"lax.{name} inside {where}: the trace unrolls one "
                    f"collective per iteration — per-layer collective "
                    f"latency XLA cannot fuse, the byte pattern the "
                    f"sharded cost budgets exist to catch.  Stack/"
                    f"concatenate the operands and issue ONE collective, "
                    f"or move the loop into lax.scan so the compiler "
                    f"can pipeline it")
