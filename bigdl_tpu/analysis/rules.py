"""The analyzer's rule families.

Every rule here encodes a bug class this repo has actually shipped (see
docs/analysis.md for the war stories):

==========  ==============================================================
SPMD101     compat drift — version-moved jax APIs spelled directly
SPMD102     PartitionSpec spelling drift (the PR-4 double-compile)
SPMD103     recompile hazards in/around jitted programs
SPMD104     donated buffer reused after the donating call
SPMD105     Python control flow on traced values
SPMD106     shard_map specs naming axes the mesh does not have
SRV201-208  serving contracts (whole-program fact table)
ASY301-305  async readiness: host-sync hygiene on the HOT PATH, scoped
            by call-graph reachability from the serving super-step
            roots (core.hotpath_chains)
==========  ==============================================================

All rules are import-resolution based, not textual: ``lax.pvary`` is
flagged under ``from jax import lax`` and not when ``lax`` is someone's
local variable, and docstrings/comments never trigger (the historical
reason the repo could not just grep for these).
"""

from __future__ import annotations

import ast
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Set, Tuple

from bigdl_tpu.analysis.core import (
    UNRESOLVED, FileContext, Finding, Rule, _own_scope_nodes,
    _unit_functions, enclosing_unit, hotpath_chains, literal_value,
    register, register_fact_collector as _register_facts,
)

# --------------------------------------------------------------------------
# shared machinery
# --------------------------------------------------------------------------

#: wrappers whose function argument becomes a traced body
_JIT_QUALNAMES = {"jax.jit", "jax.pmap"}
_SHARD_MAP_QUALNAMES = {
    "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
    "bigdl_tpu.utils.compat.shard_map",
    "bigdl_tpu.utils.compat.resolve_shard_map",
}
#: control-flow combinators: (qualname -> positions of traced callees)
_COMBINATOR_FN_ARGS = {
    "jax.lax.scan": (0,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.fori_loop": (2,),
    "jax.lax.cond": (1, 2),
    "jax.lax.switch": None,       # every arg from 1 on is a branch
    "jax.lax.associative_scan": (0,),
    "jax.checkpoint": (0,),
    "jax.remat": (0,),
}

#: attributes of a traced array that are static at trace time — branching
#: or formatting on these is fine
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding",
                 "weak_type", "itemsize", "nbytes"}
#: calls whose result on a tracer is static / python-level
_STATIC_CALLS = {"len", "isinstance", "callable", "hasattr", "getattr",
                 "type", "id", "repr"}


def _const_int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    """(1, 2) / 1 / [0] as a tuple of ints, else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int) \
                    and not isinstance(e.value, bool):
                out.append(e.value)
            else:
                return None
        return tuple(out)
    return None


def _const_str_set(node: ast.AST) -> Optional[Set[str]]:
    """Set of string constants in a str / tuple/list-of-str literal."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[str] = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.add(e.value)
            else:
                return None
        return out
    return None


def _kwarg(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    names = [p.arg for p in
             list(getattr(a, "posonlyargs", [])) + list(a.args)
             + list(a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


class _TracedFn:
    """A function object the analyzer believes gets traced, plus which of
    its parameters are dynamic (non-static) there."""

    def __init__(self, fn: ast.AST, via: str,
                 static_argnums: Tuple[int, ...] = (),
                 static_argnames: Sequence[str] = ()) -> None:
        self.fn = fn                      # FunctionDef / Lambda
        self.via = via                    # "jax.jit", "compat.shard_map", ...
        names = _param_names(fn)
        drop = set(static_argnames)
        for i in static_argnums:
            if 0 <= i < len(names):
                drop.add(names[i])
        self.dynamic_params = {n for n in names if n not in drop
                               and n != "self"}


def _local_defs(ctx: FileContext) -> Dict[str, List[ast.AST]]:
    """name -> FunctionDefs in the file (all scopes), in source order."""
    out = ctx.cache.get("local_defs")
    if out is None:
        out = ctx.cache["local_defs"] = {}
        for node in sorted(ctx.by_type(ast.FunctionDef,
                                       ast.AsyncFunctionDef),
                           key=lambda n: n.lineno):
            out.setdefault(node.name, []).append(node)
    return out


def _resolve_fn_arg(ctx: FileContext, node: ast.AST,
                    defs: Dict[str, List[ast.AST]],
                    before_line: int) -> Optional[ast.AST]:
    """The function object an argument refers to: a Lambda/def literal,
    or the nearest preceding local def with that name."""
    if isinstance(node, ast.Lambda):
        return node
    if isinstance(node, ast.Name) and node.id in defs:
        cands = [d for d in defs[node.id] if d.lineno <= before_line]
        return cands[-1] if cands else defs[node.id][0]
    return None


def _is_partial(ctx: FileContext, call: ast.Call) -> bool:
    q = ctx.qualname(call.func)
    return q in {"functools.partial", "partial"} or \
        (isinstance(call.func, ast.Name) and call.func.id == "partial")


def _jit_info(ctx: FileContext, value: ast.AST,
              ) -> Optional[Tuple[ast.Call, Tuple[int, ...], List[str]]]:
    """If ``value`` is a (possibly partial-wrapped) ``jax.jit(...)`` call,
    -> (the jit Call, static_argnums, static_argnames)."""
    if not isinstance(value, ast.Call):
        return None
    call = value
    q = ctx.qualname(call.func)
    if q in {"functools.partial", "partial"} and call.args:
        inner_q = ctx.qualname(call.args[0])
        if inner_q in _JIT_QUALNAMES:
            q = inner_q
        else:
            return None
    if q not in _JIT_QUALNAMES:
        return None
    nums = _kwarg(call, "static_argnums")
    names = _kwarg(call, "static_argnames")
    return (call,
            _const_int_tuple(nums) or () if nums is not None else (),
            sorted(_const_str_set(names) or set()) if names is not None
            else [])


def _traced_functions(ctx: FileContext) -> List[_TracedFn]:
    """Every local def/lambda the file hands to jit / shard_map / a lax
    control-flow combinator, plus defs decorated with them.  Cached per
    file — SPMD103 and SPMD105 share one derivation."""
    cached = ctx.cache.get("traced_functions")
    if cached is not None:
        return cached
    defs = _local_defs(ctx)
    traced: List[_TracedFn] = []
    seen: Set[int] = set()

    def add(fn: Optional[ast.AST], via: str,
            static_argnums: Tuple[int, ...] = (),
            static_argnames: Sequence[str] = ()) -> None:
        if fn is None or id(fn) in seen:
            return
        seen.add(id(fn))
        traced.append(_TracedFn(fn, via, static_argnums, static_argnames))

    for node in ctx.by_type(ast.Call, ast.FunctionDef,
                            ast.AsyncFunctionDef):
        if isinstance(node, ast.Call):
            q = ctx.qualname(node.func)
            if q in _JIT_QUALNAMES or q in _SHARD_MAP_QUALNAMES:
                info = _jit_info(ctx, node)
                nums, names = (info[1], info[2]) if info else ((), [])
                if node.args:
                    add(_resolve_fn_arg(ctx, node.args[0], defs,
                                        node.lineno), q or "jit",
                        nums, names)
            elif q in _COMBINATOR_FN_ARGS:
                poss = _COMBINATOR_FN_ARGS[q]
                if poss is None:                       # lax.switch
                    poss = tuple(range(1, len(node.args)))
                for i in poss:
                    if i < len(node.args):
                        add(_resolve_fn_arg(ctx, node.args[i], defs,
                                            node.lineno), q)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    q = ctx.qualname(dec.func)
                    if q in _JIT_QUALNAMES:
                        nums = _kwarg(dec, "static_argnums")
                        names = _kwarg(dec, "static_argnames")
                        add(node, q, _const_int_tuple(nums) or ()
                            if nums is not None else (),
                            sorted(_const_str_set(names) or set())
                            if names is not None else [])
                    elif _is_partial(ctx, dec) and dec.args and \
                            ctx.qualname(dec.args[0]) in _JIT_QUALNAMES:
                        nums = _kwarg(dec, "static_argnums")
                        names = _kwarg(dec, "static_argnames")
                        add(node, "jax.jit", _const_int_tuple(nums) or ()
                            if nums is not None else (),
                            sorted(_const_str_set(names) or set())
                            if names is not None else [])
                else:
                    q = ctx.qualname(dec)
                    if q in _JIT_QUALNAMES:
                        add(node, q)
    ctx.cache["traced_functions"] = traced
    return traced


def _dynamic_uses(expr: ast.AST, tainted: Set[str]) -> List[ast.Name]:
    """Name nodes in ``expr`` bound to tainted (traced) values that are
    used *dynamically* — i.e. NOT behind a trace-time-static accessor
    (``x.shape``/``x.ndim``/``x.dtype``..., ``len(x)``, ``isinstance``,
    ``x is None``).  These are the uses that concretize a tracer."""
    offending: List[ast.Name] = []

    def visit(node: ast.AST, static: bool) -> None:
        if isinstance(node, ast.Name):
            if node.id in tainted and not static:
                offending.append(node)
            return
        if isinstance(node, ast.Attribute):
            visit(node.value, static or node.attr in _STATIC_ATTRS)
            return
        if isinstance(node, ast.Call):
            fname = node.func.id if isinstance(node.func, ast.Name) else None
            inner_static = static or fname in _STATIC_CALLS
            for child in list(node.args) + [kw.value for kw in node.keywords]:
                visit(child, inner_static)
            if not isinstance(node.func, ast.Name):
                visit(node.func, static)
            return
        if isinstance(node, ast.Compare) and \
                all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            for child in [node.left] + list(node.comparators):
                visit(child, True)
            return
        for child in ast.iter_child_nodes(node):
            visit(child, static)

    visit(expr, False)
    return offending


# --------------------------------------------------------------------------
# SPMD101 — compat drift
# --------------------------------------------------------------------------

#: qualified names that moved between jax releases and therefore must be
#: spelled only inside utils/compat.py; value = the shim to use instead
_COMPAT_ONLY = {
    "jax.shard_map": "utils.compat.shard_map",
    "jax.experimental.shard_map": "utils.compat.shard_map",
    "jax.typeof": "utils.compat.varying_axes",
    "jax.lax.pvary": "utils.compat.device_varying_marker",
    "jax.lax.pcast": "utils.compat.device_varying_marker",
}
#: getattr-probe spellings of the same drift ({module qualname: attrs})
_COMPAT_ONLY_PROBES = {
    "jax": {"shard_map": "utils.compat.shard_map",
            "typeof": "utils.compat.varying_axes"},
    "jax.lax": {"pvary": "utils.compat.device_varying_marker",
                "pcast": "utils.compat.device_varying_marker"},
}


def _compat_match(qual: str) -> Optional[Tuple[str, str]]:
    """-> (matched banned prefix, replacement shim) or None."""
    for banned, shim in _COMPAT_ONLY.items():
        if qual == banned or qual.startswith(banned + "."):
            return banned, shim
    return None


@register
class CompatDriftRule(Rule):
    code = "SPMD101"
    name = "compat-drift"
    summary = ("version-moved jax API (shard_map / typeof / pvary / pcast) "
               "spelled directly instead of through utils.compat")
    hint = ("route through bigdl_tpu.utils.compat — shard_map for "
            "jax.shard_map/jax.experimental.shard_map, varying_axes for "
            "jax.typeof(...).vma, device_varying_marker for lax.pvary/"
            "lax.pcast; the shim resolves the right spelling per jax "
            "generation")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if ctx.is_compat:
            return
        flagged: Set[Tuple[int, int]] = set()

        def emit(node: ast.AST, qual: str, shim: str) -> Optional[Finding]:
            key = (node.lineno, node.col_offset)
            if key in flagged:
                return None
            flagged.add(key)
            return ctx.finding(
                node, self.code,
                f"direct use of `{qual}` outside utils/compat.py "
                f"— this API moved between jax releases",
                hint=f"use `{shim}` — {self.hint}")

        for node in ctx.by_type(ast.Import, ast.ImportFrom,
                                ast.Attribute, ast.Call):
            if isinstance(node, ast.Import):
                for a in node.names:
                    m = _compat_match(a.name)
                    if m:
                        f = emit(node, a.name, m[1])
                        if f:
                            yield f
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for a in node.names:
                    m = _compat_match(f"{node.module}.{a.name}")
                    if m:
                        f = emit(node, f"{node.module}.{a.name}", m[1])
                        if f:
                            yield f
            elif isinstance(node, ast.Attribute):
                qual = ctx.qualname(node)
                if qual:
                    m = _compat_match(qual)
                    if m and not isinstance(ctx.parents.get(node),
                                            ast.Attribute):
                        f = emit(node, qual, m[1])
                        if f:
                            yield f
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    node.func.id == "getattr" and len(node.args) >= 2:
                mod = ctx.qualname(node.args[0])
                attr = node.args[1]
                if mod in _COMPAT_ONLY_PROBES and \
                        isinstance(attr, ast.Constant) and \
                        attr.value in _COMPAT_ONLY_PROBES[mod]:
                    shim = _COMPAT_ONLY_PROBES[mod][attr.value]
                    f = emit(node, f'getattr({mod}, "{attr.value}")', shim)
                    if f:
                        yield f


# --------------------------------------------------------------------------
# SPMD102 — PartitionSpec spelling drift
# --------------------------------------------------------------------------

_PSPEC_QUALNAMES = {"jax.sharding.PartitionSpec",
                    "jax.experimental.pjit.PartitionSpec"}


@register
class SpecSpellingRule(Rule):
    code = "SPMD102"
    name = "spec-spelling"
    summary = ("PartitionSpec single-axis tuple spelling `P((\"a\",))` — "
               "hashes differently from `P(\"a\")` and double-compiles")
    hint = ("spell single-axis entries as the bare string: "
            "`P(\"data\")`, never `P((\"data\",))` — jit cache keys and "
            "NamedSharding equality treat them as DIFFERENT specs even "
            "though they place identically, so one drifted spelling "
            "silently compiles every program twice (the PR-4 bug); for "
            "placement specs, build through "
            "bigdl_tpu.serving.sharded.named_sharding which also drops "
            "size-1 axes")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ctx.by_type(ast.Call):
            if ctx.qualname(node.func) not in _PSPEC_QUALNAMES:
                continue
            for arg in node.args:
                if isinstance(arg, (ast.Tuple, ast.List)) and \
                        len(arg.elts) == 1:
                    spelled = ast.unparse(arg)
                    yield ctx.finding(
                        arg, self.code,
                        f"single-axis tuple spelling `{spelled}` in "
                        f"PartitionSpec — equivalent placement to the bare "
                        f"string but a DIFFERENT hash/compile key",
                        hint=self.hint)


# --------------------------------------------------------------------------
# SPMD103 — recompile hazards
# --------------------------------------------------------------------------

_BLOCKSPEC_QUALNAMES = {"jax.experimental.pallas.BlockSpec"}


@register
class RecompileHazardRule(Rule):
    code = "SPMD103"
    name = "recompile-hazard"
    summary = ("f-string/.format on traced values inside jitted bodies; "
               "structure-varying containers passed to jitted callables; "
               "Pallas BlockSpec index-map closures over per-call values")
    hint = ("traced values cannot be formatted (concretization error, or "
            "a retrace per shape via `.shape` interpolation) — format "
            "outside the traced function, e.g. in the caller or via "
            "jax.debug.print; containers built by comprehension change "
            "their pytree STRUCTURE with the data, and structure is part "
            "of the jit cache key — pad to a fixed layout or bucket it "
            "(see serving/admission.py); a BlockSpec index map that "
            "closes over an enclosing function's local bakes that value "
            "into the kernel trace — every distinct value is a NEW "
            "compiled kernel; pass per-call offsets as operands "
            "(scalar prefetch) or fold them into the grid "
            "(see ops/decode_attention.py for the closure-free pattern)")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        # (a) formatting on traced values inside traced bodies
        for tf in _traced_functions(ctx):
            tainted = set(tf.dynamic_params)
            for node in ast.walk(tf.fn):
                if isinstance(node, ast.JoinedStr):
                    offs: List[ast.Name] = []
                    for part in node.values:
                        if isinstance(part, ast.FormattedValue):
                            offs.extend(_dynamic_uses(part.value, tainted))
                    if offs:
                        yield ctx.finding(
                            node, self.code,
                            f"f-string interpolates traced value "
                            f"`{offs[0].id}` inside a body traced via "
                            f"{tf.via}",
                            hint=self.hint)
                elif isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "format":
                    offs = []
                    for a in list(node.args) + \
                            [kw.value for kw in node.keywords]:
                        offs.extend(_dynamic_uses(a, tainted))
                    if offs:
                        yield ctx.finding(
                            node, self.code,
                            f".format() on traced value `{offs[0].id}` "
                            f"inside a body traced via {tf.via}",
                            hint=self.hint)

        # (c) Pallas BlockSpec index maps that close over per-call
        # values: the index map is traced into the kernel's program, so
        # a captured enclosing-scope local (a per-request offset, a
        # data-derived start) keys a NEW pallas compile per distinct
        # value. Index maps should be pure functions of the grid
        # indices; per-call data belongs in operands. (Module-level
        # constants and the lambda's own params are fine — only names
        # bound in an enclosing function scope fire.)
        for node in ctx.by_type(ast.Call):
            if ctx.qualname(node.func) not in _BLOCKSPEC_QUALNAMES:
                continue
            im = node.args[1] if len(node.args) >= 2 else None
            for kw in node.keywords:
                if kw.arg == "index_map":
                    im = kw.value
            if not isinstance(im, ast.Lambda):
                continue
            a = im.args
            own = {p.arg for p in
                   list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)}
            if a.vararg:
                own.add(a.vararg.arg)
            if a.kwarg:
                own.add(a.kwarg.arg)
            outer = ctx.scope_local_names(im)
            for n in ast.walk(im.body):
                if isinstance(n, ast.Name) and n.id not in own and \
                        n.id in outer:
                    yield ctx.finding(
                        im, self.code,
                        f"BlockSpec index map closes over enclosing-"
                        f"scope value `{n.id}` — the closure is baked "
                        f"into the kernel trace, so every distinct "
                        f"value compiles a new pallas program",
                        hint=self.hint)
                    break

        # (b) structure-varying container literally built at the call
        # site of a known-jitted callable
        jitted_names: Set[str] = set()
        for node in ctx.by_type(ast.Assign, ast.Return):
            if isinstance(node, ast.Assign) and _jit_info(ctx, node.value):
                for t in node.targets:
                    d = ctx.dotted(t)
                    if d:
                        jitted_names.add(d)
            elif isinstance(node, ast.Return) and node.value is not None \
                    and _jit_info(ctx, node.value):
                fn = ctx.enclosing_function(node)
                if isinstance(fn, ast.FunctionDef):
                    # e.g. a cached_property returning jax.jit(...) —
                    # call sites spell it self.<name>
                    jitted_names.add(f"self.{fn.name}")
        if not jitted_names:
            return
        for node in ctx.by_type(ast.Call):
            if ctx.dotted(node.func) not in jitted_names:
                continue
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(a, (ast.DictComp, ast.ListComp, ast.SetComp,
                                  ast.GeneratorExp)):
                    yield ctx.finding(
                        a, self.code,
                        "container built by comprehension flows into "
                        f"jitted callable `{ctx.dotted(node.func)}` — its "
                        "pytree structure varies with the data, so every "
                        "new structure is a new compile",
                        hint=self.hint)


# --------------------------------------------------------------------------
# SPMD104 — donation misuse
# --------------------------------------------------------------------------

@register
class DonationReuseRule(Rule):
    code = "SPMD104"
    name = "donation-reuse"
    summary = ("argument donated via donate_argnums read again after the "
               "donating call")
    hint = ("a donated buffer is INVALID after the call (XLA reuses its "
            "memory for the outputs) — rebind the name to the call's "
            "result (`carry = step(carry, x)`) or drop donation for "
            "buffers you must keep")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        # donated callable name -> donated positional indices
        donated = _donating_callables(ctx)
        if not donated:
            return

        for node in ctx.by_type(ast.Call):
            callee = ctx.dotted(node.func)
            if callee not in donated:
                continue
            scope = ctx.enclosing_function(node) or ctx.tree
            for i in donated[callee]:
                if i >= len(node.args):
                    continue
                buf = ctx.dotted(node.args[i])
                if buf is None or buf == "self":
                    continue
                reuse = _first_reuse(ctx, scope, buf, node)
                if reuse is not None:
                    yield ctx.finding(
                        reuse, self.code,
                        f"`{buf}` was donated to `{callee}` on line "
                        f"{node.lineno} (donate_argnums includes position "
                        f"{i}) and is read again here",
                        hint=self.hint)


def _donating_callables(ctx: FileContext) -> Dict[str, Tuple[int, ...]]:
    """Dotted callable name -> donated positional indices, for every
    jitted-with-donation binding visible in the file (the SPMD104
    ground truth, shared with SRV204's call-graph lifting; cached per
    file)."""
    cached = ctx.cache.get("donating_callables")
    if cached is not None:
        return cached
    donated: Dict[str, Tuple[int, ...]] = {}
    try:
        for node in ctx.by_type(ast.Assign, ast.Return, ast.FunctionDef,
                                ast.AsyncFunctionDef):
            info = None
            if isinstance(node, ast.Assign):
                info = _jit_info(ctx, node.value)
                targets = [ctx.dotted(t) for t in node.targets]
            elif isinstance(node, ast.Return) and node.value is not None:
                info = _jit_info(ctx, node.value)
                fn = ctx.enclosing_function(node)
                targets = [f"self.{fn.name}"] \
                    if isinstance(fn, ast.FunctionDef) else []
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    j = _jit_info(ctx, dec) if isinstance(dec, ast.Call) \
                        else None
                    if j:
                        info, targets = j, [node.name]
                        break
                else:
                    continue
            else:
                continue
            if not info:
                continue
            nums = _kwarg(info[0], "donate_argnums")
            pos = _const_int_tuple(nums) if nums is not None else None
            if pos:
                for t in targets:
                    if t:
                        donated[t] = pos
    finally:
        ctx.cache["donating_callables"] = donated
    return donated


def _first_reuse(ctx: FileContext, scope: ast.AST, buf: str,
                 call: ast.Call) -> Optional[ast.AST]:
    """First Load of ``buf`` after the donating ``call`` in ``scope``
    (same function only — closures and other functions are out of
    this linear approximation) with no intervening rebinding.  Shared
    by SPMD104 and its call-graph-lifted twin SRV204."""
    call_line = getattr(call, "end_lineno", call.lineno)
    scope_fn = scope if isinstance(
        scope, (ast.FunctionDef, ast.AsyncFunctionDef,
                ast.Lambda)) else None
    loads: List[ast.AST] = []
    stores: List[int] = []
    for n in ast.walk(scope):
        if isinstance(n, ast.AugAssign):
            # `cache += 1` reads the old buffer before rebinding —
            # the target carries Store ctx only, so surface the
            # implicit read here
            if ctx.dotted(n.target) == buf and \
                    ctx.enclosing_function(n) is scope_fn and \
                    n.lineno > call_line:
                loads.append(n.target)
            continue
        d = ctx.dotted(n) if isinstance(n, (ast.Name, ast.Attribute)) \
            else None
        if d != buf:
            continue
        if ctx.enclosing_function(n) is not scope_fn:
            continue
        ic = getattr(n, "ctx", None)
        if isinstance(ic, ast.Load):
            # strictly after the donating call's last line — the
            # call's own argument loads never count
            if n.lineno > call_line:
                loads.append(n)
        elif isinstance(ic, (ast.Store, ast.Del)):
            stores.append(n.lineno)
    for n in sorted(loads, key=lambda x: (x.lineno, x.col_offset)):
        # a store masks only loads on LATER lines: in
        # `cache = cache + 1` the RHS reads the (dead) buffer before
        # the same-statement rebind takes effect
        if not any(call.lineno <= s < n.lineno for s in stores):
            return n
    return None


# --------------------------------------------------------------------------
# SPMD105 — tracer leaks
# --------------------------------------------------------------------------

@register
class TracerLeakRule(Rule):
    code = "SPMD105"
    name = "tracer-leak"
    summary = ("Python `if`/`while` on a traced value inside a "
               "jitted/shard_mapped/scanned body")
    hint = ("Python control flow runs at TRACE time and needs a concrete "
            "bool — on a tracer this raises (or silently bakes in one "
            "branch). Use lax.cond / lax.select / jnp.where for value-"
            "dependent branches; branching on static facts "
            "(`x is None`, `x.ndim`, `x.shape[0]`, `len(xs)`) is fine "
            "and not flagged")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        reported: Set[Tuple[int, int]] = set()
        for tf in _traced_functions(ctx):
            params = set(tf.dynamic_params)
            if not params:
                continue
            for node in ast.walk(tf.fn):
                if not isinstance(node, (ast.If, ast.While, ast.IfExp,
                                         ast.Assert)):
                    continue
                test = node.test
                offs = _dynamic_uses(test, params)
                if not offs:
                    continue
                key = (node.lineno, node.col_offset)
                if key in reported:
                    continue
                reported.add(key)
                kind = {ast.If: "if", ast.While: "while",
                        ast.IfExp: "conditional expression",
                        ast.Assert: "assert"}[type(node)]
                yield ctx.finding(
                    node, self.code,
                    f"`{kind}` on traced value `{offs[0].id}` inside a "
                    f"body traced via {tf.via}",
                    hint=self.hint)


# --------------------------------------------------------------------------
# SPMD106 — mesh-axis consistency
# --------------------------------------------------------------------------

_MESH_QUALNAMES = {"jax.sharding.Mesh", "jax.experimental.maps.Mesh"}
#: mesh factories with FIXED axis names (bigdl_tpu.serving.sharded.make_mesh
#: always builds ("data", "model"))
_MESH_FACTORIES = {
    "bigdl_tpu.serving.sharded.make_mesh": {"data", "model"},
    "bigdl_tpu.serving.make_mesh": {"data", "model"},
}


def _mesh_axes_from_call(ctx: FileContext,
                         call: ast.Call) -> Optional[Set[str]]:
    q = ctx.qualname(call.func)
    if q in _MESH_FACTORIES:
        return set(_MESH_FACTORIES[q])
    if q in _MESH_QUALNAMES:
        ax = _kwarg(call, "axis_names")
        if ax is None and len(call.args) >= 2:
            ax = call.args[1]
        if ax is None:
            return None
        return _const_str_set(ax)
    return None


@register
class MeshAxisRule(Rule):
    code = "SPMD106"
    name = "mesh-axis"
    summary = ("in_specs/out_specs naming an axis the shard_map's mesh "
               "does not define")
    hint = ("every axis name in in_specs/out_specs must be one of the "
            "Mesh's axis_names — a misspelled axis fails at trace time "
            "at best, silently replicates at worst; fix the spec or the "
            "Mesh construction")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ctx.by_type(ast.Call):
            q = ctx.qualname(node.func)
            if q not in _SHARD_MAP_QUALNAMES:
                continue
            mesh_arg = _kwarg(node, "mesh")
            if mesh_arg is None:
                continue
            axes: Optional[Set[str]] = None
            mesh_label = ast.unparse(mesh_arg)
            if isinstance(mesh_arg, ast.Call):
                axes = _mesh_axes_from_call(ctx, mesh_arg)
            else:
                d = ctx.dotted(mesh_arg)
                if d:
                    # scope-chain provenance (core.resolve_binding):
                    # the nearest preceding assignment wins, and a
                    # binding the analyzer cannot see into (a helper
                    # call, a parameter) SHADOWS literal constructions
                    # rather than being skipped over
                    val = ctx.resolve_binding(d, node)
                    if isinstance(val, ast.Call):
                        axes = _mesh_axes_from_call(ctx, val)
            if axes is None:
                continue           # provenance unknown — stay silent
            for kw_name in ("in_specs", "out_specs"):
                specs = _kwarg(node, kw_name)
                if specs is None:
                    continue
                for f in self._check_specs(ctx, specs, axes, kw_name,
                                           mesh_label):
                    yield f

    def _check_specs(self, ctx: FileContext, specs: ast.AST,
                     axes: Set[str], kw_name: str,
                     mesh_label: str) -> Iterator[Finding]:
        for node in ast.walk(specs):
            if not isinstance(node, ast.Call):
                continue
            if ctx.qualname(node.func) not in _PSPEC_QUALNAMES:
                continue
            for s in ast.walk(node):
                if isinstance(s, ast.Constant) and \
                        isinstance(s.value, str) and s.value not in axes:
                    yield ctx.finding(
                        s, self.code,
                        f"{kw_name} names axis `{s.value}` but mesh "
                        f"`{mesh_label}` defines axes "
                        f"{sorted(axes)}",
                        hint=self.hint)


# ==========================================================================
# The SRV2xx serving-contract family — WHOLE-PROGRAM rules.
#
# Everything below consumes the ProjectContext fact table
# (core.collect_file_facts / merge_facts): per-file fact collectors
# extract the cross-module ground truth (which attributes hold compiled
# steps, the pooled-carry key schema, the KVPool class hierarchy, the
# finish-reason vocabulary, donation signatures of helper functions),
# the engine merges them across every scanned file, and the rules below
# check each file against the MERGED table.  Single-file scans (the
# fixtures) degrade to per-file facts plus the documented fallbacks.
# ==========================================================================

#: the compiled-step caches in bigdl_tpu.models.transformer; value =
#: index of the step fn in the returned tuple (None = the call's whole
#: result IS the step fn)
_STEP_GETTERS = {
    "bigdl_tpu.models.transformer.get_decode_step": 0,
    "bigdl_tpu.models.transformer.get_batch_decode_step": 0,
    "bigdl_tpu.models.transformer.get_batch_verify_step": 0,
    "bigdl_tpu.models.transformer.get_prefill_step": None,
    "bigdl_tpu.models.transformer.get_batch_prefill_step": None,
}

#: the same steps reached through the model-family seam
#: (serving/family.py): ``family.<method>(...)`` on any receiver
_STEP_METHODS = {
    "decode_step": 0,
    "batch_prefill_step": None,
    "prefill_step": None,
}

#: fallback pooled-carry key schema, used only when the scan does not
#: include models/transformer.py (single-file fixture runs): must match
#: what _serving_init_carry declares
_DEFAULT_CARRY_PATTERNS = (
    "pos", "rng", "tok_counts", "prompt_mask",
    r"k\d+", r"v\d+", r"k\d+_scale", r"v\d+_scale",
)

#: fallback finish-reason vocabulary (single-file fixture runs): must
#: match ServingMetrics.FINISH_REASONS
_DEFAULT_FINISH_REASONS = frozenset(
    {"eos", "stop", "length", "shed", "deadline", "infeasible", "error",
     "cancelled"})

#: fallback serialized row-payload schema (single-file fixture runs):
#: must match serving/disagg.py's ROW_PAYLOAD_KEYS declaration
_DEFAULT_PAYLOAD_KEYS = ("request", "carry", "draft", "chunk_done",
                         "chunk_target", "adapter")

#: KVPool-lineage roots: any class whose base chain reaches a class
#: with one of these qualified-name tails owns pooled device state with
#: host mirrors
_KVPOOL_TAILS = (".KVPool",)


def _last_seg(dotted: Optional[str]) -> Optional[str]:
    return None if dotted is None else dotted.rsplit(".", 1)[-1]


def _in_serving_tree(ctx: FileContext) -> bool:
    return "bigdl_tpu/serving/" in ctx.relpath.replace("\\", "/")


def _serving_scope(ctx: FileContext) -> bool:
    """True for files the serving-contract rules police: the serving
    plane itself, plus any file that imports from it (tests, fixtures,
    a future second engine) — cached per file."""
    hit = ctx.cache.get("serving_scope")
    if hit is None:
        hit = _in_serving_tree(ctx) or any(
            m.startswith("bigdl_tpu.serving")
            or m.startswith("bigdl_tpu.models.transformer")
            for m in _imported_modules(ctx))
        ctx.cache["serving_scope"] = hit
    return hit


def _imported_modules(ctx: FileContext) -> List[str]:
    mods = ctx.cache.get("imported_modules")
    if mods is None:
        mods = []
        for node in ctx.by_type(ast.Import, ast.ImportFrom):
            if isinstance(node, ast.Import):
                mods.extend(a.name for a in node.names)
            elif node.module and node.level == 0:
                mods.append(node.module)
        ctx.cache["imported_modules"] = mods
    return mods


def _facts(ctx: FileContext) -> Dict:
    if ctx.project is not None:
        return ctx.project.facts
    # hand-built context (no engine): per-file facts only
    from bigdl_tpu.analysis.core import collect_file_facts

    return collect_file_facts(ctx)


# -- fact collectors --------------------------------------------------------

def _defines_dispatch(ctx: FileContext) -> bool:
    """True when the file defines a ``_dispatch`` routing of its own —
    the minimal-engine shape SRV201 polices outside bigdl_tpu/serving/."""
    hit = ctx.cache.get("defines_dispatch")
    if hit is None:
        hit = any(fn.name == "_dispatch"
                  for fn in ctx.by_type(ast.FunctionDef,
                                        ast.AsyncFunctionDef))
        ctx.cache["defines_dispatch"] = hit
    return hit


@_register_facts
def _step_binding_facts(ctx: FileContext) -> Dict:
    """Which attribute/variable names hold compiled steps from the
    ``get_*_step`` caches — the SRV201 ground truth.  Collected from
    files that live in dispatch scope (the serving tree, or a file
    with a ``_dispatch`` of its own) and merged, so
    ``eng._batch_prefill_fn`` used in admission.py resolves through the
    binding in engine.py.  Bindings elsewhere (``generate()``/
    ``beam_generate`` in models/, tests, benchmarks) are deliberately
    NOT tracked — their generic names (``step``) would indict every
    method called ``step`` in the engine."""
    if not (_in_serving_tree(ctx) or _defines_dispatch(ctx)):
        return {}
    attrs: Dict[str, List[str]] = {}
    for node in ctx.by_type(ast.Assign):
        if not isinstance(node.value, ast.Call):
            continue
        func = node.value.func
        q = ctx.qualname(func)
        if q in _STEP_GETTERS:
            idx = _STEP_GETTERS[q]
        elif isinstance(func, ast.Attribute) and func.attr in _STEP_METHODS:
            q, idx = f"family.{func.attr}", _STEP_METHODS[func.attr]
        else:
            continue
        for t in node.targets:
            target = t
            if idx is not None:
                if not (isinstance(t, (ast.Tuple, ast.List))
                        and len(t.elts) > idx):
                    continue
                target = t.elts[idx]
            seg = _last_seg(ctx.dotted(target))
            if seg:
                attrs.setdefault(seg, []).append(q)
    return {"step_attrs": {k: sorted(set(v))
                           for k, v in attrs.items()}} if attrs else {}


@_register_facts
def _carry_schema_facts(ctx: FileContext) -> Dict:
    """The pooled-carry key schema, extracted from the ONE layout
    declaration (``_serving_init_carry`` in models/transformer.py):
    constant keys verbatim, f-string keys with interpolations widened
    to ``\\d+`` (the layer index).  SRV202 checks every carry subscript
    against these patterns."""
    for fn in ctx.by_type(ast.FunctionDef):
        if fn.name != "_serving_init_carry":
            continue
        pats: Set[str] = set()
        for node in ast.walk(fn):
            key = None
            if isinstance(node, ast.Assign) and \
                    isinstance(node.targets[0], ast.Subscript):
                key = node.targets[0].slice
            elif isinstance(node, ast.Dict):
                for k in node.keys:
                    p = _key_pattern(k)
                    if p:
                        pats.add(p)
                continue
            p = _key_pattern(key)
            if p:
                pats.add(p)
        if pats:
            return {"carry_patterns": sorted(pats)}
    return {}


@_register_facts
def _row_payload_facts(ctx: FileContext) -> Dict:
    """The serialized row-payload key schema, extracted from the ONE
    wire-format declaration (``ROW_PAYLOAD_KEYS`` in
    serving/disagg.py).  SRV202's payload half checks every subscript
    on a ``payload``-named dict against it — the cross-module twin of
    the carry schema, so a typo'd transfer key is machine-caught
    before it ships a row that restores wrong."""
    from bigdl_tpu.analysis.core import UNRESOLVED as _UNRES
    from bigdl_tpu.analysis.core import literal_value

    for node in ctx.by_type(ast.Assign):
        if any(isinstance(t, ast.Name) and t.id == "ROW_PAYLOAD_KEYS"
               for t in node.targets):
            val = literal_value(node.value)
            if val is not _UNRES:
                return {"payload_keys": sorted(val)}
    return {}


def _key_pattern(node: Optional[ast.AST]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return re.escape(node.value)
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(re.escape(v.value))
            elif isinstance(v, ast.FormattedValue):
                parts.append(r"\d+")
            else:
                return None
        return "".join(parts)
    return None


@_register_facts
def _class_edge_facts(ctx: FileContext) -> Dict:
    """Class-inheritance edges (qualified through each file's imports)
    — SRV203 computes the KVPool lineage from the merged edge set, so
    a subclass two modules away is still covered."""
    edges: Dict[str, List[str]] = {}
    for node in ctx.by_type(ast.ClassDef):
        qual = f"{ctx.module}.{node.name}" if ctx.module else node.name
        bases = []
        for b in node.bases:
            bq = ctx.qualname(b)
            if bq is None:
                d = ctx.dotted(b)
                if d and "." not in d:
                    bq = f"{ctx.module}.{d}" if ctx.module else d
            if bq:
                bases.append(bq)
        edges[qual] = sorted(set(bases))
    return {"class_edges": edges} if edges else {}


@_register_facts
def _finish_reason_facts(ctx: FileContext) -> Dict:
    """The declared finish-reason vocabulary
    (``ServingMetrics.FINISH_REASONS``) — SRV205's schema."""
    from bigdl_tpu.analysis.core import UNRESOLVED as _UNRES
    from bigdl_tpu.analysis.core import literal_value

    for node in ctx.by_type(ast.ClassDef):
        if node.name != "ServingMetrics":
            continue
        for sub in node.body:
            if isinstance(sub, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "FINISH_REASONS"
                    for t in sub.targets):
                val = literal_value(sub.value)
                if val is not _UNRES:
                    return {"finish_reasons": sorted(val)}
    return {}


@_register_facts
def _donated_wrapper_facts(ctx: FileContext) -> Dict:
    """Module-level functions that DONATE one of their parameters (pass
    it at a donated position of a jitted-with-donation callable) —
    SRV204's cross-module half.  Keys are qualified function names;
    values are the donated caller-argument positions."""
    out: Dict[str, List[int]] = {}
    for qual, positions in _donating_wrappers(ctx).items():
        if "." not in qual:           # module-level plain function
            full = f"{ctx.module}.{qual}" if ctx.module else qual
            out[full] = sorted(positions)
    return {"donated_wrappers": out} if out else {}


def _donating_wrappers(ctx: FileContext) -> Dict[str, List[int]]:
    """name -> donated caller-arg positions, for every function in the
    file whose PARAMETER flows into a donated position of a local
    donating callable.  Methods are keyed ``self.<name>`` (positions
    already exclude ``self``); plain functions by bare name.  One level
    of lifting — a wrapper of a wrapper is out of scope (documented)."""
    cached = ctx.cache.get("donating_wrappers")
    if cached is not None:
        return cached
    donated = _donating_callables(ctx)
    wrappers: Dict[str, List[int]] = {}
    if donated:
        for fn in ctx.by_type(ast.FunctionDef, ast.AsyncFunctionDef):
            params = _param_names(fn)
            is_method = bool(params) and params[0] == "self"
            hits: Set[int] = set()
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = ctx.dotted(node.func)
                if callee not in donated:
                    continue
                for i in donated[callee]:
                    if i < len(node.args) and \
                            isinstance(node.args[i], ast.Name) and \
                            node.args[i].id in params:
                        p = params.index(node.args[i].id)
                        if is_method:
                            if p > 0:
                                hits.add(p - 1)
                        else:
                            hits.add(p)
            if hits:
                key = f"self.{fn.name}" if is_method else fn.name
                wrappers[key] = sorted(hits)
    ctx.cache["donating_wrappers"] = wrappers
    return wrappers


# -- SRV201 — dispatch bypass ----------------------------------------------

@register
class DispatchBypassRule(Rule):
    code = "SRV201"
    name = "dispatch-bypass"
    summary = ("compiled serving step invoked directly inside the "
               "serving plane instead of through engine._dispatch")
    hint = ("every serving-path device dispatch must route through "
            "`engine._dispatch(site, fn, *args)` — a direct call "
            "silently bypasses fault injection, the step watchdog, and "
            "retry accounting (serving/faults.py). Spell it "
            "`self._dispatch(\"decode\", self._step_fn, ...)`; tests "
            "and benchmarks outside serving/ may call steps directly")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        # scope: the serving plane itself, or a file that defines a
        # `_dispatch` routing of its own (the fixture/minimal-engine
        # shape) — test/bench code without a dispatcher is exempt
        if not (_in_serving_tree(ctx) or _defines_dispatch(ctx)):
            return
        step_attrs = _facts(ctx).get("step_attrs", {})
        if not step_attrs:
            return
        # local aliases: `fn = self.engine._batch_prefill_fn` makes a
        # bare-name call in the SAME function a bypass too
        aliases: Dict[str, list] = {}
        for node in ctx.by_type(ast.Assign):
            seg = _last_seg(ctx.dotted(node.value)) \
                if isinstance(node.value, (ast.Name, ast.Attribute)) \
                else None
            if seg in step_attrs:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        aliases.setdefault(t.id, []).append(
                            (ctx.enclosing_function(node), seg))
        for node in ctx.by_type(ast.Call):
            seg = None
            if isinstance(node.func, ast.Attribute):
                seg = _last_seg(ctx.dotted(node.func))
                if seg not in step_attrs:
                    continue
            elif isinstance(node.func, ast.Name):
                nm = node.func.id
                if nm in step_attrs:
                    seg = nm
                else:
                    scope = ctx.enclosing_function(node)
                    for ascope, aseg in aliases.get(nm, ()):
                        if ascope is scope:
                            seg = aseg
                            break
                if seg is None:
                    continue
            else:
                continue
            getters = step_attrs.get(seg, ["get_*_step"])
            yield ctx.finding(
                node, self.code,
                f"compiled step `{_last_seg(ctx.dotted(node.func)) or seg}`"
                f" (bound from {getters[0].rsplit('.', 1)[-1]}) invoked "
                f"directly — this dispatch bypasses engine._dispatch",
                hint=self.hint)


# -- SRV202 — carry-key schema ---------------------------------------------

@register
class CarryKeyRule(Rule):
    code = "SRV202"
    name = "carry-key-schema"
    summary = ("string key on a pooled serving carry (or serialized "
               "row payload) that its declared schema does not define")
    hint = ("pooled-carry keys are a CLOSED schema declared once in "
            "models/transformer.py:_serving_init_carry (pos, rng, "
            "tok_counts, prompt_mask, k<i>/v<i> and their _scale rows), "
            "and row-payload keys one declared in serving/disagg.py:"
            "ROW_PAYLOAD_KEYS (request, carry, draft, chunk_done, "
            "chunk_target, adapter) — a typo'd key fails only at "
            "runtime, or "
            "worse, silently creates a NEW key the step (or the "
            "handoff restore) never reads; fix the spelling or extend "
            "the schema declaration first")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not _serving_scope(ctx):
            return
        facts = _facts(ctx)
        carry_pats = facts.get("carry_patterns") or \
            list(_DEFAULT_CARRY_PATTERNS)
        payload_keys = facts.get("payload_keys") or \
            list(_DEFAULT_PAYLOAD_KEYS)
        rx = {
            "carry": re.compile(
                "|".join(f"(?:{p})" for p in carry_pats)),
            "payload": re.compile(
                "|".join(re.escape(k) for k in payload_keys)),
        }
        what = {
            "carry": "the pooled-carry layout declared by "
                     "_serving_init_carry",
            "payload": "the serialized row-payload schema declared by "
                       "ROW_PAYLOAD_KEYS (serving/disagg.py)",
        }
        for node in ctx.by_type(ast.Subscript, ast.Call, ast.Compare):
            recv, key, kind = self._carry_key(ctx, node)
            if recv is None or key is None:
                continue
            if rx[kind].fullmatch(key):
                continue
            noun = "carry" if kind == "carry" else "row payload"
            yield ctx.finding(
                node, self.code,
                f"key {key!r} on {noun} `{recv}` is not in "
                f"{what[kind]}",
                hint=self.hint)

    @staticmethod
    def _carry_key(ctx: FileContext, node: ast.AST):
        """(receiver, key, schema kind) when ``node`` reads/writes a
        string key on a carry-named object (the pooled-carry schema)
        or a ``payload``-named one (the serialized row-payload schema
        — ``KVPool.row_state`` dicts and the disagg wire payloads):
        subscripts, ``.get("k")`` calls, and ``"k" in carry``
        membership tests."""
        if isinstance(node, ast.Subscript):
            recv, key = node.value, node.slice
        elif isinstance(node, ast.Call):
            if not (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "get" and node.args):
                return None, None, None
            recv, key = node.func.value, node.args[0]
        else:                                   # Compare: "k" in carry
            if not (len(node.ops) == 1
                    and isinstance(node.ops[0], (ast.In, ast.NotIn))):
                return None, None, None
            recv, key = node.comparators[0], node.left
        d = ctx.dotted(recv)
        seg = _last_seg(d)
        if seg is None:
            return None, None, None
        if "payload" in seg:
            kind = "payload"
        elif "carry" in seg:
            kind = "carry"
        else:
            return None, None, None
        if not (isinstance(key, ast.Constant)
                and isinstance(key.value, str)):
            return None, None, None
        return d, key.value, kind


# -- SRV203 — host-mirror lockstep -----------------------------------------

@register
class MirrorLockstepRule(Rule):
    code = "SRV203"
    name = "mirror-lockstep"
    summary = ("KVPool-lineage method moves the device `pos` without "
               "updating the chunk_done/chunk_target host mirrors")
    hint = ("KVPool.chunk_done/chunk_target are HOST MIRRORS of the "
            "device `pos` (the chunked-admission pump plans from them "
            "without a device readback — serving/chunked.py); any "
            "method that moves a slot's target-carry pos must keep "
            "them in lockstep (write the mirror, or delegate to "
            "write_prefill/set_pos/begin_chunks/super()). The DRAFT "
            "carry has no mirrors and is exempt")

    #: calls that move pos as a side effect (the donated reset/scatter)
    _POS_MOVERS = {"_free_reset", "_scatter"}
    #: delegating calls that already maintain the mirrors
    _MIRROR_KEEPERS = {"write_prefill", "set_pos", "begin_chunks", "free"}

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        lineage = self._lineage(ctx)
        if not lineage:
            return
        for cls in ctx.by_type(ast.ClassDef):
            qual = f"{ctx.module}.{cls.name}" if ctx.module else cls.name
            if qual not in lineage:
                continue
            for fn in cls.body:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                move = self._first_pos_move(ctx, fn)
                if move is None:
                    continue
                if self._touches_mirror(ctx, fn):
                    continue
                yield ctx.finding(
                    move, self.code,
                    f"{cls.name}.{fn.name} moves the device `pos` but "
                    f"never updates chunk_done/chunk_target — the host "
                    f"mirrors drift from the device state",
                    hint=self.hint)

    @staticmethod
    def _lineage(ctx: FileContext) -> Set[str]:
        """Classes in this PROJECT whose base chain reaches KVPool,
        computed from the merged class-edge facts (cross-module)."""
        edges = _facts(ctx).get("class_edges", {})
        out: Set[str] = set()
        for qual in edges:
            chain, todo = set(), [qual]
            while todo:
                q = todo.pop()
                if q in chain:
                    continue
                chain.add(q)
                todo.extend(edges.get(q, ()))
            if any(q.endswith(t) or q == t.lstrip(".")
                   for q in chain for t in _KVPOOL_TAILS):
                out.add(qual)
        return out

    def _first_pos_move(self, ctx: FileContext,
                        fn: ast.AST) -> Optional[ast.AST]:
        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    if isinstance(t, ast.Subscript) and \
                            ctx.dotted(t.value) == "self.carry" and \
                            isinstance(t.slice, ast.Constant) and \
                            t.slice.value == "pos":
                        return t
            elif isinstance(node, ast.Call):
                seg = _last_seg(ctx.dotted(node.func))
                if seg in self._POS_MOVERS and \
                        ctx.dotted(node.func) == f"self.{seg}":
                    return node
        return None

    def _touches_mirror(self, ctx: FileContext, fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and \
                    node.attr in ("chunk_done", "chunk_target") and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id == "self":
                return True
            if isinstance(node, ast.Call):
                d = ctx.dotted(node.func)
                seg = _last_seg(d)
                if seg in self._MIRROR_KEEPERS and d != f"self.{fn.name}" \
                        and (d or "").startswith("self."):
                    return True
                # super().free(...) etc. delegates the whole contract
                if isinstance(node.func, ast.Attribute) and \
                        isinstance(node.func.value, ast.Call) and \
                        isinstance(node.func.value.func, ast.Name) and \
                        node.func.value.func.id == "super":
                    return True
        return False


# -- SRV204 — interprocedural donation reuse -------------------------------

@register
class CrossDonationRule(Rule):
    code = "SRV204"
    name = "cross-donation-reuse"
    summary = ("buffer donated through a helper function (the helper "
               "passes its parameter to a donating jit) and read again "
               "by the caller")
    hint = ("SPMD104 lifted through the call graph: the helper's "
            "parameter flows into a `donate_argnums` position, so the "
            "CALLER's buffer is invalid after the helper returns — "
            "rebind the name to the helper's result (`carry = "
            "ingest(carry, u)`), exactly like the direct-donation "
            "idiom. One level of lifting; wrappers of wrappers are out "
            "of scope (docs/analysis.md)")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        local = _donating_wrappers(ctx)
        xmod = _facts(ctx).get("donated_wrappers", {})
        if not local and not xmod:
            return
        for node in ctx.by_type(ast.Call):
            callee = ctx.dotted(node.func)
            if callee is None:
                continue
            positions = local.get(callee)
            label = callee
            if positions is None:
                q = ctx.qualname(node.func)
                if q:
                    hit = xmod.get(q)
                    if hit is None:
                        # module keys are path-derived; the import may
                        # spell a shorter (or sys.path-rooted) prefix —
                        # match on the dotted suffix
                        for k, v in xmod.items():
                            if k.endswith("." + q):
                                hit, q = v, k
                                break
                    if hit is not None:
                        positions, label = hit, q
            if not positions:
                continue
            # the wrapper's own body is exempt (that call is the
            # definition site, already modeled)
            scope = ctx.enclosing_function(node) or ctx.tree
            if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                params = _param_names(scope)
                key = f"self.{scope.name}" if params[:1] == ["self"] \
                    else scope.name
                if key == callee:
                    continue
            for i in positions:
                if i >= len(node.args):
                    continue
                buf = ctx.dotted(node.args[i])
                if buf is None or buf == "self":
                    continue
                reuse = _first_reuse(ctx, scope, buf, node)
                if reuse is not None:
                    yield ctx.finding(
                        reuse, self.code,
                        f"`{buf}` was donated THROUGH `{label}` on line "
                        f"{node.lineno} (its parameter {i} flows into a "
                        f"donate_argnums position) and is read again "
                        f"here",
                        hint=self.hint)


# -- SRV205 — finish-reason accounting -------------------------------------

@register
class FinishReasonRule(Rule):
    code = "SRV205"
    name = "finish-reason-accounting"
    summary = ("finish_reason string outside the declared "
               "ServingMetrics.FINISH_REASONS vocabulary")
    hint = ("finish reasons are a CLOSED vocabulary declared by "
            "ServingMetrics.FINISH_REASONS, and every reason has a "
            "per-reason counter path (serving/finish_<reason> via "
            "on_finish_reason) — a novel string silently escapes "
            "goodput/shed accounting and dashboards. Fix the typo, or "
            "add the reason to FINISH_REASONS + its counter first")

    #: call sites that consume a reason string: final segment -> the
    #: positional index of the reason argument
    _REASON_CALLS = {"_shed": 1, "_finish_row": 1, "on_finish_reason": 0}

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not _serving_scope(ctx):
            return
        vocab = _facts(ctx).get("finish_reasons")
        vocab = set(vocab) if vocab else set(_DEFAULT_FINISH_REASONS)
        for node in ctx.by_type(ast.Assign, ast.Call):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Attribute) and \
                            t.attr == "finish_reason" and \
                            isinstance(node.value, ast.Constant) and \
                            isinstance(node.value.value, str) and \
                            node.value.value not in vocab:
                        yield ctx.finding(
                            node, self.code,
                            f"finish_reason {node.value.value!r} is not "
                            f"in ServingMetrics.FINISH_REASONS "
                            f"{sorted(vocab)}",
                            hint=self.hint)
                        break
                continue
            seg = _last_seg(ctx.dotted(node.func))
            idx = self._REASON_CALLS.get(seg or "")
            if idx is None:
                continue
            arg = node.args[idx] if idx < len(node.args) else \
                _kwarg(node, "reason")
            if isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str) and arg.value not in vocab:
                yield ctx.finding(
                    node, self.code,
                    f"reason {arg.value!r} passed to {seg}() is not in "
                    f"ServingMetrics.FINISH_REASONS {sorted(vocab)}",
                    hint=self.hint)


# -- SRV206 — stranded rows -------------------------------------------------

@register
class StrandedRowRule(Rule):
    code = "SRV206"
    name = "stranded-row"
    summary = ("row removed from a scheduler table with no requeue, "
               "handoff, or finish disposition in scope")
    hint = ("every code path that takes a request out of a pool's "
            "running/partial tables must leave it SOMEWHERE: "
            "requeue/submit it back into a scheduler, serialize it "
            "for handoff (row_state / pack_payload), or land a "
            "FINISH_REASONS disposition (_finish_row/_ledger_finish/"
            "_shed/on_finish_reason/finish/cancel) — the static twin "
            "of the pool-failover invariant (docs/serving.md \"Pool "
            "failover and autoscaling\"). A row that silently leaves "
            "the tables strands its request: drain() never finishes "
            "it and no finish_<reason> counter accounts for it. The "
            "scheduler's own primitives (the class that OWNS the "
            "tables) are the sanctioned removal spellings and are "
            "exempt")

    #: the slot-holding scheduler tables the invariant covers (the
    #: waiting heap has its own closed drop surface — pop_waiting —
    #: inside the owning class)
    _TABLES = ("running", "partial")
    #: removal spellings on a table receiver
    _REMOVERS = ("pop", "clear", "popitem")
    #: calls that give the removed row a destination: scheduler
    #: re-entry, handoff serialization, or a finish disposition
    _KEEPERS = {"requeue", "submit", "row_state", "pack_payload",
                "finish", "_finish_row", "_ledger_finish", "_shed",
                "on_finish_reason", "cancel_running", "cancel"}

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not _serving_scope(ctx):
            return
        for node in ctx.by_type(ast.Delete, ast.Call):
            hit = self._removal(ctx, node)
            if hit is None:
                continue
            table, recv = hit
            fn = ctx.enclosing_function(node)
            if fn is None:
                continue                 # module-level = fixture setup
            if recv == f"self.{table}" and self._class_owns_tables(ctx,
                                                                   node):
                continue                 # the owner's primitives
            if self._has_keeper(ctx, fn, node):
                continue
            verb = "del" if isinstance(node, ast.Delete) else \
                f".{node.func.attr}()"
            yield ctx.finding(
                node, self.code,
                f"row removed from `{recv}` ({verb}) with no "
                f"requeue/submit, row_state/pack_payload handoff, or "
                f"finish disposition in "
                f"`{getattr(fn, 'name', '<lambda>')}` — the request "
                f"is stranded",
                hint=self.hint)

    def _removal(self, ctx: FileContext,
                 node: ast.AST) -> Optional[Tuple[str, str]]:
        """(table, receiver-spelling) when ``node`` removes from a
        running/partial table: ``del <x>.running[...]`` or
        ``<x>.running.pop/clear/popitem(...)``."""
        if isinstance(node, ast.Delete):
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    recv = ctx.dotted(t.value)
                    table = self._table_of(recv)
                    if table is not None:
                        return table, recv
            return None
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in self._REMOVERS:
            recv = ctx.dotted(node.func.value)
            table = self._table_of(recv)
            if table is not None:
                return table, recv
        return None

    def _table_of(self, recv: Optional[str]) -> Optional[str]:
        if recv is None:
            return None
        for table in self._TABLES:
            if recv == table or recv.endswith("." + table):
                return table
        return None

    def _class_owns_tables(self, ctx: FileContext,
                           node: ast.AST) -> bool:
        """Is ``node`` inside a class whose own body assigns
        ``self.running`` (the Scheduler shape)? Its methods ARE the
        sanctioned removal primitives."""
        cur = ctx.parents.get(node)
        while cur is not None and not isinstance(cur, ast.ClassDef):
            cur = ctx.parents.get(cur)
        if cur is None:
            return False
        for sub in ast.walk(cur):
            if isinstance(sub, ast.Assign):
                targets = sub.targets
            elif isinstance(sub, ast.AnnAssign):   # self.running: Dict
                targets = [sub.target]
            else:
                continue
            for t in targets:
                if isinstance(t, ast.Attribute) and \
                        t.attr in self._TABLES and \
                        isinstance(t.value, ast.Name) and \
                        t.value.id == "self":
                    return True
        return False

    def _has_keeper(self, ctx: FileContext, fn: ast.AST,
                    removal: ast.AST) -> bool:
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call) and sub is not removal:
                seg = _last_seg(ctx.dotted(sub.func))
                if seg in self._KEEPERS:
                    return True
        return False


# -- SRV207 — tier-codec bypass ---------------------------------------------

@register
class TierCodecBypassRule(Rule):
    code = "SRV207"
    name = "tier-codec-bypass"
    summary = ("row state written to a block store outside the "
               "row_state()/pack_payload codec, or device state read "
               "from a slot already freed (spilled)")
    hint = ("the host KV tier has exactly ONE wire format: a row "
            "leaves HBM as `pack_payload(request_meta(req), "
            "pool.row_state(slot))` bytes, and comes back through "
            "`unpack_payload` + `restore_row` (docs/serving.md "
            "\"Tiered KV\"). A raw row_state dict (or anything tainted "
            "by one) written into a block store skips the length-"
            "prefixed codec — the bytes are unreadable by every fetch "
            "path and the byte-identity contract silently dies. And a "
            "`pool.free(slot)` BEFORE `row_state(slot)` serializes a "
            "recycled row: spill captures whatever request owns the "
            "slot next. Pack first, free after — the order every "
            "shipping site (preemption, handoff, drain) already "
            "follows. Wrapper detection is one level deep: a helper "
            "whose parameter flows into a store `.put()` counts as a "
            "store write at its call sites")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not _serving_scope(ctx):
            return
        wrappers = self._store_put_wrappers(ctx)
        for fn in ctx.by_type(ast.FunctionDef, ast.AsyncFunctionDef):
            tainted, sanitized = self._taints(ctx, fn)
            yield from self._raw_store_writes(ctx, fn, tainted,
                                              sanitized, wrappers)
            yield from self._freed_slot_reads(ctx, fn)

    # -- taint bookkeeping (per function, flow-insensitive) ---------------

    @staticmethod
    def _params(fn: ast.AST) -> List[str]:
        a = fn.args
        return [p.arg for p in (getattr(a, "posonlyargs", []) + a.args
                                + a.kwonlyargs)]

    def _taints(self, ctx: FileContext,
                fn: ast.AST) -> Tuple[Set[str], Set[str]]:
        """(tainted, sanitized) local names: tainted = carries a raw
        row-state payload (a ``payload``-named parameter, a
        ``row_state()`` result, or a copy of either); sanitized =
        assigned from ``pack_payload()`` (the codec's output is the
        ONLY sanctioned store content)."""
        tainted = {p for p in self._params(fn)
                   if p == "payload" or p.endswith("_payload")}
        sanitized: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for sub in ast.walk(fn):
                if not (isinstance(sub, ast.Assign)
                        and len(sub.targets) == 1
                        and isinstance(sub.targets[0], ast.Name)):
                    continue
                tgt, v = sub.targets[0].id, sub.value
                if isinstance(v, ast.Call):
                    seg = _last_seg(ctx.dotted(v.func))
                    if seg == "row_state" and tgt not in tainted:
                        tainted.add(tgt)
                        changed = True
                    elif seg == "pack_payload" and tgt not in sanitized:
                        sanitized.add(tgt)
                        changed = True
                elif isinstance(v, ast.Name) and v.id in tainted \
                        and tgt not in tainted:
                    tainted.add(tgt)
                    changed = True
        return tainted, sanitized

    # -- sink 1: un-coded writes into a block store -----------------------

    def _store_put_wrappers(self, ctx: FileContext) -> Dict[str, Set[int]]:
        """Function name -> positional indices (self excluded) whose
        argument flows into a ``<...store>.put(...)`` call inside the
        function body — one level of lifting, like SRV204."""
        out: Dict[str, Set[int]] = {}
        for fn in ctx.by_type(ast.FunctionDef, ast.AsyncFunctionDef):
            params = self._params(fn)
            offset = 1 if params[:1] == ["self"] else 0
            for sub in ast.walk(fn):
                if not (isinstance(sub, ast.Call)
                        and self._is_store_put(ctx, sub)):
                    continue
                for arg in sub.args:
                    if isinstance(arg, ast.Name) and arg.id in params:
                        i = params.index(arg.id) - offset
                        if i >= 0:
                            out.setdefault(fn.name, set()).add(i)
        return out

    @staticmethod
    def _is_store_put(ctx: FileContext, call: ast.Call) -> bool:
        if not (isinstance(call.func, ast.Attribute)
                and call.func.attr == "put"):
            return False
        recv = ctx.dotted(call.func.value)
        seg = _last_seg(recv)
        return bool(seg) and "store" in seg.lower()

    def _raw_store_writes(self, ctx: FileContext, fn: ast.AST,
                          tainted: Set[str], sanitized: Set[str],
                          wrappers: Dict[str, Set[int]]
                          ) -> Iterator[Finding]:
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call) or \
                    ctx.enclosing_function(sub) is not fn:
                continue
            if self._is_store_put(ctx, sub):
                bad_args = [a for a in sub.args[1:]]      # skip the key
            else:
                seg = _last_seg(ctx.dotted(sub.func))
                positions = wrappers.get(seg or "")
                # the wrapper's own body is the modeled definition site
                if positions is None or seg == fn.name:
                    continue
                bad_args = [sub.args[i] for i in positions
                            if i < len(sub.args)]
            for arg in bad_args:
                if isinstance(arg, ast.Name) and arg.id in tainted \
                        and arg.id not in sanitized:
                    yield ctx.finding(
                        sub, self.code,
                        f"`{arg.id}` carries a raw row_state payload "
                        f"and is written into a block store without "
                        f"passing through pack_payload — the tier's "
                        f"fetch paths cannot decode it",
                        hint=self.hint)

    # -- sink 2: row_state after free (spilled-slot device read) ----------

    def _freed_slot_reads(self, ctx: FileContext,
                          fn: ast.AST) -> Iterator[Finding]:
        freed: Dict[str, int] = {}
        for sub in ast.walk(fn):
            if not (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and len(sub.args) == 1
                    and isinstance(sub.args[0], ast.Name)
                    and ctx.enclosing_function(sub) is fn):
                continue
            name = sub.args[0].id
            if sub.func.attr == "free":
                freed.setdefault(name, sub.lineno)
            elif sub.func.attr == "row_state" and name in freed \
                    and freed[name] < sub.lineno:
                yield ctx.finding(
                    sub, self.code,
                    f"row_state(`{name}`) on line {sub.lineno} reads a "
                    f"slot freed on line {freed[name]} — the slot may "
                    f"already be recycled; serialize BEFORE freeing",
                    hint=self.hint)


# -- SRV208 — undeclared actuation ------------------------------------------

#: the serving plane's runtime CONTROL KNOBS — per-row / per-admitter
#: host fields the autopilot's actuator bus owns. An attribute WRITE to
#: one of these outside the declared ACTUATION_SITES (or a constructor)
#: is an undeclared actuation: it moves a knob the audit log never sees
_KNOB_ATTRS = frozenset({"chunk_budget", "max_new_tokens",
                         "draft_tokens", "draft_cap", "degrade_at",
                         "degraded"})
#: pool lifecycle transitions — actuations spelled as CALLS, not writes
_KNOB_CALLS = frozenset({"_activate_pool", "drain_pool"})
#: fallback ACTUATION_SITES vocabulary (single-file fixture runs): must
#: match serving/autopilot.py ACTUATION_SITES
_DEFAULT_ACTUATION_SITES = frozenset({
    "autopilot.ActuatorBus.set_chunk_budget",
    "autopilot.ActuatorBus.set_draft_cap",
    "autopilot.ActuatorBus.degrade_waiting",
    "autopilot.ActuatorBus.restore_waiting",
    "engine.ServingEngine._apply_degrade",
    "engine.ServingEngine._restore_degrade",
    "disagg.DisaggregatedEngine._autoscale",
    "disagg.DisaggregatedEngine._failover_pool",
})


@_register_facts
def _actuation_site_facts(ctx: FileContext) -> Dict:
    """The declared actuator vocabulary (``ACTUATION_SITES``) —
    SRV208's ground truth, extracted the way MH403 reads CLOCK_SITES."""
    for node in ctx.by_type(ast.Assign):
        if not any(isinstance(t, ast.Name) and t.id == "ACTUATION_SITES"
                   for t in node.targets):
            continue
        val = literal_value(node.value)
        if val is not UNRESOLVED:
            return {"actuation_sites": sorted(val)}
    return {}


def _actuation_sites(ctx: FileContext) -> Set[str]:
    sites = _facts(ctx).get("actuation_sites")
    return set(sites) if sites else set(_DEFAULT_ACTUATION_SITES)


@register
class UndeclaredActuationRule(Rule):
    code = "SRV208"
    name = "undeclared-actuation"
    summary = ("serving control knob mutated (chunk_budget / degrade "
               "fields / draft cap / pool activate-drain) outside the "
               "declared ACTUATION_SITES vocabulary")
    hint = ("every runtime knob the control plane moves — the chunked "
            "admitter's budget, a request's degrade fields, the "
            "speculative draft cap, pool activation/drain — goes "
            "through the declared actuator API "
            "(serving/autopilot.py ACTUATION_SITES, the FENCE_SITES / "
            "CLOCK_SITES pattern), so every actuation lands in the "
            "bus's audit log and hysteresis owns the cadence. A knob "
            "assigned anywhere else is an invisible actuation: it "
            "fights the controllers, skips the log, and breaks the "
            "replay story. Route it through ActuatorBus (or the "
            "engine's _apply_degrade/_restore_degrade), or — for a "
            "genuinely new actuator — add its unit to ACTUATION_SITES "
            "first (a reviewable one-line diff). Constructors are "
            "exempt: setting a knob's INITIAL value is configuration, "
            "not actuation")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not (_in_serving_tree(ctx) or _defines_dispatch(ctx)):
            return
        sites = _actuation_sites(ctx)

        def undeclared(node) -> Optional[str]:
            """The enclosing unit's qualname when the node sits outside
            every declared site (None = sanctioned). Module/class-body
            statements (dataclass field defaults) are declarations, not
            actuations, and constructors set initial values."""
            unit = enclosing_unit(ctx, node)
            if unit is None:
                return None
            uq = unit[0]
            if uq.rsplit(".", 1)[-1] in ("__init__", "__post_init__"):
                return None
            if any(uq == s or uq.endswith("." + s) for s in sites):
                return None
            return uq

        for node in ctx.by_type(ast.Assign, ast.AnnAssign, ast.AugAssign):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for tgt in targets:
                if not (isinstance(tgt, ast.Attribute)
                        and tgt.attr in _KNOB_ATTRS):
                    continue
                uq = undeclared(node)
                if uq is not None:
                    yield ctx.finding(
                        node, self.code,
                        f"control knob `.{tgt.attr}` assigned in "
                        f"`{uq}` — outside the declared "
                        f"ACTUATION_SITES vocabulary",
                        hint=self.hint)
        for node in ctx.by_type(ast.Call):
            if not (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _KNOB_CALLS):
                continue
            uq = undeclared(node)
            if uq is not None:
                yield ctx.finding(
                    node, self.code,
                    f"pool lifecycle actuation `.{node.func.attr}()` "
                    f"in `{uq}` — outside the declared "
                    f"ACTUATION_SITES vocabulary",
                    hint=self.hint)


# ==========================================================================
# The ASY3xx async-readiness family — HOT-PATH host-sync rules.
#
# The async dispatch-ahead refactor (ROADMAP "raw speed") needs the
# super-step loop to stop forcing device→host syncs it never declared.
# These rules machine-inventory every such sync: ASY301 implicit
# readbacks, ASY302 raw block_until_ready / fence-vocabulary drift,
# ASY303 host branches on un-fenced device values, ASY304 per-iteration
# readback accumulation, ASY305 wall-clock pairs timing un-fenced
# device work. All of them apply ONLY to functions reachable from the
# serving plane's hot-path roots through the merged call-graph facts
# (core.hotpath_chains) — benches, tests, and setup/teardown code are
# exempt by REACHABILITY, not by path glob. The one idiom a deliberate
# sync may wear is serving/fences.py (fence = one batched device_get,
# fence_wait = block_until_ready for timers); the rules extract its
# module + site vocabulary as facts, so the fence sites the async
# refactor will move are born machine-checked.
# ==========================================================================

#: fallback fence-site vocabulary (single-file fixture runs): must
#: match serving/fences.py FENCE_SITES
_DEFAULT_FENCE_SITES = frozenset({"decode", "verify", "draft", "prefill"})
#: host-crossing cast builtins (one positional arg = the readback shape)
_READBACK_CASTS = frozenset({"float", "int", "bool"})
#: numpy conversions that force a device value across (jnp.asarray is
#: the host→device UPLOAD and deliberately absent)
_NP_READBACK_QUALS = frozenset({"numpy.asarray", "numpy.array"})
_DEVICE_GET_QUALS = frozenset({"jax.device_get"})
_BLOCK_READY_NAME = "block_until_ready"
#: wall-clock sources (plus any `*._clock()` callable attribute — the
#: engine's injectable clock)
_CLOCK_QUALS = frozenset({"time.time", "time.perf_counter",
                          "time.monotonic", "time.process_time"})
#: calls whose RESULT lives on device: the engine's fault-routing
#: dispatcher and the pool's row slice; compiled-step attrs come from
#: the SRV201 step_attrs fact, jax factories from their qualnames
_DEVICE_CALL_SEGS = frozenset({"_dispatch", "read_row"})
_DEVICE_FACTORY_PREFIXES = ("jax.numpy.", "jax.random.", "jax.lax.")


@_register_facts
def _fence_facts(ctx: FileContext) -> Dict:
    """The declared fence-site vocabulary (``FENCE_SITES``) and the
    module that declares it — ASY301/302's ground truth, extracted the
    way SRV205 reads FINISH_REASONS."""
    for node in ctx.by_type(ast.Assign):
        if not any(isinstance(t, ast.Name) and t.id == "FENCE_SITES"
                   for t in node.targets):
            continue
        val = literal_value(node.value)
        if val is not UNRESOLVED:
            return {"fence_sites": sorted(val),
                    "fence_modules": [ctx.module]}
    return {}


def _is_fence_module(ctx: FileContext) -> bool:
    """True for the file that DECLARES the fence idiom — the one module
    allowed to spell device_get/block_until_ready raw (the compat-shim
    pattern)."""
    hit = ctx.cache.get("is_fence_module")
    if hit is None:
        hit = any(
            isinstance(t, ast.Name) and t.id == "FENCE_SITES"
            for node in ctx.by_type(ast.Assign) for t in node.targets)
        ctx.cache["is_fence_module"] = hit
    return hit


def _fence_call_kind(ctx: FileContext,
                     call: ast.Call) -> Optional[str]:
    """``"fence"``/``"fence_wait"`` when ``call`` resolves to the
    declared fence module's idiom (fallback when the fact is absent —
    single-file runs: any module spelled ``...fences``)."""
    q = ctx.qualname(call.func)
    if not q:
        return None
    mod, _, name = q.rpartition(".")
    if name not in ("fence", "fence_wait"):
        return None
    mods = _facts(ctx).get("fence_modules")
    if mods:
        if mod in mods or any(m.endswith("." + mod) or
                              mod.endswith("." + m) for m in mods):
            return name
        return None
    return name if mod.rsplit(".", 1)[-1] == "fences" else None


def _fence_sites(ctx: FileContext) -> Set[str]:
    sites = _facts(ctx).get("fence_sites")
    return set(sites) if sites else set(_DEFAULT_FENCE_SITES)


#: fallbacks for single-file fixture runs — must match serving/fences.py
_DEFAULT_WINDOW_KNOBS = frozenset({"dispatch_ahead"})
_DEFAULT_DELAYED_SITES = frozenset({"decode"})


@_register_facts
def _window_facts(ctx: FileContext) -> Dict:
    """The declared dispatch-ahead vocabulary — ``WINDOW_KNOBS`` (the
    engine knobs a window-depth guard may reference, ASY308's ground
    truth) and ``DELAYED_CONSUMER_SITES`` (the fence sites allowed to
    sit behind the window, ASY306/309's ground truth) — extracted from
    the fence module the way :func:`_fence_facts` reads FENCE_SITES."""
    out: Dict = {}
    for node in ctx.by_type(ast.Assign):
        for t in node.targets:
            if not isinstance(t, ast.Name):
                continue
            if t.id == "WINDOW_KNOBS":
                val = literal_value(node.value)
                if val is not UNRESOLVED:
                    out["window_knobs"] = sorted(val)
            elif t.id == "DELAYED_CONSUMER_SITES":
                val = literal_value(node.value)
                if val is not UNRESOLVED:
                    out["delayed_sites"] = sorted(val)
    return out


def _window_knobs(ctx: FileContext) -> Set[str]:
    v = _facts(ctx).get("window_knobs")
    return set(v) if v else set(_DEFAULT_WINDOW_KNOBS)


def _delayed_sites(ctx: FileContext) -> Set[str]:
    v = _facts(ctx).get("delayed_sites")
    return set(v) if v else set(_DEFAULT_DELAYED_SITES)


def _is_window_pop(call: ast.Call) -> bool:
    """``<recv>.popleft()`` / ``<recv>.pop(0)`` — the delayed
    consumer's oldest-first take from a window collection."""
    f = call.func
    if not isinstance(f, ast.Attribute):
        return False
    if f.attr == "popleft" and not call.args:
        return True
    return (f.attr == "pop" and len(call.args) == 1
            and isinstance(call.args[0], ast.Constant)
            and call.args[0].value == 0)


def _window_collections(ctx: FileContext) -> Set[str]:
    """Dotted receivers that ARE dispatch-ahead window collections in
    this file: something a hot unit ``.append``s DEVICE-tainted values
    into AND something is ``popleft()``/``pop(0)``ed from (the
    producer/consumer pair). Requiring the pop side keeps plain
    device-handle accumulators — the speculative plane's draft chain
    list, metric buffers — out: a window is a queue, not a list."""
    hit = ctx.cache.get("asy_window_colls")
    if hit is not None:
        return hit
    appended: Set[str] = set()
    for _qual, fn, _chain in _hot_units(ctx):
        scan = _asy_scan(ctx, fn)
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "append" and node.args):
                continue
            recv = ctx.dotted(node.func.value)
            if not recv:
                continue
            if any(_taint_use(ctx, a, scan.tainted_at(node.lineno))
                   for a in node.args):
                appended.add(recv)
    popped: Set[str] = set()
    if appended:
        for node in ctx.by_type(ast.Call):
            if _is_window_pop(node):
                recv = ctx.dotted(node.func.value)
                if recv:
                    popped.add(recv)
    hit = appended & popped
    ctx.cache["asy_window_colls"] = hit
    return hit


def _unit_window_role(ctx: FileContext, fn: ast.AST,
                      colls: Set[str]) -> Tuple[bool, bool]:
    """``(owns, consumes)`` for one unit: owns = appends to a window
    collection (the dispatch side), consumes = pops one (the delayed-
    consumer side). The ASY306-310 rules scope by these roles."""
    owns = consumes = False
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        recv = ctx.dotted(node.func.value)
        if recv not in colls:
            continue
        if node.func.attr == "append":
            owns = True
        elif _is_window_pop(node):
            consumes = True
    return owns, consumes


def _carry_seg(name: str) -> bool:
    """Names/attributes that ARE pooled device state by the serving
    plane's naming convention: ``carry``, ``dcarry``, ``draft_carry``,
    ``resume_carry``, ``prefill_carry``, ``_zero_carry``..."""
    return name.endswith("carry")


def _step_attr_segs(ctx: FileContext) -> Set[str]:
    segs = ctx.cache.get("asy_step_segs")
    if segs is None:
        segs = set(_facts(ctx).get("step_attrs", {}).keys())
        ctx.cache["asy_step_segs"] = segs
    return segs


def _device_call(ctx: FileContext, call: ast.Call) -> bool:
    """Calls whose result is a device value."""
    f = call.func
    if isinstance(f, (ast.Name, ast.Attribute)):
        seg = _last_seg(ctx.dotted(f))
        if seg in _DEVICE_CALL_SEGS or seg in _step_attr_segs(ctx):
            return True
    q = ctx.qualname(f)
    return bool(q) and (q.startswith(_DEVICE_FACTORY_PREFIXES)
                        or q == "jax.device_put")


def _readback_kind(ctx: FileContext, call: ast.Call) -> Optional[str]:
    """``"cast"``/``"item"``/``"np"``/``"device_get"`` when ``call`` is
    a host-crossing readback OPERATION (taint of its argument decides
    whether it is a finding)."""
    f = call.func
    if isinstance(f, ast.Name) and f.id in _READBACK_CASTS \
            and len(call.args) == 1 and not call.keywords:
        return "cast"
    if isinstance(f, ast.Attribute) and f.attr == "item" \
            and not call.args:
        return "item"
    q = ctx.qualname(f)
    if q in _NP_READBACK_QUALS:
        return "np"
    if q in _DEVICE_GET_QUALS:
        return "device_get"
    return None


def _taint_use(ctx: FileContext, expr: ast.AST,
               tainted: Set[str]) -> Optional[ast.AST]:
    """First DYNAMIC use of a device value in ``expr``: a tainted name,
    a carry-suffixed name/attribute, or a device-producing call. Static
    accessors (``x.shape``, ``len``, ``is None``) never count, and
    fence/readback calls are boundaries — their results are host
    values, judged at their own call sites."""
    out: List[ast.AST] = []

    def visit(node: ast.AST, static: bool) -> None:
        if out:
            return
        if isinstance(node, ast.Name):
            if not static and (node.id in tainted or _carry_seg(node.id)):
                out.append(node)
            return
        if isinstance(node, ast.Attribute):
            if not static and _carry_seg(node.attr):
                out.append(node)
                return
            visit(node.value, static or node.attr in _STATIC_ATTRS)
            return
        if isinstance(node, ast.Call):
            if _fence_call_kind(ctx, node) or _readback_kind(ctx, node):
                return
            if _device_call(ctx, node):
                if not static:
                    out.append(node)
                return
            fname = node.func.id if isinstance(node.func, ast.Name) \
                else None
            inner_static = static or fname in _STATIC_CALLS
            for child in list(node.args) + \
                    [kw.value for kw in node.keywords]:
                visit(child, inner_static)
            if not isinstance(node.func, ast.Name):
                visit(node.func, static)
            return
        if isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot))
                   for op in node.ops):
                for child in [node.left] + list(node.comparators):
                    visit(child, True)
                return
            if all(isinstance(op, (ast.In, ast.NotIn))
                   for op in node.ops):
                # key membership ("rng" in carry) inspects the carry
                # DICT's structure on host — never a device sync; only
                # the probed value itself can be one
                visit(node.left, static)
                for child in node.comparators:
                    visit(child, True)
                return
        for child in ast.iter_child_nodes(node):
            visit(child, static)

    visit(expr, False)
    return out[0] if out else None


def _hot_chains(ctx: FileContext) -> Dict[str, Tuple[str, ...]]:
    """unit qual -> root chain, for every unit reachable from a
    hot-path root (project-memoized — one BFS per analyzer run)."""
    proj = ctx.project
    if proj is not None:
        hit = proj.cache.get("hotpath_chains")
        if hit is None:
            hit = proj.cache["hotpath_chains"] = hotpath_chains(
                proj.facts)
        return hit
    return hotpath_chains(_facts(ctx))


def _target_names_of(target: ast.AST) -> List[str]:
    """Plain names bound by an assignment/loop target (tuple/list
    destructuring included) — shared by the ASY device-taint and MH
    divergence-taint timelines."""
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in target.elts:
            out.extend(_target_names_of(e))
        return out
    return []


def _taint_state_at(events: Dict[str, List[Tuple[int, bool]]],
                    line: int) -> Set[str]:
    """Names whose last taint event at or before ``line`` is True —
    the one timeline-replay rule both taint scans share."""
    out: Set[str] = set()
    for name, evs in events.items():
        state = False
        for ln, val in evs:
            if ln > line:
                break
            state = val
        if state:
            out.add(name)
    return out


class _AsyScan:
    """One shared pass over a hot unit: the device-taint timeline, the
    readback/fence/dispatch/clock inventories, and the loop-accumulation
    claims — every ASY rule reads this instead of re-walking."""

    def __init__(self, ctx: FileContext, fn: ast.AST) -> None:
        self.ctx = ctx
        self.fn = fn
        #: name -> [(line, tainted_bool)] in line order
        self.events: Dict[str, List[Tuple[int, bool]]] = {}
        #: lines of super-step device dispatches (_dispatch / step attrs)
        self.dispatch_lines: List[int] = []
        #: lines where the pending device work is SYNCED (fences,
        #: block_until_ready, readbacks of tainted values)
        self.sync_lines: List[int] = []
        #: (call node, kind, site literal or None) for fence idiom calls
        self.fences: List[Tuple[ast.Call, str, Optional[str]]] = []
        #: (call node, kind, offending use) readback candidates
        self.readbacks: List[Tuple[ast.Call, str, Optional[ast.AST]]] = []
        #: block_until_ready call nodes
        self.blocks: List[ast.AST] = []
        #: clock-call assignment targets: name -> [assign lines]
        self.clock_assigns: Dict[str, List[int]] = {}
        #: loads of clock targets: (node, name, line)
        self.clock_loads: List[Tuple[ast.AST, str, int]] = []
        #: node ids of readbacks claimed by loop accumulation (ASY304)
        self.accum_claimed: Set[int] = set()
        #: (accumulation node, inner readback call) ASY304 findings
        self.accumulations: List[Tuple[ast.AST, ast.Call]] = []
        self._build()

    # -- taint timeline (shared replay rule: _taint_state_at) ---------------

    def tainted_at(self, line: int) -> Set[str]:
        return _taint_state_at(self.events, line)

    def _target_names(self, target: ast.AST) -> List[str]:
        return _target_names_of(target)

    def _build(self) -> None:
        ctx = self.ctx
        cur: Set[str] = set()

        def mark(names: List[str], line: int, val: bool) -> None:
            for n in names:
                if val:
                    cur.add(n)
                elif n in cur:
                    cur.discard(n)
                else:
                    continue
                self.events.setdefault(n, []).append((line, val))

        stmts = sorted(
            (n for n in ast.walk(self.fn)
             if isinstance(n, (ast.Assign, ast.AnnAssign, ast.AugAssign,
                               ast.For, ast.Call, ast.Name, ast.If,
                               ast.While))),
            key=lambda n: (getattr(n, "lineno", 0),
                           getattr(n, "col_offset", 0)))
        clock_targets: Set[str] = set()
        for node in stmts:
            line = getattr(node, "lineno", 0)
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                value = node.value
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                if value is None:
                    continue
                # elementwise tuple unpacking: `best, n = node, i + m`
                # must not smear one element's taint onto the others
                if len(targets) == 1 and \
                        isinstance(targets[0], (ast.Tuple, ast.List)) and \
                        isinstance(value, (ast.Tuple, ast.List)) and \
                        len(targets[0].elts) == len(value.elts):
                    for t, v in zip(targets[0].elts, value.elts):
                        mark(self._target_names(t), line,
                             bool(_taint_use(ctx, v, cur)))
                    continue
                names = []
                for t in targets:
                    names.extend(self._target_names(t))
                if isinstance(value, ast.Call):
                    kind = _fence_call_kind(ctx, value)
                    if kind == "fence":
                        mark(names, line, False)     # host copies
                        continue
                    if kind == "fence_wait":
                        # same (device) tree back — taint passes through
                        mark(names, line, bool(
                            any(_taint_use(ctx, a, cur)
                                for a in value.args)))
                        continue
                    if _readback_kind(ctx, value):
                        mark(names, line, False)     # host value now
                        continue
                    if self._is_clock_call(value) and len(names) == 1:
                        self.clock_assigns.setdefault(
                            names[0], []).append(line)
                        clock_targets.add(names[0])
                        continue
                mark(names, line, bool(_taint_use(ctx, value, cur)))
            elif isinstance(node, ast.AugAssign):
                names = self._target_names(node.target)
                if _taint_use(ctx, node.value, cur):
                    mark(names, line, True)
            elif isinstance(node, ast.For):
                names = self._target_names(node.target)
                mark(names, line, bool(_taint_use(ctx, node.iter, cur)))
            elif isinstance(node, ast.Name):
                if isinstance(getattr(node, "ctx", None), ast.Load) and \
                        node.id in clock_targets:
                    self.clock_loads.append((node, node.id, node.lineno))

        # second pass: calls (dispatches, fences, readbacks, blocks)
        for node in ast.walk(self.fn):
            if not isinstance(node, ast.Call):
                continue
            line = node.lineno
            kind = _fence_call_kind(ctx, node)
            if kind:
                site = None
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    site = node.args[0].value
                self.fences.append((node, kind, site))
                self.sync_lines.append(line)
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and \
                    f.attr == _BLOCK_READY_NAME:
                self.blocks.append(node)
                self.sync_lines.append(line)
                continue
            q = ctx.qualname(f)
            if q == f"jax.{_BLOCK_READY_NAME}":
                self.blocks.append(node)
                self.sync_lines.append(line)
                continue
            rb = _readback_kind(ctx, node)
            if rb:
                tainted = self.tainted_at(line)
                if rb == "device_get":
                    self.readbacks.append((node, rb, node))
                    self.sync_lines.append(line)
                    continue
                src = node.func.value if rb == "item" else node.args[0]
                off = _taint_use(ctx, src, tainted)
                if off is not None:
                    self.readbacks.append((node, rb, off))
                    self.sync_lines.append(line)
                continue
            if isinstance(f, (ast.Name, ast.Attribute)):
                seg = _last_seg(ctx.dotted(f))
                if seg in _DEVICE_CALL_SEGS - {"read_row"} or \
                        seg in _step_attr_segs(ctx):
                    self.dispatch_lines.append(line)

        # third pass: loop accumulation of readbacks (ASY304 claims)
        rb_by_id = {id(n): (n, k, o) for n, k, o in self.readbacks}
        for loop in (n for n in ast.walk(self.fn)
                     if isinstance(n, (ast.For, ast.While))):
            for node in ast.walk(loop):
                value = None
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr in ("append", "extend") and \
                        len(node.args) == 1:
                    value = node.args[0]
                elif isinstance(node, ast.AugAssign):
                    value = node.value
                if value is None:
                    continue
                for sub in ast.walk(value):
                    hit = rb_by_id.get(id(sub))
                    if hit is not None and id(sub) not in \
                            self.accum_claimed:
                        self.accum_claimed.add(id(sub))
                        self.accumulations.append((node, hit[0]))
                        break

    def _is_clock_call(self, call: ast.Call) -> bool:
        q = self.ctx.qualname(call.func)
        if q in _CLOCK_QUALS:
            return True
        seg = _last_seg(self.ctx.dotted(call.func))
        return seg == "_clock" and not call.args


def _asy_scan(ctx: FileContext, fn: ast.AST) -> _AsyScan:
    key = ("asy_scan", id(fn))
    hit = ctx.cache.get(key)
    if hit is None:
        hit = ctx.cache[key] = _AsyScan(ctx, fn)
    return hit


def _hot_units(ctx: FileContext):
    """(qual, fn, chain) for this file's hot-path-reachable units."""
    if _is_fence_module(ctx):
        return
    chains = _hot_chains(ctx)
    if not chains:
        return
    for qual, fn, _cls in _unit_functions(ctx):
        chain = chains.get(qual)
        if chain is not None:
            yield qual, fn, chain


# -- ASY301 — implicit device→host readback on the hot path ----------------

@register
class HotReadbackRule(Rule):
    code = "ASY301"
    name = "hot-readback"
    summary = ("implicit device→host readback (.item/float/int/bool/"
               "np.asarray/device_get) on a hot-path-reachable function")
    hint = ("every device→host crossing on the super-step hot path "
            "must wear the fence idiom — "
            "`fence(\"<site>\", *values)` (serving/fences.py) does ONE "
            "batched jax.device_get and returns host arrays, so "
            "downstream bookkeeping never syncs again. Batch several "
            "small readbacks into one fence; cold code (benches, "
            "tests, setup) is exempt by call-graph reachability")

    _KINDS = {"cast": "host cast", "item": ".item()",
              "np": "np.asarray/np.array",
              "device_get": "raw jax.device_get"}

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for qual, fn, chain in _hot_units(ctx):
            scan = _asy_scan(ctx, fn)
            for node, kind, off in scan.readbacks:
                if id(node) in scan.accum_claimed:
                    continue                    # ASY304 owns it
                what = ast.unparse(off)[:40] if off is not None else ""
                yield ctx.finding(
                    node, self.code,
                    f"{self._KINDS[kind]} readback of device value "
                    f"`{what}` in `{qual}` — hot-path-reachable "
                    f"(via {' -> '.join(chain)})",
                    hint=self.hint)


# -- ASY302 — block_until_ready / fence vocabulary drift -------------------

@register
class UnfencedBlockRule(Rule):
    code = "ASY302"
    name = "unfenced-block"
    summary = ("block_until_ready outside the declared fence module, "
               "or a fence site string outside FENCE_SITES, on the "
               "hot path")
    hint = ("deliberate completion waits wear the fence idiom: "
            "`fence_wait(\"<site>\", tree)` (serving/fences.py) is the "
            "ONE designated home of block_until_ready, and its site "
            "vocabulary is CLOSED (FENCE_SITES) so the async refactor "
            "can enumerate every sync point it must move. Add new "
            "sites to FENCE_SITES first")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        sites = _fence_sites(ctx)
        for qual, fn, chain in _hot_units(ctx):
            scan = _asy_scan(ctx, fn)
            for node in scan.blocks:
                yield ctx.finding(
                    node, self.code,
                    f"raw block_until_ready in `{qual}` — hot-path-"
                    f"reachable (via {' -> '.join(chain)}) and outside "
                    f"the declared fence module",
                    hint=self.hint)
            for node, kind, site in scan.fences:
                if site is not None and site not in sites:
                    yield ctx.finding(
                        node, self.code,
                        f"{kind} site {site!r} is not in the declared "
                        f"FENCE_SITES vocabulary {sorted(sites)}",
                        hint=self.hint)


# -- ASY303 — host control flow on un-fenced device values ------------------

@register
class LoopBranchSyncRule(Rule):
    code = "ASY303"
    name = "hot-branch-sync"
    summary = ("Python branch (if/while/ternary/assert) on an un-fenced "
               "device value in a hot-path-reachable function")
    hint = ("a Python branch needs a concrete bool, so it SYNCS the "
            "host on the whole pending device pipeline — exactly the "
            "stall the async dispatch-ahead loop must not pay. Branch "
            "on values from a declared `fence(...)` readback (host "
            "arrays), keep pure host mirrors (KVPool.chunk_done), or "
            "move the decision on-device (lax.cond/jnp.where)")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for qual, fn, chain in _hot_units(ctx):
            scan = _asy_scan(ctx, fn)
            seen: Set[Tuple[int, int]] = set()
            for node in ast.walk(fn):
                if not isinstance(node, (ast.If, ast.While, ast.IfExp,
                                         ast.Assert)):
                    continue
                off = _taint_use(ctx, node.test,
                                 scan.tainted_at(node.lineno))
                if off is None:
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue
                seen.add(key)
                kind = {ast.If: "if", ast.While: "while",
                        ast.IfExp: "conditional expression",
                        ast.Assert: "assert"}[type(node)]
                yield ctx.finding(
                    node, self.code,
                    f"`{kind}` on un-fenced device value "
                    f"`{ast.unparse(off)[:40]}` in `{qual}` — forces a "
                    f"host sync before the next dispatch "
                    f"(hot via {' -> '.join(chain)})",
                    hint=self.hint)


# -- ASY304 — per-iteration readback accumulation ---------------------------

@register
class ReadbackAccumulationRule(Rule):
    code = "ASY304"
    name = "readback-accumulation"
    summary = ("append/+= of a per-iteration device readback inside a "
               "hot-path loop — one host sync per iteration")
    hint = ("accumulating readbacks item by item syncs the device "
            "EVERY iteration; batch them — keep the loop on device "
            "values (accumulating device handles is free) and cross to "
            "host ONCE per step through a single `fence(...)` of the "
            "small results, then do the host bookkeeping between "
            "fences")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for qual, fn, chain in _hot_units(ctx):
            scan = _asy_scan(ctx, fn)
            for node, rb in scan.accumulations:
                yield ctx.finding(
                    node, self.code,
                    f"per-iteration readback "
                    f"`{ast.unparse(rb)[:48]}` accumulated inside a "
                    f"loop in `{qual}` (hot via "
                    f"{' -> '.join(chain)}) — one device sync per "
                    f"iteration",
                    hint=self.hint)


# -- ASY305 — wall-clock reads straddling un-fenced device work -------------

@register
class ClockStraddleRule(Rule):
    code = "ASY305"
    name = "clock-straddle"
    summary = ("clock-read pair timing a device dispatch with no fence "
               "between dispatch and the second read — the measured "
               "time is launch latency, not work")
    hint = ("under async dispatch the host clock keeps running while "
            "the device works, so `t1 - t0` around an un-synced "
            "dispatch measures only the LAUNCH — decode_gap_s, phase "
            "timers, and the watchdog all lie. Pin the timer to a "
            "fence: `fence_wait(\"<site>\", out)` (or consume the "
            "step's `fence(...)` readback) before reading the clock "
            "again")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        colls = _window_collections(ctx)
        for qual, fn, chain in _hot_units(ctx):
            scan = _asy_scan(ctx, fn)
            if not scan.dispatch_lines:
                continue
            # the entry-timestamp idiom is NOT a straddle: a pre-
            # dispatch clock read riding a window-collection append
            # (`win.append(Entry(..., t0, ...))`) is consumed by the
            # DELAYED consumer, which measures elapsed against it
            # strictly after its own fence — the pin ASY305 wants is
            # the entry's consumption, and ASY310 checks that side
            stamped: Set[int] = set()
            if colls:
                for node in ast.walk(fn):
                    if (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Attribute)
                            and node.func.attr == "append"
                            and ctx.dotted(node.func.value) in colls):
                        for a in node.args:
                            for sub in ast.walk(a):
                                stamped.add(id(sub))
            for name, assigns in scan.clock_assigns.items():
                for i, a_line in enumerate(assigns):
                    next_assign = assigns[i + 1] if i + 1 < len(assigns) \
                        else float("inf")
                    loads = sorted(
                        ((node, ln) for node, n, ln in scan.clock_loads
                         if n == name and a_line < ln < next_assign),
                        key=lambda t: t[1])
                    for node, ln in loads:
                        if id(node) in stamped:
                            continue
                        bad = any(
                            a_line < d < ln and not any(
                                d < s <= ln for s in scan.sync_lines)
                            for d in scan.dispatch_lines)
                        if bad:
                            yield ctx.finding(
                                node, self.code,
                                f"clock pair `{name}` (set line "
                                f"{a_line}) read here straddles an "
                                f"un-fenced device dispatch in "
                                f"`{qual}` (hot via "
                                f"{' -> '.join(chain)}) — the elapsed "
                                f"time measures the launch, not the "
                                f"work",
                                hint=self.hint)
                            break


# ==========================================================================
# ASY306-310 — the dispatch-ahead discipline (analyzer tier 5).
#
# The delayed-consumer refactor (ServingEngine dispatch_ahead=W —
# docs/serving.md "Dispatch-ahead decode") keeps up to W decode
# dispatches in flight BEHIND the fence that consumes them. Four
# orderings make that window wrong and one makes it lie, and each is a
# static shape: consuming a deferred readback into the SAME step's
# dispatch (ASY306), re-donating a carry the in-flight window still
# owns (ASY307), bounding the window by anything but a declared knob
# (ASY308), an extra fence inside the dispatch side re-serializing the
# window (ASY309), and a delayed consumer that stopped reading the
# clock, starving the watchdog and fault replay (ASY310). A "window"
# is detected structurally — a collection hot units append
# device-tainted values into AND pop oldest-first from
# (_window_collections) — so the rules were born BEFORE the refactor
# landed and gate every future one.
# ==========================================================================


# -- ASY306 — deferred readback consumed into the same step's dispatch ------

@register
class StaleConsumerRule(Rule):
    code = "ASY306"
    name = "stale-consumer"
    summary = ("a delayed-site fence readback feeds a value back into "
               "a dispatch LATER in the same unit — consume-before-"
               "dispatch ordering the window must not have")
    hint = ("a deferred fence's readback (tokens, finish verdicts, "
            "ban flips) is W steps STALE — feeding it into the same "
            "unit's next dispatch silently re-serializes the window "
            "(the dispatch must wait for the fence) or, worse, chains "
            "the wrong tokens. Chain steady-state dispatches on the "
            "previous dispatch's DEVICE handle and keep the fenced "
            "host values in the delayed consumer's bookkeeping "
            "(ServingEngine._consume_window); flush the window before "
            "any dispatch that needs host-consumed state")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        dsites = _delayed_sites(ctx)
        step_segs = _step_attr_segs(ctx)
        for qual, fn, chain in _hot_units(ctx):
            scan = _asy_scan(ctx, fn)
            fence_ids = {id(node) for node, kind, site in scan.fences
                         if kind == "fence" and site in dsites}
            if not fence_ids:
                continue
            # names bound FROM a delayed-site fence, with simple
            # forward propagation through assignments (`toks =
            # jnp.asarray(nxt)` keeps the taint); name -> bind line
            bound: Dict[str, int] = {}
            assigns = sorted(
                (n for n in ast.walk(fn) if isinstance(n, ast.Assign)),
                key=lambda n: n.lineno)
            for node in assigns:
                names: List[str] = []
                for t in node.targets:
                    names.extend(_target_names_of(t))
                if id(node.value) in fence_ids:
                    for n in names:
                        bound.setdefault(n, node.lineno)
                    continue
                if isinstance(node.value, ast.Call) and \
                        isinstance(node.value.func,
                                   (ast.Name, ast.Attribute)):
                    seg = _last_seg(ctx.dotted(node.value.func))
                    if seg in _DEVICE_CALL_SEGS or seg in step_segs:
                        # a dispatch RESULT is a fresh device handle —
                        # chaining the next dispatch on it is exactly
                        # the sanctioned steady-state pattern, so the
                        # stale-host taint stops here (the stale value
                        # already fired on the dispatch's own args)
                        continue
                if any(isinstance(sub, ast.Name) and sub.id in bound
                       and sub.lineno > bound[sub.id]
                       for sub in ast.walk(node.value)):
                    for n in names:
                        bound.setdefault(n, node.lineno)
            if not bound:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if not isinstance(f, (ast.Name, ast.Attribute)):
                    continue
                seg = _last_seg(ctx.dotted(f))
                if seg not in _DEVICE_CALL_SEGS - {"read_row"} and \
                        seg not in step_segs:
                    continue
                hit = next(
                    (sub for a in list(node.args) +
                     [kw.value for kw in node.keywords]
                     for sub in ast.walk(a)
                     if isinstance(sub, ast.Name) and sub.id in bound
                     and node.lineno > bound[sub.id]), None)
                if hit is not None:
                    yield ctx.finding(
                        node, self.code,
                        f"delayed-site fence readback `{hit.id}` "
                        f"(consumed line {bound[hit.id]}) feeds this "
                        f"dispatch in `{qual}` (hot via "
                        f"{' -> '.join(chain)}) — the window must "
                        f"dispatch from device handles, not "
                        f"just-fenced host state",
                        hint=self.hint)


# -- ASY307 — carry donated again while the window still owns it ------------

@register
class WindowDonationRule(Rule):
    code = "ASY307"
    name = "window-donation"
    summary = ("a carry buffer donated to an in-flight (not-yet-"
               "fenced) dispatch is read or donated again before it "
               "is rebound — use-after-donate lifted to the multi-"
               "step window")
    hint = ("every dispatch DONATES its carry argument (the buffer is "
            "dead the moment the call is issued — SPMD104/SRV204); "
            "with W dispatches in flight the live buffer is the LAST "
            "dispatch's return, so touching the donated spelling "
            "before rebinding it reads freed memory W steps early. "
            "Rebind on the same line (`_, carry = dispatch(..., "
            "carry)`) or immediately commit the returned carry "
            "(`pool.carry = carry`) before anything else reads it")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        colls = _window_collections(ctx)
        if not colls:
            return
        step_segs = _step_attr_segs(ctx)
        for qual, fn, chain in _hot_units(ctx):
            owns, consumes = _unit_window_role(ctx, fn, colls)
            if not (owns or consumes):
                continue
            # (line, kind, dotted, node) timeline of carry donations,
            # loads, and stores, replayed in line order per spelling
            events: List[Tuple[int, int, str, str, ast.AST]] = []
            donated_ids: Set[int] = set()
            # `_, carry = dispatch(..., carry)` rebinds the donated
            # spelling in the SAME statement — the sanctioned idiom;
            # that donation is cleared the instant the call returns
            rebinds: Dict[int, Set[str]] = {}
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and \
                        isinstance(node.value, ast.Call):
                    tgts: Set[str] = set()
                    for t in node.targets:
                        for sub in ast.walk(t):
                            d = ctx.dotted(sub)
                            if d:
                                tgts.add(d)
                    rebinds[id(node.value)] = tgts
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, (ast.Name, ast.Attribute)):
                    seg = _last_seg(ctx.dotted(node.func))
                    if seg in _DEVICE_CALL_SEGS - {"read_row"} or \
                            seg in step_segs:
                        for a in node.args:
                            d = ctx.dotted(a)
                            if d and _carry_seg(_last_seg(d)):
                                if d in rebinds.get(id(node), ()):
                                    for sub in ast.walk(a):
                                        donated_ids.add(id(sub))
                                    continue
                                # the donation anchors at the ARG's own
                                # position (multi-line calls), and the
                                # arg is the donation, not a read of it
                                for sub in ast.walk(a):
                                    donated_ids.add(id(sub))
                                events.append(
                                    (a.lineno, a.col_offset,
                                     "donate", d, node))
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        for sub in ast.walk(t):
                            d = ctx.dotted(sub)
                            if d and _carry_seg(_last_seg(d)):
                                events.append(
                                    (node.lineno, -1, "store", d, sub))
                elif isinstance(node, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(node, "ctx", None), ast.Load):
                    if id(node) in donated_ids:
                        continue
                    d = ctx.dotted(node)
                    if d and _carry_seg(_last_seg(d)):
                        events.append((node.lineno, node.col_offset,
                                       "load", d, node))
            by_name: Dict[str, List] = {}
            for ev in sorted(events, key=lambda e: (e[0], e[1])):
                by_name.setdefault(ev[3], []).append(ev)
            for name, evs in by_name.items():
                donated_at: Optional[int] = None
                for line, _col, kind, _d, node in evs:
                    if kind == "store":
                        donated_at = None    # rebound: live again
                        # (a same-line store — `_, c = disp(..., c)` —
                        # clears the donation it rode in on too)
                    elif kind == "donate":
                        if donated_at is not None and line > donated_at:
                            yield ctx.finding(
                                node, self.code,
                                f"carry `{name}` donated again here "
                                f"while an in-flight dispatch (line "
                                f"{donated_at}) still owns it, in "
                                f"`{qual}` (hot via "
                                f"{' -> '.join(chain)})",
                                hint=self.hint)
                            break
                        donated_at = line
                    elif kind == "load" and donated_at is not None \
                            and line > donated_at:
                        yield ctx.finding(
                            node, self.code,
                            f"carry `{name}` read here after being "
                            f"donated to the in-flight dispatch at "
                            f"line {donated_at} in `{qual}` (hot via "
                            f"{' -> '.join(chain)}) — rebind it from "
                            f"the dispatch's return first",
                            hint=self.hint)
                        break


# -- ASY308 — window depth not bound by a declared knob ---------------------

@register
class UnboundedWindowRule(Rule):
    code = "ASY308"
    name = "unbounded-window"
    summary = ("a dispatch-ahead window depth guard that does not "
               "reference a declared WINDOW_KNOBS engine knob — a "
               "literal or bare counter bounds the window")
    hint = ("the window depth is an ENGINE CONTRACT (W=0 must be the "
            "fence-immediately engine, byte for byte), so every depth "
            "guard must read a knob from the declared WINDOW_KNOBS "
            "vocabulary (serving/fences.py — the FENCE_SITES pattern): "
            "`while len(self._window) > self.dispatch_ahead`. A "
            "literal depth or a bare loop counter is vocabulary drift "
            "the W-sweep contracts cannot reach")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        colls = _window_collections(ctx)
        if not colls:
            return
        knobs = _window_knobs(ctx)

        def knob_ref(expr: ast.AST) -> bool:
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Attribute) and \
                        sub.attr.lstrip("_") in knobs:
                    return True
                if isinstance(sub, ast.Name) and \
                        sub.id.lstrip("_") in knobs:
                    return True
            return False

        def len_of_window(expr: ast.AST) -> bool:
            return any(
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Name)
                and sub.func.id == "len" and len(sub.args) == 1
                and ctx.dotted(sub.args[0]) in colls
                for sub in ast.walk(expr))

        def has_window_append(body_node: ast.AST) -> bool:
            return any(
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "append"
                and ctx.dotted(sub.func.value) in colls
                for sub in ast.walk(body_node))

        for qual, fn, chain in _hot_units(ctx):
            owns, _consumes = _unit_window_role(ctx, fn, colls)
            if not owns:
                continue       # the consumer's `while window:` drain
                               # is truthiness, not a depth bound
            for node in ast.walk(fn):
                if isinstance(node, (ast.While, ast.If)):
                    if len_of_window(node.test) and \
                            not knob_ref(node.test):
                        yield ctx.finding(
                            node, self.code,
                            f"window depth guard "
                            f"`{ast.unparse(node.test)[:48]}` in "
                            f"`{qual}` (hot via {' -> '.join(chain)}) "
                            f"references no declared WINDOW_KNOBS "
                            f"knob {sorted(knobs)}",
                            hint=self.hint)
                elif isinstance(node, ast.For):
                    if has_window_append(node) and \
                            not knob_ref(node.iter):
                        yield ctx.finding(
                            node, self.code,
                            f"dispatch-ahead loop "
                            f"`for {ast.unparse(node.target)} in "
                            f"{ast.unparse(node.iter)[:40]}` fills a "
                            f"window in `{qual}` (hot via "
                            f"{' -> '.join(chain)}) without a "
                            f"declared WINDOW_KNOBS bound "
                            f"{sorted(knobs)}",
                            hint=self.hint)


# -- ASY309 — a fence inside the dispatch side of the window ----------------

@register
class InWindowFenceRule(Rule):
    code = "ASY309"
    name = "in-window-fence"
    summary = ("a fence/fence_wait site other than the declared "
               "delayed-consumer readback inside a window-DISPATCHING "
               "unit — re-serializes the window by accident")
    hint = ("the dispatch side of a dispatch-ahead window must not "
            "wait on the device AT ALL — any fence there drains the "
            "whole pipeline before the next dispatch, silently "
            "turning W back into 0. Exactly the DELAYED_CONSUMER_SITES"
            " readbacks (serving/fences.py) may be consumed against "
            "the window, and they belong in the delayed consumer "
            "(ServingEngine._consume_window), not the dispatch loop; "
            "move any other sync out of the window-owning unit")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        colls = _window_collections(ctx)
        if not colls:
            return
        dsites = _delayed_sites(ctx)
        for qual, fn, chain in _hot_units(ctx):
            owns, _consumes = _unit_window_role(ctx, fn, colls)
            if not owns:
                continue
            scan = _asy_scan(ctx, fn)
            for node, kind, site in scan.fences:
                if kind == "fence" and site in dsites:
                    continue   # the declared delayed readback (W=0
                               # consumes it inline; ASY306 guards the
                               # ordering either way)
                yield ctx.finding(
                    node, self.code,
                    f"{kind}:{site or '?'} inside window-dispatching "
                    f"unit `{qual}` (hot via {' -> '.join(chain)}) — "
                    f"re-serializes the dispatch-ahead window",
                    hint=self.hint)


# -- ASY310 — delayed consumer without a clock sample -----------------------

@register
class UnpairedDeferredClockRule(Rule):
    code = "ASY310"
    name = "unpaired-deferred-clock"
    summary = ("a window-consuming unit fences a delayed site without "
               "reading the engine clock — the deferred sample is "
               "unpaired, so watchdog + fault replay go blind")
    hint = ("every deferred fence consumption must advance/read the "
            "engine's virtual clock: the watchdog's elapsed "
            "(dispatch t0 -> fence landed) is what catches a stalled "
            "deferred readback, and byte-identical fault replay keys "
            "off those clock samples. Bracket the fence with "
            "`self._clock()` reads (the fence_wait phase + the "
            "entry-elapsed watchdog sample, as "
            "ServingEngine._consume_window does)")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        colls = _window_collections(ctx)
        if not colls:
            return
        dsites = _delayed_sites(ctx)
        for qual, fn, chain in _hot_units(ctx):
            _owns, consumes = _unit_window_role(ctx, fn, colls)
            if not consumes:
                continue
            scan = _asy_scan(ctx, fn)
            deferred = [node for node, kind, site in scan.fences
                        if kind == "fence" and site in dsites]
            if not deferred:
                continue
            has_clock = any(
                isinstance(node, ast.Call) and scan._is_clock_call(node)
                for node in ast.walk(fn))
            if not has_clock:
                yield ctx.finding(
                    deferred[0], self.code,
                    f"delayed consumer `{qual}` (hot via "
                    f"{' -> '.join(chain)}) fences a deferred site "
                    f"with NO engine-clock read — the watchdog's "
                    f"elapsed and fault replay lose their sample",
                    hint=self.hint)


# ==========================================================================
# The MH4xx multi-host lockstep & determinism family.
#
# The next serving tier runs the disaggregated pools process-per-host
# over CoordServiceBlockStore on a real jax.distributed pod, and the
# bug class that kills SPMD pods is SILENT LOCKSTEP DIVERGENCE: one
# process traces a different program, calls a collective the others
# skip, or makes a routing/replay decision from wall-clock or unseeded
# randomness the other processes don't share. Every worker must execute
# the identical step sequence (the synchronous-AllReduce design of the
# BigDL reference and the MLPerf pod-scaling work both hinge on it).
#
# The machinery is a DIVERGENCE-TAINT layer on the existing
# interprocedural call graph:
#
# * values derived from ``jax.process_index()`` or per-peer block-store
#   reads (``try_get``/``get_blocking`` on a store) are
#   *process-divergent* — each process sees a different value.
#   ``jax.process_count()`` is recorded as a divergence ROOT for the
#   worksheet (``--report lockstep``) but is pod-uniform in a healthy
#   pod, so branches on it are lockstep-safe and exempt from MH401;
# * facts record which units invoke cross-process AGREEMENT POINTS:
#   collectives (psum / all_gather / ppermute ...), compiled-step
#   dispatches (``_dispatch`` / step-attr calls — every process must
#   trace and launch the same program), and block-store barriers /
#   straggler waits (``get_blocking`` / ``get_weights``);
# * a reverse reachability closure over the merged call edges answers
#   "does this call reach an agreement point?" project-wide.
#
# On top of that: MH401 divergent branch reaching an agreement point
# (the classic trace-divergence pod hang), MH402 collectives/handoffs
# issued from unordered-set iteration (PYTHONHASHSEED makes set order
# per-process), MH403 raw wall-clock reads in the serving plane outside
# the closed CLOCK_SITES vocabulary (the FENCE_SITES pattern — lockstep
# decisions must run on the injected engine clock), MH404 ambient
# randomness on replay paths (byte-identical failover/preemption replay
# must be a pure function of request seeds), MH405 block-store keys
# built from divergent values without the process-id namespace
# (cross-process key collisions).
# ==========================================================================

#: cross-process collective primitives: every process in the mesh must
#: call these the same number of times in the same order or the pod
#: hangs
_COLLECTIVE_QUALS = frozenset({
    "jax.lax.psum", "jax.lax.pmean", "jax.lax.pmax", "jax.lax.pmin",
    "jax.lax.psum_scatter", "jax.lax.all_gather", "jax.lax.all_to_all",
    "jax.lax.ppermute", "jax.lax.pshuffle",
})
#: block-store barrier / straggler-wait spellings (the host-side
#: agreement points of the blockstore parameter plane)
_BARRIER_SEGS = frozenset({"get_blocking", "get_weights", "wait_all",
                           "barrier"})
#: the per-process identity — THE divergence root
_PROCESS_ID_QUALS = frozenset({"jax.process_index"})
#: recorded divergence roots for the worksheet (process_count is
#: pod-uniform, so it feeds the inventory but not the MH401 taint)
_PROCESS_TOPOLOGY_QUALS = frozenset({"jax.process_index",
                                     "jax.process_count"})
#: per-peer block-store reads: another process wrote the value, so
#: what THIS process sees depends on arrival order — divergent
_PEER_READ_SEGS = frozenset({"try_get", "get_blocking"})
#: cross-process handoff spellings (payload send order feeds the
#: receiver's agreement) — MH402's second trigger class
_HANDOFF_SEGS = frozenset({"send", "pack_payload", "put"})
#: raw wall-clock sources the serving plane must not read outside the
#: declared CLOCK_SITES (time.sleep included: serving simulates stalls
#: on the VirtualClock, never by sleeping)
_WALL_CLOCK_QUALS = frozenset({"time.time", "time.perf_counter",
                               "time.monotonic", "time.process_time",
                               "time.sleep"})
#: fallback CLOCK_SITES vocabulary (single-file fixture runs): must
#: match serving/faults.py CLOCK_SITES
_DEFAULT_CLOCK_SITES = frozenset({"faults.default_clock",
                                  "metrics.ServingMetrics.on_step"})
#: seeded RNG constructors — sanctioned WITH an explicit seed argument
_SEEDED_RNG_QUALS = frozenset({
    "numpy.random.default_rng", "numpy.random.RandomState",
    "numpy.random.SeedSequence", "numpy.random.Generator",
    "random.Random",
})
#: fresh jax key constructors — sanctioned only inside the sampling
#: module's seed derivation (sampling.lane_key)
_FRESH_KEY_QUALS = frozenset({"jax.random.PRNGKey", "jax.random.key"})


@_register_facts
def _clock_site_facts(ctx: FileContext) -> Dict:
    """The declared clock-site vocabulary (``CLOCK_SITES``) and the
    module that declares it — MH403's ground truth, extracted the way
    ASY302 reads FENCE_SITES."""
    for node in ctx.by_type(ast.Assign):
        if not any(isinstance(t, ast.Name) and t.id == "CLOCK_SITES"
                   for t in node.targets):
            continue
        val = literal_value(node.value)
        if val is not UNRESOLVED:
            return {"clock_sites": sorted(val),
                    "clock_modules": [ctx.module]}
    return {}


def _clock_sites(ctx: FileContext) -> Set[str]:
    sites = _facts(ctx).get("clock_sites")
    return set(sites) if sites else set(_DEFAULT_CLOCK_SITES)


def _is_blockstore_module(ctx: FileContext) -> bool:
    """True for the module that DEFINES the block-store layer (the
    ``BlockStore`` base class): its polling loops ARE the cross-process
    synchronization implementation — branching on per-peer reads is its
    job, so MH401 exempts it (the compat.py / fences.py pattern)."""
    hit = ctx.cache.get("is_blockstore_module")
    if hit is None:
        hit = any(cls.name == "BlockStore"
                  for cls in ctx.by_type(ast.ClassDef))
        ctx.cache["is_blockstore_module"] = hit
    return hit


def _class_method_names(ctx: FileContext) -> Dict[str, Set[str]]:
    out = ctx.cache.get("class_method_names")
    if out is None:
        out = ctx.cache["class_method_names"] = {}
        for cls in ctx.by_type(ast.ClassDef):
            out[cls.name] = {
                f.name for f in cls.body
                if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))}
    return out


@_register_facts
def _lockstep_facts(ctx: FileContext) -> Dict:
    """Per-unit multi-host facts: ``collective_units`` (units that
    directly invoke a cross-process agreement point — a collective, a
    compiled-step dispatch, or a block-store barrier) and
    ``divergent_units`` (units that read a divergence root —
    ``jax.process_index``/``process_count`` or a per-peer store read).
    The reachability closure and the ``--report lockstep`` worksheet
    are built from the merged tables."""
    units = _unit_functions(ctx)
    if not units:
        return {}
    step_segs = set(_step_binding_facts(ctx).get("step_attrs", {}))
    coll: Dict[str, List[str]] = {}
    div: Dict[str, List[str]] = {}
    for qual, fn, _cls in units:
        kinds: Set[str] = set()
        roots: Set[str] = set()
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            q = ctx.qualname(node.func)
            seg = _last_seg(ctx.dotted(node.func))
            if q in _COLLECTIVE_QUALS:
                kinds.add(f"collective:{q.rsplit('.', 1)[-1]}")
            elif seg == "_dispatch" or seg in step_segs:
                kinds.add("dispatch")
            elif seg in _BARRIER_SEGS:
                kinds.add(f"barrier:{seg}")
            if q in _PROCESS_TOPOLOGY_QUALS:
                roots.add(q.rsplit(".", 1)[-1])
            elif seg in _PEER_READ_SEGS and _storeish_receiver(ctx,
                                                              node):
                roots.add("peer-read")
        if kinds:
            coll[qual] = sorted(kinds)
        if roots:
            div[qual] = sorted(roots)
    out: Dict[str, Any] = {}
    if coll:
        out["collective_units"] = coll
    if div:
        out["divergent_units"] = div
    return out


def _storeish_receiver(ctx: FileContext, call: ast.Call) -> bool:
    """True when the call's receiver looks like a block store
    (``...store.try_get`` / ``bs.get_blocking``)."""
    if not isinstance(call.func, ast.Attribute):
        return False
    d = ctx.dotted(call.func.value)
    return bool(d) and "store" in d.rsplit(".", 1)[-1].lower()


def _collective_reach(ctx: FileContext) -> Set[str]:
    """Unit quals from which a cross-process agreement point is
    reachable through the merged call-graph edges (the agreement units
    themselves included) — reverse BFS over the same edge-resolution
    rules ``core.hotpath_chains`` uses, project-memoized."""
    def compute(facts: Dict) -> Set[str]:
        edges: Dict[str, List[str]] = facts.get("call_edges") or {}
        methods: Dict[str, List[str]] = facts.get("method_units") or {}
        coll = set(facts.get("collective_units") or {})
        if not coll:
            return set()
        by_tail: Dict[str, List[str]] = {}
        for q in edges:
            by_tail.setdefault(q.rsplit(".", 1)[-1], []).append(q)
        rev: Dict[str, List[str]] = {}
        for qual, callees in edges.items():
            for callee in callees:
                if callee.startswith("."):
                    targets = methods.get(callee[1:], [])
                elif callee in edges:
                    targets = [callee]
                else:
                    tail = callee.rsplit(".", 1)[-1]
                    targets = [q for q in by_tail.get(tail, ())
                               if q.endswith("." + callee)
                               or callee.endswith("." + q)]
                for t in targets:
                    rev.setdefault(t, []).append(qual)
        seen = set(coll)
        queue = list(coll)
        while queue:
            q = queue.pop()
            for p in rev.get(q, ()):
                if p not in seen:
                    seen.add(p)
                    queue.append(p)
        return seen

    proj = ctx.project
    if proj is not None:
        hit = proj.cache.get("collective_reach")
        if hit is None:
            hit = proj.cache["collective_reach"] = compute(proj.facts)
        return hit
    return compute(_facts(ctx))


def _callee_token(ctx: FileContext, call: ast.Call,
                  cls: Optional[str]) -> Optional[str]:
    """The call-graph edge token a Call would contribute (mirrors
    ``core._call_graph_facts`` at one call site): a qualified name,
    a ``.attr`` suffix, or None."""
    f = call.func
    mod = ctx.module
    if isinstance(f, ast.Name):
        local = ctx.cache.get("toplevel_defs")
        if local is None:
            local = ctx.cache["toplevel_defs"] = {
                fn.name for fn in ctx.tree.body
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))}
        if f.id in local:
            return f"{mod}.{f.id}" if mod else f.id
        return ctx.qualname(f)
    if isinstance(f, ast.Attribute):
        q = ctx.qualname(f)
        if q:
            return q
        d = ctx.dotted(f)
        if d and cls and d == f"self.{f.attr}" and \
                f.attr in _class_method_names(ctx).get(cls, ()):
            return f"{mod}.{cls}.{f.attr}" if mod else f"{cls}.{f.attr}"
        return "." + f.attr
    return None


def _agreement_call(ctx: FileContext, call: ast.Call,
                    cls: Optional[str]) -> Optional[str]:
    """What cross-process agreement ``call`` commits this process to:
    ``"collective:psum"``-style for a direct collective, ``"dispatch"``
    for a compiled-step launch, ``"barrier:..."`` for a block-store
    wait, ``"reaches <unit>"`` when the callee reaches one through the
    merged call graph — else None."""
    q = ctx.qualname(call.func)
    if q in _COLLECTIVE_QUALS:
        return f"collective:{q.rsplit('.', 1)[-1]}"
    seg = _last_seg(ctx.dotted(call.func))
    if seg == "_dispatch" or seg in _step_attr_segs(ctx):
        return "dispatch"
    if seg in _BARRIER_SEGS:
        return f"barrier:{seg}"
    token = _callee_token(ctx, call, cls)
    if token is None:
        return None
    reach = _collective_reach(ctx)
    if not reach:
        return None
    facts = _facts(ctx)
    methods: Dict[str, List[str]] = facts.get("method_units") or {}
    if token.startswith("."):
        targets = methods.get(token[1:], [])
    elif token in reach:
        return f"reaches {token}"
    else:
        targets = [t for t in reach
                   if t.endswith("." + token) or token.endswith("." + t)]
    for t in targets:
        if t in reach:
            return f"reaches {t}"
    return None


def _divergent_self_attrs(ctx: FileContext) -> Dict[Tuple[str, str], str]:
    """``(class name, attr) -> "pid" | "div"`` for attributes assigned
    a divergence root anywhere in the class body (``self.pid =
    jax.process_index()`` in ``__init__``, branched on in a method —
    the cross-method half the per-unit timeline cannot see)."""
    out = ctx.cache.get("divergent_self_attrs")
    if out is None:
        out = ctx.cache["divergent_self_attrs"] = {}
        for cls in ctx.by_type(ast.ClassDef):
            for node in ast.walk(cls):
                if not isinstance(node, ast.Assign):
                    continue
                kind = None
                if _pid_direct_expr(ctx, node.value, set()):
                    kind = "pid"
                elif _div_root_call(ctx, node.value):
                    kind = "div"
                if kind is None:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Attribute) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == "self":
                        out[(cls.name, t.attr)] = kind
    return out


def _div_root_call(ctx: FileContext, expr: ast.AST) -> bool:
    """Any divergence-root call inside ``expr`` (process_index or a
    per-peer store read)."""
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            if ctx.qualname(node.func) in _PROCESS_ID_QUALS:
                return True
            if _last_seg(ctx.dotted(node.func)) in _PEER_READ_SEGS and \
                    _storeish_receiver(ctx, node):
                return True
    return False


def _pid_direct_expr(ctx: FileContext, expr: ast.AST,
                     pid_names: Set[str],
                     cls: Optional[str] = None) -> bool:
    """True when ``expr`` IS the process id (usable as a key
    namespace): a bare ``jax.process_index()`` call, an ``int()`` or
    ``str()`` wrap of one, a name currently bound to one, or a
    pid-assigned ``self.`` attribute."""
    if isinstance(expr, ast.Call):
        if ctx.qualname(expr.func) in _PROCESS_ID_QUALS:
            return True
        if isinstance(expr.func, ast.Name) and \
                expr.func.id in ("int", "str") and len(expr.args) == 1:
            return _pid_direct_expr(ctx, expr.args[0], pid_names, cls)
        return False
    if isinstance(expr, ast.Name):
        return expr.id in pid_names
    if isinstance(expr, ast.Attribute) and \
            isinstance(expr.value, ast.Name) and expr.value.id == "self":
        return _divergent_self_attrs(ctx).get((cls or "", expr.attr)) \
            == "pid"
    return False


class _DivScan:
    """Per-unit divergence-taint timeline: which local names hold
    process-divergent values (derived from ``jax.process_index()`` or
    per-peer store reads) at each line, plus the ``pid``-direct subset
    (names that ARE the process id — the legal key namespace)."""

    def __init__(self, ctx: FileContext, fn: ast.AST,
                 cls: Optional[str]) -> None:
        self.ctx = ctx
        self.fn = fn
        self.cls = cls
        self.events: Dict[str, List[Tuple[int, bool]]] = {}
        self.pid_names_final: Set[str] = set()
        self._pid_cur: Set[str] = set()
        self._build()

    def tainted_at(self, line: int) -> Set[str]:
        return _taint_state_at(self.events, line)

    def _build(self) -> None:
        ctx = self.ctx
        cur: Set[str] = set()

        def mark(names: List[str], line: int, val: bool) -> None:
            for n in names:
                if val:
                    cur.add(n)
                elif n in cur:
                    cur.discard(n)
                else:
                    continue
                self.events.setdefault(n, []).append((line, val))

        stmts = sorted(
            (n for n in ast.walk(self.fn)
             if isinstance(n, (ast.Assign, ast.AnnAssign, ast.AugAssign,
                               ast.For))),
            key=lambda n: (getattr(n, "lineno", 0),
                           getattr(n, "col_offset", 0)))
        for node in stmts:
            line = getattr(node, "lineno", 0)
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                value = node.value
                if value is None:
                    continue
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                names: List[str] = []
                for t in targets:
                    names.extend(_target_names_of(t))
                if _pid_direct_expr(ctx, value, self._pid_cur, self.cls):
                    self._pid_cur.update(names)
                else:
                    self._pid_cur.difference_update(names)
                mark(names, line,
                     self.div_use(value, line, _cur=cur) is not None)
            elif isinstance(node, ast.AugAssign):
                if self.div_use(node.value, line, _cur=cur) is not None:
                    mark(_target_names_of(node.target), line, True)
            elif isinstance(node, ast.For):
                mark(_target_names_of(node.target), line,
                     self.div_use(node.iter, line, _cur=cur) is not None)
        self.pid_names_final = set(self._pid_cur)

    def div_use(self, expr: ast.AST, line: int,
                _cur: Optional[Set[str]] = None) -> Optional[ast.AST]:
        """First process-divergent use inside ``expr``: a tainted name,
        a divergence-root call, or a divergent ``self.`` attribute."""
        ctx = self.ctx
        tainted = _cur if _cur is not None else self.tainted_at(line)
        out: List[ast.AST] = []

        def visit(node: ast.AST) -> None:
            if out:
                return
            if isinstance(node, ast.Name):
                if node.id in tainted:
                    out.append(node)
                return
            if isinstance(node, ast.Attribute):
                if isinstance(node.value, ast.Name) and \
                        node.value.id == "self" and \
                        (self.cls or "", node.attr) in \
                        _divergent_self_attrs(ctx):
                    out.append(node)
                    return
                visit(node.value)
                return
            if isinstance(node, ast.Call):
                if ctx.qualname(node.func) in _PROCESS_ID_QUALS:
                    out.append(node)
                    return
                if _last_seg(ctx.dotted(node.func)) in _PEER_READ_SEGS \
                        and _storeish_receiver(ctx, node):
                    out.append(node)
                    return
                for child in list(node.args) + \
                        [kw.value for kw in node.keywords]:
                    visit(child)
                if not isinstance(node.func, ast.Name):
                    visit(node.func)
                return
            for child in ast.iter_child_nodes(node):
                visit(child)

        visit(expr)
        return out[0] if out else None

    def pid_in_parts(self, parts: Sequence[ast.AST]) -> bool:
        return any(_pid_direct_expr(self.ctx, p, self.pid_names_final,
                                    self.cls) for p in parts)


def _div_scan(ctx: FileContext, fn: ast.AST,
              cls: Optional[str]) -> _DivScan:
    key = ("div_scan", id(fn))
    hit = ctx.cache.get(key)
    if hit is None:
        hit = ctx.cache[key] = _DivScan(ctx, fn, cls)
    return hit


def _file_has_div_roots(ctx: FileContext) -> bool:
    """Cheap gate: any divergence-root call anywhere in the file
    (process_index or a store-receiver peer read)."""
    hit = ctx.cache.get("has_div_roots")
    if hit is None:
        hit = False
        for node in ctx.by_type(ast.Call):
            if ctx.qualname(node.func) in _PROCESS_ID_QUALS or (
                    _last_seg(ctx.dotted(node.func)) in _PEER_READ_SEGS
                    and _storeish_receiver(ctx, node)):
                hit = True
                break
        ctx.cache["has_div_roots"] = hit
    return hit


# -- MH401 — divergent branch reaching a collective -------------------------

@register
class DivergentBranchRule(Rule):
    code = "MH401"
    name = "divergent-branch-collective"
    summary = ("Python branch on a process-divergent value whose body "
               "reaches a collective / compiled-step dispatch / "
               "block-store barrier — the classic trace-divergence "
               "pod hang")
    hint = ("every process in an SPMD pod must execute the identical "
            "dispatch + collective sequence; a branch on "
            "jax.process_index() (or a per-peer store read) that "
            "guards a collective means one process calls it and the "
            "others don't — the pod hangs at the next barrier. Hoist "
            "the agreement point out of the branch (all processes "
            "dispatch; rank-gate only the pure-host side effects like "
            "logging/checkpoint WRITES), or make the decision from "
            "pod-uniform state")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if _is_blockstore_module(ctx) or not _file_has_div_roots(ctx):
            return
        for qual, fn, cls in _unit_functions(ctx):
            scan = _div_scan(ctx, fn, cls)
            seen: Set[Tuple[int, int]] = set()
            for node in ast.walk(fn):
                # If/While/IfExp only: an `assert` on a divergent value
                # is the standard single-process TEST idiom (asserting
                # on a store read), and the pod-hang shape is a guarded
                # agreement point, which asserts cannot express
                if not isinstance(node, (ast.If, ast.While, ast.IfExp)):
                    continue
                off = scan.div_use(node.test, node.lineno)
                if off is None:
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue
                bodies: List[ast.AST] = []
                if isinstance(node, ast.IfExp):
                    bodies = [node.body, node.orelse]
                else:
                    bodies = list(node.body) + list(node.orelse)
                hit = None
                for b in bodies:
                    for sub in ast.walk(b):
                        if isinstance(sub, ast.Call):
                            kind = _agreement_call(ctx, sub, cls)
                            if kind:
                                hit = (sub, kind)
                                break
                    if hit:
                        break
                if hit is None:
                    continue
                seen.add(key)
                yield ctx.finding(
                    node, self.code,
                    f"branch on process-divergent value "
                    f"`{ast.unparse(off)[:40]}` guards a cross-process "
                    f"agreement point ({hit[1]}) in `{qual}` — "
                    f"processes diverge on whether they "
                    f"dispatch/collect",
                    hint=self.hint)


# -- MH402 — collectives/handoffs from unordered iteration ------------------

@register
class OrderDivergentIterationRule(Rule):
    code = "MH402"
    name = "unordered-agreement-iteration"
    summary = ("collective or cross-process handoff issued from "
               "iteration over a set — per-process iteration order "
               "feeds cross-process agreement")
    hint = ("set iteration order depends on hash seeding and insertion "
            "history, which differ per process — two processes looping "
            "`for x in pending:` issue their sends/collectives in "
            "DIFFERENT orders and the receivers (or the collective "
            "schedule) disagree. Iterate a canonical order instead: "
            "`for x in sorted(pending):` (one reviewable line), or "
            "keep the work queue a list")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for qual, fn, cls in _unit_functions(ctx):
            for loop in (n for n in ast.walk(fn)
                         if isinstance(n, ast.For)):
                if not _set_provenance(ctx, loop.iter, loop):
                    continue
                hit = None
                for stmt in loop.body:
                    for sub in ast.walk(stmt):
                        if not isinstance(sub, ast.Call):
                            continue
                        kind = _agreement_call(ctx, sub, cls)
                        if kind is None and \
                                _last_seg(ctx.dotted(sub.func)) in \
                                _HANDOFF_SEGS:
                            kind = f"handoff:" \
                                f"{_last_seg(ctx.dotted(sub.func))}"
                        if kind:
                            hit = kind
                            break
                    if hit:
                        break
                if hit is None:
                    continue
                yield ctx.finding(
                    loop, self.code,
                    f"iteration over a set issues a cross-process "
                    f"agreement point ({hit}) in `{qual}` — set order "
                    f"is per-process, so the agreement order diverges",
                    hint=self.hint)


_SET_METHOD_SEGS = frozenset({"union", "intersection", "difference",
                              "symmetric_difference"})


def _set_provenance(ctx: FileContext, node: ast.AST, at: ast.AST,
                    depth: int = 0) -> bool:
    """True when ``node`` is statically a ``set``: a literal /
    comprehension / ``set()``/``frozenset()`` call / set-algebra method
    or operator over one, or a name whose visible binding is one.
    Unknown provenance stays silent (``sorted(s)`` is a list — the
    compliant spelling)."""
    if depth > 4:
        return False
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        if isinstance(node.func, ast.Name) and \
                node.func.id in ("set", "frozenset"):
            return True
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _SET_METHOD_SEGS:
            return _set_provenance(ctx, node.func.value, at, depth + 1)
        return False
    if isinstance(node, ast.BinOp) and \
            isinstance(node.op, (ast.BitOr, ast.BitAnd, ast.Sub,
                                 ast.BitXor)):
        return _set_provenance(ctx, node.left, at, depth + 1) or \
            _set_provenance(ctx, node.right, at, depth + 1)
    if isinstance(node, (ast.Name, ast.Attribute)):
        d = ctx.dotted(node)
        if d:
            val = ctx.resolve_binding(d, at)
            if val is not None:
                return _set_provenance(ctx, val, at, depth + 1)
    return False


# -- MH403 — clock discipline -----------------------------------------------

@register
class ClockDisciplineRule(Rule):
    code = "MH403"
    name = "clock-discipline"
    summary = ("raw wall-clock read (time.time/perf_counter/monotonic/"
               "sleep) in the serving plane outside the declared "
               "CLOCK_SITES vocabulary")
    hint = ("serving-plane lifecycle decisions (deadlines, health, "
            "backoff, autoscaling, stall simulation) run on the ONE "
            "injected engine clock (`self._clock()` — a VirtualClock "
            "in tests, `faults.default_clock` in production), so "
            "every process and every replay sees the same time. A raw "
            "time.* read forks the time source: route it through the "
            "engine clock, or — for a genuinely new raw site — add "
            "its unit to serving/faults.py CLOCK_SITES first (the "
            "FENCE_SITES pattern). time.sleep never belongs in "
            "serving: stalls advance the VirtualClock")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not (_in_serving_tree(ctx) or _defines_dispatch(ctx)):
            return
        sites = _clock_sites(ctx)
        for node in ctx.by_type(ast.Call):
            q = ctx.qualname(node.func)
            if q not in _WALL_CLOCK_QUALS:
                continue
            unit = enclosing_unit(ctx, node)
            if unit is not None:
                uq = unit[0]
                if any(uq == s or uq.endswith("." + s) for s in sites):
                    continue
            where = unit[0] if unit else "<module>"
            yield ctx.finding(
                node, self.code,
                f"raw wall-clock read `{q}` in `{where}` — outside "
                f"the declared CLOCK_SITES {sorted(sites)}",
                hint=self.hint)


# -- MH404 — ambient randomness on replay paths -----------------------------

@register
class AmbientRandomnessRule(Rule):
    code = "MH404"
    name = "ambient-randomness"
    summary = ("ambient randomness in the serving plane: stdlib "
               "random.*, the global numpy generator, an unseeded "
               "default_rng, or a fresh PRNGKey outside sampling's "
               "seed derivation")
    hint = ("byte-identical failover/preemption replay is a pure "
            "function of request seeds: every draw must come from "
            "sampling.lane_key(seed) derivation (fold_in/split/"
            "advance_lane) or an explicitly seeded generator "
            "(np.random.default_rng(seed) — the fault injector's "
            "sanctioned source). Ambient entropy (random.*, module-"
            "level np.random draws, default_rng(), a fresh PRNGKey "
            "outside serving/sampling.py) differs per process and per "
            "run, so replays and pod peers silently diverge")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not (_in_serving_tree(ctx) or _defines_dispatch(ctx)):
            return
        in_sampling = ctx.module.rsplit(".", 1)[-1] == "sampling"
        for node in ctx.by_type(ast.Call):
            q = ctx.qualname(node.func)
            if not q:
                continue
            if q in _SEEDED_RNG_QUALS:
                if not node.args and not node.keywords:
                    yield ctx.finding(
                        node, self.code,
                        f"`{q}()` with no seed draws ambient OS "
                        f"entropy — replays and pod peers diverge",
                        hint=self.hint)
                continue
            if q in _FRESH_KEY_QUALS:
                if not in_sampling:
                    yield ctx.finding(
                        node, self.code,
                        f"fresh `{q}` outside sampling's seed "
                        f"derivation — request streams must derive "
                        f"every key from sampling.lane_key",
                        hint=self.hint)
                continue
            if q.startswith("random.") or q.startswith("numpy.random."):
                yield ctx.finding(
                    node, self.code,
                    f"`{q}` draws from ambient/global RNG state — "
                    f"not a pure function of request seeds",
                    hint=self.hint)


# -- MH405 — block-store key namespace --------------------------------------

@register
class StoreKeyNamespaceRule(Rule):
    code = "MH405"
    name = "store-key-namespace"
    summary = ("block-store key built from a process-divergent value "
               "without the process-id namespace — cross-process key "
               "collisions")
    hint = ("a store key derived from per-process state (a local slot "
            "number, a peer-read value) can collide across processes: "
            "two workers write the same key for DIFFERENT rows and "
            "one silently wins. Namespace such keys by the process id "
            "(the BlockStoreParameter pattern: "
            "f\"{ns}/g/{t}/{part}/{src}\" carries the source pid) or "
            "derive them from pod-uniform coordinates only")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not _file_has_div_roots(ctx):
            return
        for qual, fn, cls in _unit_functions(ctx):
            scan = _div_scan(ctx, fn, cls)
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "put"
                        and _storeish_receiver(ctx, node)
                        and node.args):
                    continue
                key = node.args[0]
                if isinstance(key, ast.Name):
                    bound = ctx.resolve_binding(key.id, node)
                    if bound is not None:
                        key = bound
                parts = _key_parts(key)
                if parts is None:
                    continue
                div = [p for p in parts
                       if scan.div_use(p, node.lineno) is not None]
                if not div or scan.pid_in_parts(parts):
                    continue
                yield ctx.finding(
                    node, self.code,
                    f"store key interpolates process-divergent value "
                    f"`{ast.unparse(div[0])[:40]}` without a process-"
                    f"id component in `{qual}` — keys can collide "
                    f"across processes",
                    hint=self.hint)


def _key_parts(key: ast.AST) -> Optional[List[ast.AST]]:
    """Non-constant components of a constructed key: f-string
    interpolations or ``+``-concat operands. None when the key is not
    a visible construction (a helper call, a plain constant)."""
    if isinstance(key, ast.JoinedStr):
        return [v.value for v in key.values
                if isinstance(v, ast.FormattedValue)]
    if isinstance(key, ast.BinOp) and isinstance(key.op, ast.Add):
        parts: List[ast.AST] = []
        stack = [key]
        while stack:
            n = stack.pop()
            if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Add):
                stack.extend([n.left, n.right])
            elif not isinstance(n, ast.Constant):
                parts.append(n)
        return parts
    return None


# -- the sync-point inventory (--report sync-points) ------------------------

_ASY_CODES = ("ASY301", "ASY302", "ASY303", "ASY304", "ASY305",
              "ASY306", "ASY307", "ASY308", "ASY309", "ASY310")


def sync_point_inventory(contexts: Sequence[FileContext]) -> List[dict]:
    """The async-refactor worksheet: every DECLARED sync (fence /
    fence_wait call) and every ASY finding on a hot-path-reachable
    unit, each with its root chain — what ``python -m bigdl_tpu.
    analysis --report sync-points`` prints. Suppressed findings
    (``# analysis: ok``) are listed with ``suppressed: true`` rather
    than hidden: the inventory is for reading, not gating."""
    from bigdl_tpu.analysis.core import _SUPPRESS_RE

    asy_rules = [r for r in all_rules_registry() if r.code in _ASY_CODES]
    out: List[dict] = []
    for ctx in contexts:
        if _is_fence_module(ctx):
            continue
        sites = _fence_sites(ctx)
        dsites = _delayed_sites(ctx)
        knobs = ", ".join(sorted(_window_knobs(ctx)))
        for qual, fn, chain in _hot_units(ctx):
            scan = _asy_scan(ctx, fn)
            for node, kind, site in scan.fences:
                if site is not None and site not in sites:
                    continue        # vocabulary drift: listed as ASY302
                # the window column: which sites sit BEHIND the
                # dispatch-ahead window (delayed consumer, depth from
                # the declared knob) vs consumed inline at depth 0
                window = (f"delayed (depth: {knobs})"
                          if kind == "fence" and site in dsites
                          else "inline")
                out.append({
                    "path": ctx.relpath,
                    "line": node.lineno + ctx.line_base,
                    "function": qual,
                    "chain": list(chain),
                    "kind": f"{kind}:{site or '?'}",
                    "classification": "declared sync point",
                    "window": window,
                    "detail": ctx.source_line(node.lineno),
                    "suggestion": (
                        "one batched device_get readback"
                        if kind == "fence" else
                        "completion wait (timer pin)"),
                    "suppressed": False,
                })
        for rule in asy_rules:
            for f in rule.check(ctx):
                out.append({
                    "path": f.path, "line": f.line,
                    "function": "", "chain": [],
                    "kind": f.code,
                    "classification": f.message,
                    "window": "",
                    "detail": f.source,
                    "suggestion": rule.hint,
                    "suppressed": bool(_SUPPRESS_RE.search(f.source)),
                })
    out.sort(key=lambda e: (e["path"], e["line"], e["kind"]))
    return out


def all_rules_registry():
    from bigdl_tpu.analysis.core import all_rules

    return all_rules()


# -- the lockstep inventory (--report lockstep) ------------------------------

_MH_CODES = ("MH401", "MH402", "MH403", "MH404", "MH405")


def lockstep_inventory(contexts: Sequence[FileContext]) -> List[dict]:
    """The multi-host pod worksheet (``--report lockstep``, the
    ``--report sync-points`` twin): everything the process-per-host
    refactor must keep in LOCKSTEP across the pod —

    * **agreement points**: every unit that directly issues a
      collective, a compiled-step dispatch, or a block-store barrier
      (with its hot-path root chain when it has one) — the lines every
      process must execute the same number of times in the same order;
    * **divergence roots**: every unit that reads
      ``jax.process_index()``/``process_count()`` or a per-peer store —
      the values a lockstep decision must never branch on;
    * **declared clock sites**: the CLOCK_SITES units (the only legal
      raw wall-clock reads in the serving plane);
    * any un-fixed MH401–405 finding, listed like the ASY findings in
      the sync-point report (suppressed ones shown, not hidden).
    """
    from bigdl_tpu.analysis.core import _SUPPRESS_RE

    mh_rules = [r for r in all_rules_registry() if r.code in _MH_CODES]
    out: List[dict] = []
    for ctx in contexts:
        chains = _hot_chains(ctx)
        sites = _clock_sites(ctx)
        for qual, fn, cls in _unit_functions(ctx):
            chain = chains.get(qual)
            kinds: List[Tuple[ast.AST, str, str]] = []
            step_segs = _step_attr_segs(ctx)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                q = ctx.qualname(node.func)
                seg = _last_seg(ctx.dotted(node.func))
                if q in _COLLECTIVE_QUALS:
                    kinds.append((node, "agreement",
                                  f"collective:{q.rsplit('.', 1)[-1]}"))
                elif seg == "_dispatch" or seg in step_segs:
                    kinds.append((node, "agreement", "dispatch"))
                elif seg in _BARRIER_SEGS:
                    kinds.append((node, "agreement", f"barrier:{seg}"))
                if q in _PROCESS_TOPOLOGY_QUALS:
                    kinds.append((node, "divergence",
                                  q.rsplit(".", 1)[-1]))
                elif seg in _PEER_READ_SEGS and \
                        _storeish_receiver(ctx, node):
                    kinds.append((node, "divergence", "peer-read"))
                if q in _WALL_CLOCK_QUALS and any(
                        qual == s or qual.endswith("." + s)
                        for s in sites):
                    kinds.append((node, "clock", q))
            seen: Set[Tuple[int, str, str]] = set()
            for node, cat, what in kinds:
                key = (node.lineno, cat, what)
                if key in seen:
                    continue
                seen.add(key)
                out.append({
                    "path": ctx.relpath,
                    "line": node.lineno + ctx.line_base,
                    "function": qual,
                    "chain": list(chain) if chain else [],
                    "kind": f"{cat}:{what}",
                    "classification": {
                        "agreement": "cross-process agreement point",
                        "divergence": "process-divergence root",
                        "clock": "declared clock site",
                    }[cat],
                    "detail": ctx.source_line(node.lineno),
                    "suggestion": "",
                    "suppressed": False,
                })
        for rule in mh_rules:
            for f in rule.check(ctx):
                out.append({
                    "path": f.path, "line": f.line,
                    "function": "", "chain": [],
                    "kind": f.code,
                    "classification": f.message,
                    "detail": f.source,
                    "suggestion": rule.hint,
                    "suppressed": bool(_SUPPRESS_RE.search(f.source)),
                })
    out.sort(key=lambda e: (e["path"], e["line"], e["kind"]))
    return out
