"""The ONE recursive jaxpr walker every static check in this repo shares.

Before this module existed the repo had two ad-hoc IR traversals —
``protocols.spec.jaxpr_materializes_shape`` (generic recursion into every
sub-jaxpr, used by the no-[D, D] dryrun probe) and
``launch.roofline.jaxpr_cost`` (loop-aware fold: scan bodies multiplied by
trip count, cond branches max-combined) — which agreed on nothing and had to
be kept in sync by hand. Both are now thin shims on the two traversal
primitives here, and every ``repro.analysis`` rule is built on the same
primitives, so "which equations does a program contain" has exactly one
answer.

Two traversal modes, one sub-jaxpr discovery:

* ``sub_jaxprs(eqn)`` — THE single place an equation's sub-programs are
  enumerated. Each is a ``SubJaxpr`` record carrying the open jaxpr, its
  execution multiplicity (scan length, shard_map mesh size), whether it is
  an *alternative* (cond/switch branches — at most one executes per visit),
  and whether the loop-aware cost fold counts it (a ``while`` condition or a
  custom-derivative side thunk is traversed by searches but priced by
  nothing, matching the historical cost model).

* ``iter_eqns(jaxpr)`` — flat generator over EVERY equation, recursively
  through all sub-jaxprs (counted or not), yielding an ``EqnSite`` with the
  equation, its path from the root, its execution multiplicity, and whether
  it sits inside a ``lax.scan``/``lax.while`` body. This is what searches
  (the shape probe, the host-transfer scan, the collective census walk)
  build on.

* ``fold(jaxpr, eqn_fn, ...)`` — structured fold for cost-model style
  accounting: per-equation values are combined with ``add`` in program
  order, a sub-jaxpr's subtotal is ``scale``d by its multiplicity *after*
  being folded (so ``n * (a + b)``, bit-identical to the historical
  jaxpr_cost arithmetic), and alternatives are reduced with ``alt``
  (componentwise max for costs).

This module deliberately imports nothing from ``repro.*`` so that
``protocols.spec`` (and anything else deep in the package graph) can depend
on it without cycles.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Iterator, Optional, Tuple

from jax.extend.core import ClosedJaxpr, Jaxpr


#: primitives whose sub-jaxpr bodies execute once per loop iteration
_LOOP_PRIMS = ("scan", "while")


def _open(j):
    """Normalize ClosedJaxpr -> Jaxpr (sub-jaxpr params mix both forms)."""
    return j.jaxpr if isinstance(j, ClosedJaxpr) else j


@dataclass(frozen=True)
class SubJaxpr:
    """One sub-program of an equation, with its traversal semantics."""
    jaxpr: Any                 # open Jaxpr
    tag: str                   # role label, e.g. "body", "branch2", "call"
    mult: float = 1.0          # executions per parent visit (scan length, ...)
    alternative: bool = False  # cond/switch branch: at most one executes
    counted: bool = True       # False -> searches visit it, cost folds skip


@dataclass(frozen=True)
class EqnSite:
    """One equation's occurrence in the recursive traversal."""
    eqn: Any
    path: Tuple[str, ...]      # enclosing-equation labels from the root
    mult: float                # total execution multiplicity at this site
    in_loop: bool              # inside a scan/while body (per-iteration code)

    @property
    def pretty_path(self) -> str:
        name = getattr(self.eqn.primitive, "name", "?")
        return "/".join(self.path + (name,)) or name


def _iter_param_jaxprs(params: dict):
    """(key, index_or_None, open_jaxpr) for every (Closed)Jaxpr in params."""
    for key, val in params.items():
        vs = val if isinstance(val, (list, tuple)) else (val,)
        for i, v in enumerate(vs):
            if isinstance(v, (ClosedJaxpr, Jaxpr)):
                idx = i if isinstance(val, (list, tuple)) else None
                yield key, idx, _open(v)


def sub_jaxprs(eqn) -> Tuple[SubJaxpr, ...]:
    """Every sub-program of ``eqn``, classified.

    scan bodies carry ``mult=length``; shard_map bodies ``mult=mesh.size``
    (per-shard shapes — every device executes the body); cond/switch
    branches are ``alternative``; a while's condition and any
    generically-discovered extra sub-jaxpr (beyond the first of
    ``jaxpr``/``call_jaxpr``/``fun_jaxpr``) is ``counted=False`` so the
    cost fold reproduces the historical accounting while searches still
    reach every equation."""
    prim = eqn.primitive.name
    params = eqn.params
    if prim == "scan":
        return (SubJaxpr(_open(params["jaxpr"]), "body",
                         mult=float(params["length"])),)
    if prim == "while":
        return (SubJaxpr(_open(params["body_jaxpr"]), "body"),
                SubJaxpr(_open(params["cond_jaxpr"]), "cond", counted=False))
    if prim == "cond":
        return tuple(SubJaxpr(_open(b), f"branch{i}", alternative=True)
                     for i, b in enumerate(params["branches"]))
    if prim == "shard_map":
        mesh = params.get("mesh")
        mult = float(mesh.size) if mesh is not None else 1.0
        return (SubJaxpr(_open(params["jaxpr"]), "body", mult=mult),)
    # generic primitives (pjit, remat/checkpoint, custom_jvp/vjp, closed
    # calls, ...): the FIRST of these keys is the executed program the cost
    # model prices; anything else jaxpr-valued in params is traversed by
    # searches only.
    primary = None
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in params:
            primary = _open(params[key])
            break
    subs = []
    if primary is not None:
        subs.append(SubJaxpr(primary, "call"))
    seen = {id(primary)}
    for key, idx, j in _iter_param_jaxprs(params):
        if id(j) in seen:
            continue
        seen.add(id(j))
        tag = key if idx is None else f"{key}[{idx}]"
        subs.append(SubJaxpr(j, tag, counted=False))
    return tuple(subs)


def iter_eqns(jaxpr, *, _path: Tuple[str, ...] = (), _mult: float = 1.0,
              _in_loop: bool = False) -> Iterator[EqnSite]:
    """Yield an ``EqnSite`` for every equation, recursively through every
    sub-jaxpr (counted or not). Accepts a ClosedJaxpr or an open Jaxpr."""
    jaxpr = _open(jaxpr)
    for eqn in jaxpr.eqns:
        yield EqnSite(eqn=eqn, path=_path, mult=_mult, in_loop=_in_loop)
        prim = eqn.primitive.name
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(
                sub.jaxpr,
                _path=_path + (f"{prim}.{sub.tag}",),
                _mult=_mult * sub.mult,
                _in_loop=_in_loop or (prim in _LOOP_PRIMS
                                      and sub.tag == "body"))


def fold(jaxpr, eqn_fn: Callable[[Any], Any], *,
         add: Callable[[Any, Any], Any],
         scale: Callable[[Any, float], Any],
         alt: Callable[[Any, Any], Any],
         zero: Any):
    """Loop-aware structured fold over a (Closed)Jaxpr.

    For each equation in program order: ``add`` the equation's own value
    (``eqn_fn(eqn)``), then for each *counted* sub-jaxpr ``add`` its folded
    subtotal ``scale``d by the sub's multiplicity — computing the subtotal
    first and scaling once keeps the float arithmetic bit-identical to the
    historical ``n * body_total`` accounting. Alternative subs (cond
    branches) are each folded and ``alt``-reduced before being added.

    ``eqn_fn`` may return a *list* to apply several ordered contributions
    as separate ``add`` calls — float addition is not associative, so a
    cost model porting ``total += a; total += b`` accounting must keep the
    two adds separate to stay bit-identical (see ``roofline.jaxpr_cost``)."""
    total = zero
    for eqn in _open(jaxpr).eqns:
        v = eqn_fn(eqn)
        for part in (v if isinstance(v, list) else (v,)):
            total = add(total, part)
        alts = None
        for sub in sub_jaxprs(eqn):
            if not sub.counted:
                continue
            v = scale(fold(sub.jaxpr, eqn_fn, add=add, scale=scale, alt=alt,
                           zero=zero), sub.mult)
            if sub.alternative:
                alts = v if alts is None else alt(alts, v)
            else:
                total = add(total, v)
        if alts is not None:
            total = add(total, alts)
    return total


# ---------------------------------------------------------------------------
# the shared shape probe (the old spec.jaxpr_materializes_shape core)
# ---------------------------------------------------------------------------

def _is_float_dtype(dtype) -> bool:
    import jax.numpy as jnp
    return dtype is not None and jnp.issubdtype(dtype, jnp.floating)


def _aval_matches(aval, shape: Tuple[int, ...], floating_only: bool) -> bool:
    if tuple(getattr(aval, "shape", ())) != shape:
        return False
    dtype = getattr(aval, "dtype", None)
    return (not floating_only or dtype is None or _is_float_dtype(dtype))


def find_avals(jaxpr, match: Callable[[Any], bool],
               max_sites: Optional[int] = None):
    """All equation sites where any operand/result aval satisfies ``match``
    — the search primitive behind the shape probe and the no-dense-mixing
    rule. Returns ``[(EqnSite, aval), ...]`` (first matching aval per
    equation)."""
    out = []
    for site in iter_eqns(jaxpr):
        for v in list(site.eqn.invars) + list(site.eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and match(aval):
                out.append((site, aval))
                break
        if max_sites is not None and len(out) >= max_sites:
            break
    return out


def materializes_shape(closed_jaxpr, shape: Tuple[int, ...],
                       floating_only: bool = True) -> bool:
    """True if any equation in the jaxpr (recursively, through scan/cond/
    pjit sub-jaxprs) produces or consumes an array of exactly ``shape`` —
    the O(D²) smoking gun the sparse path's no-[D, D] guarantee is pinned
    against.

    ``floating_only`` (the default) restricts the probe to float dtypes:
    the dense mixing operator is always a float matrix, while legitimate
    O(D) index structures can coincide with the shape (gossip_async's
    [R, D] int32 partner stack has R == D for odd D). A float coincidence
    — a model whose packed width happens to equal D — would still trip
    the probe; pick shapes/widths accordingly when asserting."""
    shape = tuple(shape)
    return bool(find_avals(
        closed_jaxpr, lambda a: _aval_matches(a, shape, floating_only),
        max_sites=1))
