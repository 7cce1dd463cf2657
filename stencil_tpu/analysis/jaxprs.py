"""Shared jaxpr-walking utilities for the stencil-lint checkers.

All three checkers operate on the same substrate: trace a function to a
jaxpr WITHOUT executing it (``jax.make_jaxpr`` over
``ShapeDtypeStruct``s), then pattern-match primitives. Nothing here
moves a byte — tracing is pure Python, so the whole pass runs in
seconds on any backendless CI box.
"""

from __future__ import annotations

from typing import Any, Callable, Iterator, List, Optional, Tuple

import jax
from jax.extend import core as jax_core

Jaxpr = jax_core.Jaxpr
ClosedJaxpr = jax_core.ClosedJaxpr
Literal = jax_core.Literal
Var = jax_core.Var


def trace(fn: Callable, *args: Any) -> ClosedJaxpr:
    """Trace ``fn`` on abstract arguments (no FLOPs, no devices)."""
    return jax.make_jaxpr(fn)(*args)


def _param_jaxprs(params: dict) -> Iterator[Jaxpr]:
    """Every sub-jaxpr reachable through an eqn's params (pjit bodies,
    cond branches, scan/while bodies, pallas kernels, shard_map...)."""
    for v in params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                if isinstance(item, ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, Jaxpr):
                    yield item


def iter_eqns(jaxpr: Jaxpr) -> Iterator[jax_core.JaxprEqn]:
    """All eqns of ``jaxpr`` and (recursively) of every sub-jaxpr, in
    syntactic order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _param_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def find_pallas_kernels(jaxpr: Jaxpr) -> List[Tuple[str, Jaxpr]]:
    """(kernel_name, kernel_jaxpr) for every ``pallas_call`` reachable
    from ``jaxpr`` (through jit/shard_map/cond/... nesting)."""
    out: List[Tuple[str, Jaxpr]] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        kj = eqn.params.get("jaxpr")
        if isinstance(kj, ClosedJaxpr):
            kj = kj.jaxpr
        if not isinstance(kj, Jaxpr):
            continue
        info = eqn.params.get("name_and_src_info")
        name = getattr(info, "name", None) or str(info) or "<kernel>"
        out.append((name, kj))
    return out


def leaf_aval(leaf: Any) -> Tuple[Tuple[int, ...], str, bool]:
    """(shape, dtype, weak_type) of an array-ish leaf."""
    import numpy as np

    shape = tuple(int(d) for d in getattr(leaf, "shape", ()))
    dtype = str(np.dtype(getattr(leaf, "dtype", np.float32)))
    weak = bool(getattr(leaf, "weak_type", False))
    aval = getattr(leaf, "aval", None)
    if aval is not None:
        weak = bool(getattr(aval, "weak_type", weak))
    return shape, dtype, weak


def flat_with_paths(tree: Any) -> List[Tuple[str, Any]]:
    """(path_string, leaf) pairs in canonical flatten order."""
    return [("".join(str(k) for k in path), leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def dtype_pairs(curr: Any, next_: Any
                ) -> Optional[List[Tuple[str,
                                         Tuple[Tuple[int, ...], str, bool],
                                         Tuple[Tuple[int, ...], str, bool]]]]:
    """The shared curr/next dtype-pair walk: flatten both trees and
    pair each leaf's (shape, dtype, weak_type) by position —
    ``(path, curr_aval, next_aval)`` per leaf, or ``None`` when the
    two trees disagree on leaf count (the pytree itself drifted).
    Both the recompile checker (carried-state fingerprint stability)
    and the precision checker (wire formats must not leak into the
    carried state) consume this one walker."""
    cf, nf = flat_with_paths(curr), flat_with_paths(next_)
    if len(cf) != len(nf):
        return None
    return [(cpath, leaf_aval(cleaf), leaf_aval(nleaf))
            for (cpath, cleaf), (_np, nleaf) in zip(cf, nf)]


def literal_int(x: Any) -> Optional[int]:
    """Static integer value of a jaxpr atom, or None when traced."""
    if isinstance(x, Literal):
        try:
            return int(x.val)
        except (TypeError, ValueError):
            return None
    if isinstance(x, (int,)):
        return int(x)
    return None


def is_semaphore_ref(atom: Any) -> bool:
    """True for operands typed as Pallas semaphore memory (the aval
    prints as ``MemRef<semaphore_mem>{dma_sem[...]}`` / barrier_sem)."""
    aval = getattr(atom, "aval", None)
    if aval is None:
        return False
    s = str(aval)
    return "sem" in s and ("semaphore" in s or "barrier" in s
                           or "dma_sem" in s)


def index_key(transforms: Any) -> Tuple:
    """Hashable static description of a ref's indexers (``.at[...]``)
    for identity purposes: literal ints stay ints, traced indices
    become the wildcard '?'. Two refs with equal (var, index_key) are
    treated as the same semaphore cell."""
    out: List[Any] = []

    def visit(o: Any) -> None:
        if isinstance(o, (tuple, list)):
            for i in o:
                visit(i)
            return
        n = literal_int(o)
        if n is not None:
            out.append(n)
        elif isinstance(o, Var):
            out.append("?")
        else:
            # NDIndexer / Slice carriers: recurse into their leaves
            indices = getattr(o, "indices", None)
            if indices is not None:
                visit(indices)
                return
            start = getattr(o, "start", None)
            size = getattr(o, "size", None)
            if start is not None or size is not None:
                visit([start, size])
    visit(transforms)
    return tuple(out)
