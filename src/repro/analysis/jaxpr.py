"""Walkers over jaxprs: the *explicit* collectives a program asked for.

GSPMD inserts collectives of its own at compile time (FSDP weight
gathers, TP partial-sum reductions) — those live in the HLO and are
census'd by ``analysis.hlo``.  The jaxpr level sees only the exchanges
the repo's code wrote explicitly (the ``shard_map`` wire collective, the
scale pmax), each tagged with the logical *axis names* it runs over —
which is exactly the information the dtype-flow rules need: "does this
cross the data axis" is a name lookup here, not a device-id
reconstruction.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Iterator, List, Tuple

# primitives that exchange bytes between devices when bound inside
# shard_map / pmap.  psum_invariant is what a checked (check_vma)
# shard_map binds psum to; axis_index and pvary are excluded: they
# read/adjust replication, nothing moves.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "psum_invariant", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "psum_scatter", "reduce_scatter",
})


def _subjaxprs(params: dict) -> Iterator[Any]:
    """Every jaxpr nested in an equation's params (call_jaxpr, branches,
    scan/while bodies, custom_vjp closures, shard_map bodies, ...)."""
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if hasattr(x, "eqns"):                   # Jaxpr
                yield x
            elif hasattr(x, "jaxpr") and hasattr(
                    getattr(x, "jaxpr", None), "eqns"):  # ClosedJaxpr
                yield x.jaxpr


def iter_eqns(jaxpr) -> Iterator[Any]:
    """All equations of ``jaxpr`` (a Jaxpr or ClosedJaxpr), recursively
    through every nested call/control-flow/shard_map body."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def _axis_names(params: dict) -> Tuple[str, ...]:
    """The logical mesh axes a collective equation runs over, whatever
    the primitive calls its parameter (``axes``, ``axis_name``)."""
    for key in ("axes", "axis_name"):
        if key in params:
            v = params[key]
            if isinstance(v, (list, tuple)):
                return tuple(str(a) for a in v)
            return (str(v),)
    return ()


@dataclasses.dataclass(frozen=True)
class ExplicitCollective:
    """One explicitly-written collective equation in a traced program."""
    primitive: str            # "psum", "all_to_all", ...
    axes: Tuple[str, ...]     # logical axis names it exchanges over
    dtype: str                # canonical dtype name ("float32", "int8")
    dims: Tuple[int, ...]     # result shape (first output)

    @property
    def numel(self) -> int:
        return math.prod(self.dims) if self.dims else 1

    def over(self, axis: str) -> bool:
        return axis in self.axes


def explicit_collectives(jaxpr) -> List[ExplicitCollective]:
    """Every collective primitive bound anywhere in ``jaxpr`` (a Jaxpr or
    ClosedJaxpr), in trace order."""
    out = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMITIVES:
            continue
        aval = eqn.outvars[0].aval
        dtype = getattr(aval, "dtype", None)
        shape = tuple(getattr(aval, "shape", ()) or ())
        out.append(ExplicitCollective(
            # psum_invariant is jax-internal for psum-under-shard_map:
            # report the name the user wrote
            primitive="psum" if name == "psum_invariant" else name,
            axes=_axis_names(eqn.params),
            dtype="" if dtype is None else str(dtype),
            dims=shape))
    return out
