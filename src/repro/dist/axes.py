"""Logical-axis registry and pattern-string activation sharding.

The ``nn``/``models`` layers annotate activations with one character per
array axis:

    'b'  — the batch-like axis: sharded over the data-parallel mesh axes
           (``("data",)`` or ``("pod", "data")`` on the multi-pod mesh)
    'm'  — a model-parallel axis (heads, hidden features): sharded over
           the tensor-parallel ``model`` mesh axis
    '.'  — replicated / unconstrained

e.g. ``constrain(x, "b.m.")`` on a ``[B, S, H, hd]`` tensor shards batch
over data and heads over model.  The registry a ``constrain`` call reads
is *scoped*, not global: a :class:`repro.api.RunContext` activates its
:class:`AxisRegistry` (built from the run's ``MeshSpec``) around every
trace via :func:`axis_scope`, so two contexts with different meshes
coexist in one process.  Outside any scope the immutable default applies
(single-device identity), so library code is importable and testable with
no mesh at all.  The registry carries its mesh, so a constraint names its
devices itself and no ambient ``with mesh:`` context is needed.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .scope import Scoped


@dataclasses.dataclass(frozen=True)
class AxisRegistry:
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    data_size: int = 1
    model_size: int = 1
    mesh: Optional[Mesh] = None       # set whenever a size exceeds 1


_AXES: Scoped[AxisRegistry] = Scoped("repro.dist.axes", AxisRegistry())


def axis_scope(registry: AxisRegistry):
    """Context manager: trace the enclosed computation under ``registry``
    (re-entrant; restores the previous registry on exit).  This is how
    ``repro.api.RunContext`` binds a mesh's logical axes with no global
    state."""
    return _AXES.scope(registry)


def reset_axes() -> None:
    """Back to the single-device identity default (tests)."""
    _AXES.reset_default()


def get_axes() -> AxisRegistry:
    return _AXES.get()


def get_model_size() -> int:
    """Tensor-parallel degree currently in scope (1 = no TP)."""
    return _AXES.get().model_size


def get_data_size() -> int:
    return _AXES.get().data_size


def registry_for_mesh(mesh) -> AxisRegistry:
    """The :class:`AxisRegistry` describing a concrete mesh (pod is outer
    data parallelism; the axis whitelist lives in ``sharding``)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    daxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dsize = 1
    for a in daxes:
        dsize *= sizes[a]
    return AxisRegistry(daxes or ("data",), "model", dsize,
                        int(sizes.get("model", 1)), mesh)


def _spec_for(pattern: str, shape: Tuple[int, ...]) -> P:
    reg = _AXES.get()
    entries = []
    for ch, dim in zip(pattern, shape):
        if ch == "b":
            ok = reg.data_size > 1 and dim % reg.data_size == 0
            entries.append(tuple(reg.data_axes) if ok else None)
        elif ch == "m":
            ok = reg.model_size > 1 and dim % reg.model_size == 0
            entries.append(reg.model_axis if ok else None)
        elif ch == ".":
            entries.append(None)
        else:
            raise ValueError(f"bad axis char {ch!r} in pattern {pattern!r}")
    return P(*entries)


def constrain(x: jax.Array, pattern: str) -> jax.Array:
    """Apply a pattern-string sharding constraint; identity on 1 device.

    ``pattern`` has one character per axis of ``x`` (see module docstring).
    """
    if len(pattern) != x.ndim:
        raise ValueError(f"pattern {pattern!r} has {len(pattern)} axes, "
                         f"array has {x.ndim} ({x.shape})")
    bad = set(pattern) - set("bm.")
    if bad:
        raise ValueError(f"bad axis chars {sorted(bad)!r} in {pattern!r}")
    reg = _AXES.get()
    if reg.data_size * reg.model_size <= 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(reg.mesh, _spec_for(pattern, x.shape)))
