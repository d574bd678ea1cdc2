"""Sharding-constraint helpers usable from model code.

`constrain(x, *axes)` applies a `with_sharding_constraint` when running
under a mesh (`jax.set_mesh`); it is a no-op otherwise, so model
code stays runnable in plain CPU tests.  Axis names follow the
production mesh ("pod", "data", "model"); the data-parallel group is
("pod","data") when the pod axis exists.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _mesh_axes() -> frozenset[str]:
    m = jax.sharding.get_abstract_mesh()
    return frozenset(m.axis_names) if not m.empty else frozenset()


def set_mesh(mesh):
    """Context manager activating `mesh` for `constrain`."""
    return jax.set_mesh(mesh)


def dp_axes() -> tuple[str, ...]:
    axes = _mesh_axes()
    return tuple(a for a in ("pod", "data") if a in axes)


def resolve(*spec) -> P:
    """Build a PartitionSpec, mapping the symbolic 'dp' axis to the
    available data-parallel axes and dropping axes absent from the mesh."""
    axes = _mesh_axes()
    out = []
    for s in spec:
        if s == "dp":
            dp = dp_axes()
            out.append(dp if dp else None)
        elif s is None or s in axes:
            out.append(s)
        elif isinstance(s, tuple):
            keep = tuple(a for a in s if a in axes)
            out.append(keep if keep else None)
        else:
            out.append(None)
    return P(*out)


def axis_size(name: str) -> int:
    m = jax.sharding.get_abstract_mesh()
    if m.empty or name not in m.axis_names:
        return 1
    return m.shape[name]


def _divisible(x, spec: P) -> bool:
    for dim, s in zip(x.shape, spec):
        if s is None:
            continue
        axes = s if isinstance(s, tuple) else (s,)
        size = 1
        for a in axes:
            size *= axis_size(a)
        if dim % size != 0:
            return False
    return True


def constrain(x, *spec):
    """with_sharding_constraint if a mesh is active and the spec tiles
    evenly, else identity."""
    if not _mesh_axes():
        return x
    p = resolve(*spec)
    if not _divisible(x, p):
        return x
    return jax.lax.with_sharding_constraint(x, p)
