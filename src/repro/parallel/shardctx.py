"""Mesh context plumbing.

Model code never imports a concrete mesh; it calls :func:`hint` /
:func:`current_mesh`.  Launchers install the active mesh with
:func:`use_mesh`.  On a bare CPU (tests, smoke runs) no mesh is installed and
every hint is a no-op, so the same model code runs everywhere.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def batch_axes() -> Tuple[str, ...]:
    """Mesh axes that carry the batch/data-parallel dimension."""
    mesh = current_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axes() -> Tuple[str, ...]:
    mesh = current_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in mesh.axis_names if a == "model")


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with jax.set_mesh(mesh):
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev


def hint(x, *spec):
    """``with_sharding_constraint`` when a mesh is active, else identity.

    ``spec`` entries are axis names (str), tuples of axis names, or None.
    The special entry ``"batch"`` expands to the active batch axes.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    resolved = []
    for s in spec:
        if s == "batch":
            ax = batch_axes()
            resolved.append(ax if ax else None)
        else:
            resolved.append(s)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


def named_sharding(*spec) -> Optional[NamedSharding]:
    mesh = current_mesh()
    if mesh is None:
        return None
    resolved = []
    for s in spec:
        if s == "batch":
            ax = batch_axes()
            resolved.append(ax if ax else None)
        else:
            resolved.append(s)
    return NamedSharding(mesh, P(*resolved))
