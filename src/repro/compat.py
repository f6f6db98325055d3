"""One home for the jax mesh and shard_map APIs the repo uses.

The repo targets jax 0.9: ``jax.shard_map``, ``jax.make_mesh(...,
axis_types=...)``, ``jax.set_mesh`` and
``jax.sharding.get_abstract_mesh``.  Everything that needs one of these
goes through this module, so a future API move is fixed in one place.
"""
from __future__ import annotations

import contextlib

import jax

shard_map = jax.shard_map


def axis_size(axis_name) -> int:
    """Static size of a named mapped axis."""
    return jax.lax.axis_size(axis_name)


def pcast(x, axis_names, *, to: str = "varying"):
    """Explicit varying/unvarying marking inside shard_map."""
    return jax.lax.pcast(x, axis_names, to=to)


def make_mesh(axis_shapes, axis_names) -> jax.sharding.Mesh:
    """jax.make_mesh with every axis of type Auto."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def current_mesh():
    """The abstract mesh set by ``set_mesh`` (empty outside any mesh
    context), usable as ``shard_map``'s ``mesh=`` argument."""
    return jax.sharding.get_abstract_mesh()


@contextlib.contextmanager
def set_mesh(mesh: jax.sharding.Mesh):
    """``with jax.set_mesh(mesh)``: ``current_mesh`` readers see ``mesh``
    during tracing."""
    with jax.set_mesh(mesh):
        yield mesh
