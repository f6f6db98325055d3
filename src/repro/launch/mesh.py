"""Mesh construction for the launchers and tests.

Defined as functions so importing this module never touches JAX device
state.
"""
from __future__ import annotations

import jax

from repro import compat


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests / elastic re-meshing)."""
    return compat.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (host) devices exist — smoke tests."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return make_mesh((data, model), ("data", "model"))
