"""Glue between the benchmark's configuration files and the system under
test (``src/repro``): the program's model config built from a
configuration file, and the benchmark's weights put into the program's
parameter layout.  The tables of names come from the configuration's
reference (``PROGRAM_PATHS``, ``program_overrides``), so that a new
architecture is a new reference file.  Only this module and the drivers
import the program.
"""
from __future__ import annotations

import os
import sys

from chipbench.harness.spec import reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def import_program():
    """Put the checkout's ``src`` on the path; raises ImportError where
    the checkout holds no program."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401


def model_config(c: dict):
    """The program's ModelConfig for configuration file ``c``: the
    registered architecture with every size taken from the file."""
    from repro.configs import get_config
    return get_config(c["program_arch"]).with_overrides(
        **reference(c).program_overrides(c))


def program_tree(c: dict, weights: dict) -> dict:
    """The benchmark's weights in the program's parameter layout."""
    paths = reference(c).PROGRAM_PATHS
    tree: dict = {}
    for name, value in weights.items():
        node = tree
        *parents, leaf = paths[name]
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = value
    return tree


def check_layout(mc, tree_shapes) -> None:
    """Raise unless ``tree_shapes`` matches the program's own parameter
    shapes for ``mc`` leaf for leaf."""
    import jax
    from repro.models import registry
    want = registry.param_shapes(mc)
    got_s, want_s = jax.tree.structure(tree_shapes), jax.tree.structure(want)
    if got_s != want_s:
        raise ValueError(f"weight layout {got_s} is not the program's "
                         f"{want_s}")
    for g, w in zip(jax.tree.leaves(tree_shapes), jax.tree.leaves(want)):
        if g.shape != w.shape or g.dtype != w.dtype:
            raise ValueError(f"weight {g.shape} {g.dtype} is not the "
                             f"program's {w.shape} {w.dtype}")


def leaf_norms(c: dict, tree) -> dict:
    """Per-leaf L2 norms of a program tree, keyed by reference name."""
    import jax.numpy as jnp
    out = {}
    for name, path in reference(c).PROGRAM_PATHS.items():
        node = tree
        for p in path:
            node = node.get(p) if isinstance(node, dict) else None
            if node is None:
                break
        if node is not None:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(
                node.astype(jnp.float32))))
    return out
