"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Model code annotates every parameter and key activation with *logical*
axis names (``"embed"``, ``"heads"``, ``"vocab"`` …).  A rule table maps
each logical axis to an ordered list of candidate mesh-axis assignments;
at resolution time the first candidate whose mesh-axis-size product
divides the actual dimension is chosen, otherwise the dim is replicated.

This is what lets a single model definition serve a 1-device smoke test,
a 256-chip pod, and a 512-chip multi-pod mesh without edits: a 14-head
attention block simply degrades to replicated heads on a 16-way tensor
axis, while the 128-head block shards 8-ways.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat

# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# Candidate mesh assignments per logical axis, in priority order.  Each
# candidate is a tuple of mesh axis names (composed axes) or () for
# "replicate".  "fsdp" axes shard parameters/optimizer state ZeRO-style.
MeshAxes = tuple[str, ...]
Rules = Mapping[str, Sequence[MeshAxes]]

# Default production rules for a ("pod", "data", "model") mesh.
DEFAULT_RULES: Rules = {
    # --- parameter / activation axes ---
    "embed":      (("pod", "data"), ("data",), ()),   # FSDP shard dim
    "embed_nofsdp": ((),),                             # replicated variant
    "mlp":        (("model",), ()),
    "heads":      (("model",), ()),
    "kv_heads":   (("model",), ()),
    "head_dim":   ((),),
    "qkv":        (("model",), ()),
    "vocab":      (("model",), ()),
    "experts":    (("model",), ()),
    "expert_mlp": (("model",), ()),
    "state":      ((),),                               # SSM state dim
    "conv":       ((),),
    "layers":     ((),),                               # scan axis
    # --- batch/sequence activation axes ---
    "batch":      (("pod", "data"), ("data",), ()),
    "act_seq":    ((),),                               # sequence (activations)
    "cache_seq":  (("model",), ()),                    # KV-cache sequence
    "cache_batch": (("pod", "data"), ("data",), ()),   # KV-cache batch rows
    "act_embed":  ((),),
    "act_heads":  (("model",), ()),
    "act_kv_heads": (("model",), ()),
    "act_mlp":    (("model",), ()),
    "act_vocab":  (("model",), ()),
    "act_experts": (("model",), ()),
    "expert_cap": (("model",), ()),                    # MoE capacity dim
    "act_expert_mlp": (("model",), ()),
    "moe_groups": (("pod", "data"), ("data",), ()),    # MoE token groups
    "frames":     ((),),                               # audio/vision frontend
}

_local = threading.local()


def current_rules() -> Rules:
    return getattr(_local, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(rules: Rules):
    """Override the logical→mesh rule table within a scope."""
    prev = getattr(_local, "rules", DEFAULT_RULES)
    _local.rules = rules
    try:
        yield
    finally:
        _local.rules = prev


def merged_rules(overrides: Mapping[str, Sequence[MeshAxes]] | None) -> Rules:
    if not overrides:
        return dict(DEFAULT_RULES)
    out = dict(DEFAULT_RULES)
    out.update(overrides)
    return out


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------

def resolve_spec(
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Rules | None = None,
) -> P:
    """Resolve logical axes for a concrete shape into a PartitionSpec.

    Falls back to replication for any dim the preferred mesh axes do not
    divide, and never assigns the same mesh axis to two dims.
    """
    return _resolve(logical_axes, shape, dict(mesh.shape), rules)


def _resolve(logical_axes, shape, axis_sizes: Mapping[str, int],
             rules: Rules | None) -> P:
    """``resolve_spec`` over the mesh axes named in ``axis_sizes`` only."""
    rules = rules or current_rules()
    shape = tuple(getattr(shape, "shape", shape))
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    used: set[str] = set()
    parts: list = []
    for name, dim in zip(logical_axes, shape):
        if name is None:
            parts.append(None)
            continue
        candidates = rules.get(name)
        if candidates is None:
            raise KeyError(f"no sharding rule for logical axis {name!r}")
        chosen: MeshAxes = ()
        for cand in candidates:
            if any(a in used for a in cand):
                continue
            if any(a not in axis_sizes for a in cand):
                continue
            size = 1
            for a in cand:
                size *= axis_sizes[a]
            if size == 1 or (dim % size == 0 and size > 1):
                chosen = cand
                break
        if chosen:
            used.update(chosen)
            parts.append(chosen if len(chosen) > 1 else chosen[0])
        else:
            parts.append(None)
    # trim trailing Nones (canonical form)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def logical_sharding(
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Rules | None = None,
) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(logical_axes, shape, mesh, rules))


def shard_hint(x: jax.Array, *logical_axes: str | None):
    """Apply a with_sharding_constraint for logical axes over the current
    mesh's Auto axes.

    Outside a ``jax.set_mesh`` context (e.g. plain CPU unit tests), and
    inside a ``shard_map`` body whose axes are all Manual, there is no
    Auto axis to constrain and this is a no-op, so model code can be
    written once.
    """
    mesh = compat.current_mesh()
    auto = {a: mesh.shape[a] for a in mesh.auto_axes}
    if not auto:
        return x
    spec = _resolve(logical_axes, x.shape, auto, None)
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Pytree helpers
# ---------------------------------------------------------------------------

def tree_shardings(tree_axes, tree_shapes, mesh: Mesh, rules: Rules | None = None):
    """Map a pytree of logical-axis tuples + a matching pytree of shapes
    to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda axes, shape: logical_sharding(axes, shape, mesh, rules),
        tree_axes,
        tree_shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def spec_tree(tree_axes, tree_shapes, mesh: Mesh, rules: Rules | None = None):
    return jax.tree.map(
        lambda axes, shape: resolve_spec(axes, shape, mesh, rules),
        tree_axes,
        tree_shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )
