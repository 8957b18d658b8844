"""Mesh-aware optional sharding constraints.

Model code calls ``maybe_constrain(x, "model", "data", None)`` — under an
abstract mesh (``jax.sharding.use_mesh`` during lowering) the constraint is
applied with axis names filtered to those the mesh actually has; with no
mesh (CPU smoke tests) it is a no-op.  This keeps model code mesh-agnostic
while letting the dry-run pin the shardings that matter (e.g. the MoE
dispatch buffer on (experts='model', capacity='data')).
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _clean_axis(ax, names):
    if ax is None:
        return None
    if isinstance(ax, (tuple, list)):
        kept = tuple(a for a in ax if a in names)
        return kept if kept else None
    return ax if ax in names else None


def maybe_constrain(x, *spec_axes):
    names = jax.sharding.get_abstract_mesh().axis_names
    if not names:
        return x
    cleaned = P(*(_clean_axis(a, names) for a in spec_axes))
    return jax.lax.with_sharding_constraint(x, cleaned)
