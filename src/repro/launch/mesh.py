"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (one v5e pod-slice class).
Multi-pod: (pod=2, data=16, model=16) — 512 chips; the 'pod' axis carries
data parallelism across the inter-pod (DCN/ICI) boundary, which is where
the FP8 wire formats pay off (ParallelPlan picks 'pod' as the wire axis;
see distributed/strategy.py).

These are FUNCTIONS, not module constants: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).
Install a mesh as the ambient one with `jax.set_mesh(mesh)`.
"""
from __future__ import annotations

from typing import Tuple

import jax

DATA_PARALLEL_AXES: Tuple[str, ...] = ("pod", "data")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """A mesh whose axes are all left to the XLA partitioner (Auto)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# Axis bookkeeping (dp axes present, per-axis sizes, wire-axis choice) lives
# on distributed.strategy.ParallelPlan — build one from (mesh, policy.dist)
# instead of reading mesh.shape by hand.
