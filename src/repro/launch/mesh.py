"""Production mesh construction.

Importing this module never touches jax device state; meshes are built only
inside the factory functions. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax import
(see dryrun.py lines 1-2).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ('data','model').
    Multi-pod: 2x16x16 = 512 chips ('pod','data','model')."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh, e.g. ((2,4), ('data','model')) on host devices. Every
    axis is ``Auto``: jax's own default is ``Explicit``, under which the
    best-effort ``with_sharding_constraint`` calls of ``parallel.sharding``
    are rejected."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))
