"""Device meshes. Every mesh of this repo comes from ``make_mesh``.

Functions, not module constants — importing this module never touches
jax device state (device count is locked at first jax init, and only
the dry-run entrypoint forces 512 host devices)."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh whose axes are all ``AxisType.Auto``: sharding follows the
    planner's placements and ``meshctx.hint`` constraints, and the
    compiler propagates the rest. (``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which the hints' UNCONSTRAINED dims and
    plain ``with_sharding_constraint`` are errors.)"""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """TPU v5e pod = 16x16 = 256 chips; the multi-pod mesh adds a
    leading DCN-connected "pod" axis (2 pods = 512 chips)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


# Hardware constants (TPU v5e) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
