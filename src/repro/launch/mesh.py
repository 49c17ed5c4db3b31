"""Mesh construction.

FUNCTIONS (not module-level constants) so importing never touches jax
device state.  Single pod: 16x16 = 256 chips (data x model).  Multi-pod:
2 x 16 x 16 = 512 chips with the leading "pod" axis as the cross-pod
(DCN) data-parallel axis.

Every axis is ``AxisType.Auto``: the model's ``with_sharding_constraint``
hints and the GSPMD partitioner need Auto axes, and ``jax.make_mesh``
defaults to Explicit ones.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Mesh of ``shape`` over ``axes`` (e.g. (2, 2) on 4 devices), all
    axes Auto; ``devices`` defaults to ``jax.devices()``."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
