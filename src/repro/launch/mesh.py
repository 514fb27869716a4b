"""Production mesh construction.

Importing this module never touches jax device state; both helpers are
functions. The production topology is a v5e-class pod of 16x16 = 256 chips;
multi-pod doubles it with a leading 'pod' (= UnifyFL silo) axis.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False,
                         shape: Optional[Tuple[int, ...]] = None):
    """(16,16) 'data','model' single pod; (2,16,16) 'pod','data','model'
    multi-pod. ``shape`` overrides sizes for reduced dev runs (axis names
    keep the same layout semantics)."""
    if multi_pod:
        shape = shape or (2, 16, 16)
        axes = ("pod", "data", "model")
    else:
        shape = shape or (16, 16)
        axes = ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))
