"""Production mesh definitions.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The dry-run sets XLA_FLAGS before any jax import
to fake 512 host devices; smoke tests and benchmarks see 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods for the multi-pod dry-run.

    Axes are ``Auto``: the models place work through sharding annotations
    and ``shard_map``, and leave propagation to the compiler."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
