"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module never touches jax device state.  The single-pod
mesh is 16x16 = 256 chips (data x model); the multi-pod mesh adds a
leading 2-pod axis (512 chips) used as an outer data-parallel dimension
(params replicate across pods; batch shards over pod x data).

Every mesh axis is ``AxisType.Auto``: the model places arrays through
sharding constraints and leaves the rest to the partitioner, which is
what jax's default (``Explicit``) axes refuse at the embedding gather.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many local devices exist (tests/examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return jax.make_mesh((data, model), ("data", "model"),
                         (AxisType.Auto, AxisType.Auto))
