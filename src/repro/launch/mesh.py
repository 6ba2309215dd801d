"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (v5e-256,
("data", "model")).  Multi-pod: 2 pods x 256 = 512 chips with the leading
"pod" axis mapped onto the inter-pod (DCN) dimension — only pure-DP
collectives (gradient all-reduce) should cross it.

Every mesh here has Auto axes: the sharding rules (``parallel/sharding.py``)
hand GSPMD parameter/batch layouts and ``with_sharding_constraint`` hints,
which is the Auto-axis contract (``jax.make_mesh`` defaults to Explicit
axes since JAX 0.7).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """("data", "model") mesh over every device present, all of them on
    the data axis.  One device -> (1, 1)."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))
