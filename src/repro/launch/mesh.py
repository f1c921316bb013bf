"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16x16 = 256 chips (data, model).
Multi-pod: 2 x 16 x 16 = 512 chips (pod, data, model) — the ``pod`` axis is
the DCN/inter-pod dimension; data parallelism spans (pod, data).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic recovery). Axes are ``Auto``: the
    steps place arrays with ``with_sharding_constraint`` and let XLA
    partition, which ``jax.make_mesh``'s default ``Explicit`` axes refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # bytes/s
ICI_BW_PER_LINK = 50e9         # bytes/s/link
