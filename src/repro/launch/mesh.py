"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis carries
data parallelism across the inter-pod (DCN/ICI-sparse) links, which is why
cross-pod traffic is restricted to gradient all-reduce (compressible, see
``distributed.collectives``).

Defined as functions — importing this module never touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the compiler propagates shardings
    (``jax.make_mesh`` defaults to Explicit axes, which refuse ops such as a
    contraction over a sharded dimension)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh(devices=None):
    """1-device mesh with the production axis names (CPU tests)."""
    return _auto_mesh((1, 1), ("data", "model"), devices=devices)
