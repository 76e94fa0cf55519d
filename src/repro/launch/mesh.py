"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run forces 512
host devices via XLA_FLAGS before first jax init, while tests/benches must
see the real single device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "dp_axes", "DATA", "MODEL", "POD"]

POD, DATA, MODEL = "pod", "data", "model"


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD, DATA, MODEL) if multi_pod else (DATA, MODEL)
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the SPMD partitioner
    propagates shardings through ops that take unsharded operands (e.g.
    the embedding gather), as the sharding rules in :mod:`repro.dist`
    assume.  ``make_mesh`` itself defaults to ``Explicit`` axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return _auto_mesh((pod, data, model), (POD, DATA, MODEL))
    return _auto_mesh((data, model), (DATA, MODEL))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (pod folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in (POD, DATA))
