"""Production mesh builders.

(16, 16) single pod = 256 chips; (2, 16, 16) = 2 pods / 512 chips. Functions,
not module constants — importing this never touches jax device state.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic re-scaling). Uses the first
    prod(shape) devices so a 512-device dry-run backend can build both the
    single-pod (256-chip) and multi-pod (512-chip) meshes."""
    import math
    import numpy as np
    n = math.prod(shape)
    devices = np.asarray(jax.devices()[:n]).reshape(shape)
    # axes must be explicitly Auto for with_sharding_constraint
    return jax.sharding.Mesh(
        devices, tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
