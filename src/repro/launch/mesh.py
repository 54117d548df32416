"""Production mesh construction.

Single pod: 16 × 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 × 16 × 16 = 512 chips, axes ("pod", "data", "model").

Defined as functions (never module-level constants) so importing this module
does not touch jax device state.  The dry-run launches with 512 placeholder
host devices (see launch/dryrun.py); the single-pod mesh uses the first 256.
"""

from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    from jax.sharding import Mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax — launch/dryrun.py does this)"
        )
    grid = np.array(devices[:n]).reshape(shape)
    return Mesh(grid, axes)
