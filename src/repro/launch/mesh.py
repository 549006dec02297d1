"""Production mesh construction.

Target: TPU v5e pods — 256 chips (16x16 ICI torus) per pod; the multi-pod
configuration is 2 pods = 512 chips with the ``pod`` axis crossing DCN.
Importing this module never touches jax device state; meshes are built
lazily inside the functions (dryrun.py sets XLA_FLAGS before any jax call).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the sharding rules place constraints with
    # with_sharding_constraint, which Explicit axes would turn into asserts
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (smoke tests)."""
    return _auto_mesh((1, 1), ("data", "model"))


HW = {
    # TPU v5e per-chip constants for the roofline terms
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link
    "hbm_bytes": 16 * 1024**3,   # capacity
}
