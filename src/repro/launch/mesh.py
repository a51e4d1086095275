"""Production mesh construction (multi-pod dry-run contract).

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state.  The caller is responsible
for setting ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before*
any jax import when running on the CPU container (see launch/dryrun.py).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over host devices for examples/tests (CPU)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"))
