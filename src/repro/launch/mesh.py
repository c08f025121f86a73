"""Production mesh definition.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries data parallelism across the DCN/ICI-superpod boundary
(and optionally FSDP for the 1T-parameter cells via fsdp_over_pod).

Defined as a function, not a module constant: importing this module must
never touch jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init).
"""

from __future__ import annotations

import jax


def _make_auto_mesh(shape, axes):
    """jax.make_mesh with every axis explicitly Auto-sharded."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU multi-device tests (device count forced by the
    test harness via subprocess)."""
    return _make_auto_mesh((data, model), ("data", "model"))
