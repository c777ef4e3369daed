"""Production mesh construction.

Functions, not module-level constants: importing this module never
touches jax device state.  The dry-run process sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so ``jax.make_mesh`` can build these meshes on CPU.

Hardware target: TPU v5e pods — 16x16 = 256 chips per pod; the
multi-pod mesh adds a leading "pod" axis (2 pods = 512 chips) that
composes with "data" for batch/FSDP sharding (DCN between pods, ICI
within).

Mesh axes are ``Auto``: shardings are placed on arrays and propagated
by the compiler, which the model's logical-axis constraints rely on
(``jax.make_mesh`` would otherwise make them ``Explicit``, and a jitted
step over arrays placed on such a mesh then rejects gathers such as the
embedding lookup).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, pods: int = 0):
    """Small mesh for CI-scale sharding tests (needs
    xla_force_host_platform_device_count >= n_data * n_model * pods)."""
    if pods:
        return _mesh((pods, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))


def mesh_info(mesh) -> dict:
    return {
        "axis_names": list(mesh.axis_names),
        "shape": {k: int(v) for k, v in mesh.shape.items()},
        "n_devices": int(mesh.size),
    }
