"""Production meshes (TPU v5e): 16x16 single pod, 2x16x16 multi-pod.

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; callers (dryrun.py) set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import.  Mesh construction goes through :func:`repro.dist.compat.make_mesh`
(Auto axis types).
"""

from __future__ import annotations

from repro.dist.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Small mesh for CPU tests (device count permitting)."""
    return make_mesh((data, model), ("data", "model"))


def chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
