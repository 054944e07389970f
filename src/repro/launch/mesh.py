"""Production meshes.

Single pod:  (16, 16)      over ("data", "model")      -- 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)   over ("pod", "data", "model") -- 512 chips

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import.

On the multi-pod mesh the "pod" axis is the slow (DCN-class) link: it is the
FL-device axis for LGC -- each pod is one paper "edge device", and LGC
compresses exactly the traffic that crosses it (DESIGN.md §3).
"""
from __future__ import annotations

import jax

from .compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None, model: int = 1
                   ) -> jax.sharding.Mesh:
    """Small mesh over the actually-present (host) devices, for examples
    and integration tests."""
    n = n_devices or len(jax.devices())
    if model < 1 or n % model != 0:
        raise ValueError(
            f"make_host_mesh: {n} devices do not factor into a "
            f"(data={n}/{model}, model={model}) mesh; n_devices must be a "
            f"positive multiple of model")
    return make_mesh((n // model, model), ("data", "model"))


def fl_axis_name(mesh: jax.sharding.Mesh) -> str:
    """The slow axis LGC compresses over: 'pod' when present, else 'data'."""
    return "pod" if "pod" in mesh.axis_names else "data"
