"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state).  Shapes:

* single-pod: (16, 16) = 256 chips, axes (data, model) — one TPU v5e pod.
* multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the
  ``pod`` axis is data-parallel across DCN; only gradient reductions
  cross it.

The dry-run launcher sets ``--xla_force_host_platform_device_count=512``
before any jax import so these meshes build on the CPU container.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, **kw):
    """``jax.make_mesh`` with every axis auto-sharded (the compiler
    places collectives; no explicit-sharding annotations needed)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(n_devices: int = 1):
    """Tiny mesh over whatever devices exist (tests)."""
    return make_mesh((1, n_devices), ("data", "model"))
