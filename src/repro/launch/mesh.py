"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis rides
inter-pod links and is used either for cross-pod data parallelism
(gradient all-reduce, compressed) or as the stream-future pipeline axis
(see repro.core.pipeline).

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state; callers own the
``XLA_FLAGS=--xla_force_host_platform_device_count=...`` dance (dryrun.py
sets it before any jax import, per the runbook).
"""
from __future__ import annotations

import jax



def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_host_mesh(axis_name: str = "pod") -> jax.sharding.Mesh:
    """All local devices on one axis (CPU tests / examples)."""
    return jax.make_mesh(
        (jax.device_count(),), (axis_name,),
        axis_types=(jax.sharding.AxisType.Auto,),
    )
