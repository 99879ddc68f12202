"""Production mesh construction (a FUNCTION — importing this touches no jax
device state; jax devices are only queried when the function is called)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods in multi-pod mode (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, found {len(devices)} — the dry-run must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import"
        )
    from repro.distributed.sharding import make_mesh

    return make_mesh(shape, axes, devices=devices[:n])
