"""Production mesh builders. Functions, not module constants — importing
this module must never touch jax device state (the dry-run sets
XLA_FLAGS before any jax initialization)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips (data, model).
    Multi-pod: 2×16×16 = 512 chips (pod, data, model) — the `pod` axis is
    the FedLuck aggregation axis (DESIGN.md §2)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices exist (tests / CPU runs)."""
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def device_info() -> dict:
    """The devices JAX runs on, as every benchmark result names them."""
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def batch_axes_for(mesh) -> tuple[str, ...]:
    """Batch shards over pod+data when the pod axis exists."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
