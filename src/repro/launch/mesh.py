"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax init,
and tests/benches must keep seeing 1 device.

Topology: TPU v5e pods of 256 chips. Single pod: (data=16, model=16).
Multi-pod: a leading "pod" axis; batch shards over ("pod", "data") so the
only cross-pod (DCN) collective is the gradient all-reduce.

Axis names are owned by :class:`repro.parallel.sharding.ServingMesh` — every
mesh built here round-trips through it, so launch, dry-run, and the serving
engines agree on one naming authority.
"""
from __future__ import annotations

import jax

from repro.parallel.sharding import ServingMesh

__all__ = ["make_production_mesh", "make_host_mesh", "make_serving_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ServingMesh.AXES
    # ServingMesh validates the axis names (it allows the leading "pod")
    # Auto axes: the model code places arrays through sharding constraints
    # (parallel.sharding.constrain), which Explicit axes would reject
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return ServingMesh(jax.make_mesh(shape, axes, axis_types=auto)).mesh


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = data or (n // model)
    return ServingMesh.create(data=data, model=model).mesh


def make_serving_mesh(spec: str) -> ServingMesh:
    """``"model=N,data=M"`` → a ServingMesh over the first N*M local devices."""
    return ServingMesh.from_spec(spec)
