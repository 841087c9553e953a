"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; smoke tests and benches see the 1 real CPU device.

Topology: TPU v5e, 16x16 = 256 chips per pod.  ``model`` is the fast
(intra-pod ICI) axis used for tensor/expert parallelism; ``data`` carries
FSDP + data parallelism; the leading ``pod`` axis extends data parallelism
across pods (lowest inter-pod traffic: one gradient all-reduce per step).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(shape)
    )


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Axes the global batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def num_chips(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
