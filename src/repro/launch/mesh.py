"""Production mesh builders.

A FUNCTION (not module-level constant) so importing this module never touches
jax device state — the dry-run sets XLA_FLAGS for 512 host devices before any
jax initialization; tests and benches see the real single device.

Every mesh is built with Auto axes: ``jax.make_mesh`` defaults to Explicit
axes, under which the models' ``PartitionSpec.UNCONSTRAINED`` gathers and
sharding-ambiguous ops are refused at trace time.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16×16 = 256 chips per pod; 2 pods = 512.

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh (tests / elastic re-mesh after a pod loss)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def describe(mesh) -> dict:
    return {
        "axes": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "n_devices": int(mesh.devices.size),
    }
