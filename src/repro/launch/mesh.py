"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before the
first jax initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (v5e); 2 pods = 512 chips multi-pod.

    Axes: ``pod`` carries SWARM pipeline stages (training) or folds into
    data parallelism (inference cells); ``data`` is FSDP/batch; ``model``
    is TP/EP.  See DESIGN.md §4.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires >= prod(shape) host devices)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_peer_mesh(n_devices: int = 0, axes=("data",)):
    """Peer-local mesh for a mesh-backed SWARM peer
    (:class:`repro.runtime.mesh.MeshExecutor`): the first ``n_devices``
    local devices (0 => all) on a 1-D ``data`` axis — the peer runs its
    stage data-parallel across them.  Works down to a single device, so
    mixed numeric/mesh swarms run anywhere (CPU tests included)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    shape = (n,) + (1,) * (len(axes) - 1)
    return jax.make_mesh(
        shape, axes, devices=devs[:n],
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
