"""Mesh-aware ``with_sharding_constraint`` that degrades to identity.

Model code annotates activations with the mesh axes they *would* occupy
on the production mesh, e.g.::

    x = constrain(x, ("pod", "data"), None, None)     # [B, S, d]

and the same line is correct everywhere:

* single-device smoke tests — no mesh installed, ``constrain`` is a no-op;
* the 2x2x2 CPU equivalence mesh — ``pod``/``data`` exist and divide, the
  hint is applied;
* the 512-device dry-run — full constraint.

Axes named in a spec but absent from the ambient mesh are dropped (a
``("pod", "data")`` spec on a single-pod ``("data", "model")`` mesh
becomes ``("data",)``), and any dim whose size does not divide the
product of its surviving mesh axes falls back to replication — the same
two rules :mod:`repro.dist.sharding` applies to parameters, so
activation hints can never contradict GSPMD's divisibility requirement.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax


AxisSpec = Union[None, str, Sequence[str]]


def current_mesh() -> Optional[jax.sharding.AbstractMesh]:
    """The ambient ``with jax.set_mesh(mesh):`` mesh, or None off-mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _names(spec: AxisSpec) -> tuple[str, ...]:
    if spec is None:
        return ()
    if isinstance(spec, str):
        return (spec,)
    return tuple(spec)


def resolve_spec(axis_specs: Sequence[AxisSpec], shape: Sequence[int],
                 mesh) -> jax.sharding.PartitionSpec:
    """Apply the drop-absent / drop-indivisible / first-use-wins rules."""
    entries: list[AxisSpec] = []
    used: set[str] = set()
    for spec, size in zip(axis_specs, shape):
        axes = tuple(n for n in _names(spec)
                     if n in mesh.axis_names and n not in used)
        n_shards = 1
        for a in axes:
            n_shards *= mesh.shape[a]
        if not axes or n_shards == 1 or size % n_shards:
            entries.append(None)
            continue
        used.update(axes)
        entries.append(axes[0] if len(axes) == 1 else axes)
    while entries and entries[-1] is None:
        entries.pop()
    return jax.sharding.PartitionSpec(*entries)


def constrain(x: jax.Array, *axis_specs: AxisSpec) -> jax.Array:
    """Constrain ``x`` onto the ambient mesh; identity when off-mesh."""
    if len(axis_specs) != x.ndim:
        raise ValueError(f"{len(axis_specs)} axis specs for rank-{x.ndim} "
                         f"array of shape {x.shape}")
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve_spec(axis_specs, x.shape, mesh)
    if not len(spec):                       # fully replicated: nothing to say
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))
