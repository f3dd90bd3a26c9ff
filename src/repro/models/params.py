"""Parameter specification framework.

A model is described as a tree of :class:`ParamSpec`s.  One definition yields

* ``init(key, specs)``      -> real arrays (smoke tests / real training)
* ``abstract(specs)``       -> ``jax.ShapeDtypeStruct`` tree (dry-run: no allocation)
* ``logical_axes(specs)``   -> tree of logical-axis-name tuples, mapped to mesh
                               axes by :mod:`repro.dist.sharding`.

Logical axis vocabulary (see DESIGN.md §4):
  layers, stage, embed, mlp, heads, kv_heads, head_dim, qk_dim, v_dim,
  vocab, experts, expert_mlp, state, conv, pos, null
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Tree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Shape + dtype + init + logical axes for one parameter tensor."""

    shape: tuple[int, ...]
    dtype: Any = jnp.float32
    init: str = "normal"          # normal | zeros | ones | embed | scaled
    axes: tuple[str, ...] = ()    # logical axis names, len == len(shape)
    scale: float = 1.0            # stddev multiplier for normal/scaled init

    def __post_init__(self):
        if self.axes and len(self.axes) != len(self.shape):
            raise ValueError(
                f"axes {self.axes} do not match shape {self.shape}")


_HEAD_AXES = ("heads", "kv_heads")


def _fan_in(shape: Sequence[int], axes: Sequence[str] = ()) -> int:
    """Width a weight's input contracts over.  Plain (and layer-stacked)
    matrices ``[..., in, out]`` contract ``shape[-2]``.  Multi-head
    projections carry a heads axis: ``[..., d, H, hd]`` contracts ``d``,
    and ``[..., H, hd, d]`` back to the model width contracts ``H*hd``
    (per-head recurrences ``[H, hd, out]`` stay at ``hd``)."""
    if len(shape) < 2:
        return max(1, shape[-1])
    heads = [i for i, a in enumerate(axes) if a in _HEAD_AXES]
    if heads and len(shape) >= 3:
        i = heads[0]
        if i == len(shape) - 2 and i > 0:
            return shape[i - 1]
        if i == len(shape) - 3 and axes[-1] == "embed":
            return shape[i] * shape[i + 1]
    return shape[-2]


def _init_one(key: jax.Array, spec: ParamSpec) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "embed":
        std = 1.0 * spec.scale
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)
    # normal / scaled: truncated-normal, std = scale / sqrt(fan_in)
    std = spec.scale / math.sqrt(_fan_in(spec.shape, spec.axes))
    x = jax.random.truncated_normal(key, -2.0, 2.0, spec.shape, jnp.float32)
    return (x * std).astype(spec.dtype)


def is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def init(key: jax.Array, specs: Tree) -> Tree:
    """Materialize a ParamSpec tree into real arrays."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_one(k, s) for k, s in zip(keys, leaves)])


def abstract(specs: Tree) -> Tree:
    """ShapeDtypeStruct tree — used by the dry-run, never allocates."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs, is_leaf=is_spec)


def logical_axes(specs: Tree) -> Tree:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def n_params(specs: Tree) -> int:
    return sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(specs, is_leaf=is_spec))


def bytes_of(specs: Tree) -> int:
    return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
               for s in jax.tree.leaves(specs, is_leaf=is_spec))


def cast_tree(tree: Tree, dtype) -> Tree:
    def c(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree.map(c, tree)
