"""Weights and inputs made on the device from the seed, in one jitted call.

A reference declares its parameter tree as ``Init`` leaves; ``make`` turns
that tree into float32 arrays.  The program and the reference are both
given these arrays, so neither takes weights the other made.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Init:
    """One parameter: its shape, how it starts (``normal``: truncated
    normal in [-2, 2] times ``std``; ``embed``: normal times ``std``;
    ``zeros``; ``ones``)."""
    shape: tuple
    kind: str = "normal"
    std: float = 1.0


def is_init(x) -> bool:
    return isinstance(x, Init)


def fan_in_std(fan_in: int) -> float:
    return 1.0 / float(np.sqrt(fan_in))


def sub_seed(seed: int, salt: int = 0) -> int:
    """A 31-bit seed derived from any whole number (the benchmark's seeds
    exceed what ``jax.random.PRNGKey`` takes)."""
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), salt])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def _one(key, init: Init):
    if init.kind == "zeros":
        return jnp.zeros(init.shape, jnp.float32)
    if init.kind == "ones":
        return jnp.ones(init.shape, jnp.float32)
    if init.kind == "embed":
        return jax.random.normal(key, init.shape, jnp.float32) * init.std
    if init.kind == "normal":
        x = jax.random.truncated_normal(key, -2.0, 2.0, init.shape,
                                        jnp.float32)
        return x * init.std
    raise ValueError(f"unknown init {init.kind!r}")


@functools.partial(jax.jit, static_argnums=1)
def _build(key, leaves: tuple):
    keys = jax.random.split(key, len(leaves))
    return [_one(k, s) for k, s in zip(keys, leaves)]


def make(specs, seed: int):
    """The float32 parameter tree for ``specs`` from ``seed``."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_init)
    return jax.tree.unflatten(
        treedef, _build(jax.random.PRNGKey(seed), tuple(leaves)))


@functools.partial(jax.jit, static_argnums=(1, 2))
def normal_inputs(key, count: int, shape: tuple):
    """``count`` distinct bfloat16 activations of ``shape``."""
    return jax.random.normal(key, (count,) + shape,
                             jnp.float32).astype(jnp.bfloat16)
