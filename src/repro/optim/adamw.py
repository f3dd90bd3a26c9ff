"""Minimal functional AdamW (optax-style triple: init / update)."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

Tree = Any


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Tree], Tree]
    update: Callable[[Tree, Tree, Tree], tuple[Tree, Tree]]


def mean_step(opt: Optimizer) -> Callable:
    """``step(grad_trees, tokens, params, state) -> (params, state)``,
    jitted: sum the gradient trees (one per peer of a stage), divide by
    the step's token count, apply ``opt`` and add the update in each
    parameter's dtype.  One program per tree shape, where the same math
    run leaf by leaf dispatched some ten device ops per parameter."""
    def step(grad_trees, tokens, params, state):
        g = grad_trees[0]
        for h in grad_trees[1:]:
            g = jax.tree.map(lambda a, b: a + b, g, h)
        g = jax.tree.map(lambda x: x / tokens, g)
        updates, state = opt.update(g, state, params)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                              params, updates)
        return params, state
    return jax.jit(step)


def _zeros_like_f32(t: Tree) -> Tree:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), t)


def adamw(lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
          eps: float = 1e-8, weight_decay: float = 0.01,
          grad_clip: float = 1.0, state_dtype=jnp.float32) -> Optimizer:
    """state_dtype=bfloat16 halves optimizer memory (beyond-paper lever;
    moments tolerate bf16 — the update math still runs in f32)."""
    def init(params: Tree) -> Tree:
        z = lambda t: jax.tree.map(
            lambda p: jnp.zeros(p.shape, state_dtype), t)
        return {"m": z(params), "v": z(params),
                "count": jnp.zeros((), jnp.int32)}

    def update(grads: Tree, state: Tree, params: Tree):
        count = state["count"] + 1
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if grad_clip > 0:
            gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                                 for g in jax.tree.leaves(grads)))
            scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        m = jax.tree.map(
            lambda m, g: (b1 * m.astype(jnp.float32)
                          + (1 - b1) * g).astype(state_dtype),
            state["m"], grads)
        v = jax.tree.map(
            lambda v, g: (b2 * v.astype(jnp.float32)
                          + (1 - b2) * g * g).astype(state_dtype),
            state["v"], grads)
        mh = jax.tree.map(
            lambda m: m.astype(jnp.float32) / (1 - b1 ** count), m)
        vh = jax.tree.map(
            lambda v: v.astype(jnp.float32) / (1 - b2 ** count), v)
        updates = jax.tree.map(
            lambda mh, vh, p: (-lr * (mh / (jnp.sqrt(vh) + eps)
                                      + weight_decay * p.astype(jnp.float32))
                               ).astype(p.dtype),
            mh, vh, params)
        return updates, {"m": m, "v": v, "count": count}

    return Optimizer(init, update)
