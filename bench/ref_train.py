"""The plain training reference a cell's first optimizer steps are held to.

The cell's stages run one after another on each microbatch; every
boundary crossing is int8 round-tripped both ways (activations forward,
cotangents back); gradients are summed over the step's microbatches and
divided by its tokens; AdamW steps each stage.  The model arithmetic
comes from the configuration's plain reference beside its file, the
weights and inputs from the seed (``bench.weights``): nothing here
imports the program or takes anything it made.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from bench import plain


def leaf_norms(tree, prefix: str = "") -> dict:
    """{path: float64 L2 norm} of every leaf."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.device_get(jax.jit(lambda xs: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs])(
        [x for _, x in leaves]))
    return {prefix + jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(leaves, norms)}


def change_norms(new, old, prefix: str = "") -> dict:
    """{path: norm of new - old} leaf by leaf."""
    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))(new, old)
    leaves = jax.tree_util.tree_flatten_with_path(diff)[0]
    return {prefix + jax.tree_util.keystr(p): float(n)
            for p, n in jax.device_get(leaves)}


FAULTS = ("half_batch", "grad_doubled")


@functools.lru_cache(maxsize=None)
def _programs(model, c_json: str, mode: str, block: int) -> dict:
    """The reference's jitted pieces, built once per (model, config,
    numerics, wire block) in a process."""
    c = json.loads(c_json)
    num = plain.Numerics(mode)
    n_stages, stages = c["n_stages"], list(c["stages_run"])
    last = stages[-1]
    takes_x = last != 0
    loss_fn = functools.partial(model.stage_loss, num, c, n_stages, last)

    def _bwd(s, p, x, dy):
        f = functools.partial(model.stage_forward, num, c, n_stages, s)
        if s == 0:
            _, pull = jax.vjp(lambda q: f(q, x), p)
            return pull(dy)[0], None
        _, pull = jax.vjp(f, p, x)
        return pull(dy)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adam(p, m, v, g, count, n_tok, hp):
        g = jax.tree.map(lambda x: x / n_tok, g)
        out = jax.tree.map(
            lambda gg, mm, vv, pp: plain.adamw_update(gg, mm, vv, count, pp,
                                                      hp), g, m, v, p)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2)

    return {
        "takes_x": takes_x,
        "qdq": jax.jit(functools.partial(plain.int8_qdq, block=block)),
        "fwd": {s: jax.jit(functools.partial(model.stage_forward, num, c,
                                             n_stages, s))
                for s in stages[:-1]},
        "last": jax.jit(jax.value_and_grad(
            loss_fn, argnums=(0, 1) if takes_x else 0)),
        "bwd": {s: jax.jit(functools.partial(_bwd, s)) for s in stages[:-1]},
        "add": jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                       donate_argnums=0),
        "zeros": jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t)),
        "adam": adam,
    }


def _double_largest(tree):
    """The fault "an answer altered where it is produced": the largest
    leaf of a stage's gradient doubled."""
    leaves, treedef = jax.tree.flatten(tree)
    big = max(range(len(leaves)), key=lambda i: leaves[i].size)
    leaves[big] = leaves[big] * 2
    return jax.tree.unflatten(treedef, leaves)


def readings(model, c: dict, num: plain.Numerics, make_params, feed,
             steps: int, per_step: int, tokens_per_mb: int, hp: dict,
             wire_block: int = 64, wire_reading: bool = False,
             fault: str | None = None) -> dict:
    """Losses of ``steps`` steps, the first step's per-leaf gradient
    norms, the per-leaf norms of the parameters' change over all of
    them, and (``wire_reading``) the norm of the first microbatch's
    cotangent as it crosses back over the wire.

    ``make_params()`` returns the stages' initial trees (one per stage
    the cell runs) and ``feed(i)`` microbatch ``i``'s (input, labels).
    ``fault`` plants one of ``FAULTS`` in the reference put in the
    program's place: ``half_batch`` counts only the first half of each
    step's microbatches (the mean taken over the rest),
    ``grad_doubled`` doubles the largest leaf of the last stage's
    gradient of every microbatch."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    stages = list(c["stages_run"])
    pr = _programs(model, json.dumps(c, sort_keys=True), num.mode,
                   wire_block)
    qdq, add, zeros = pr["qdq"], pr["add"], pr["zeros"]
    hp = {k: float(v) for k, v in hp.items()}
    params = make_params()
    m = [zeros(p) for p in params]
    v = [zeros(p) for p in params]
    out = {"loss": [], "grad": {}, "change": {}, "wire": None}
    used = per_step // 2 if fault == "half_batch" else per_step
    for step in range(steps):
        acc = [zeros(p) for p in params]
        loss_sum = 0.0
        for i in range(used):
            x, labels = feed(step * per_step + i)
            xs = [x]
            for j, s in enumerate(stages[:-1]):
                xs.append(qdq(pr["fwd"][s](params[j], xs[-1])))
            loss, grads = pr["last"](params[-1], xs[-1], labels)
            gp, gx = grads if pr["takes_x"] else (grads, None)
            if fault == "grad_doubled":
                gp = _double_largest(gp)
            acc[-1] = add(acc[-1], gp)
            dy = qdq(gx) if gx is not None else None
            if wire_reading and step == 0 and i == 0 and dy is not None:
                out["wire"] = float(jnp.sqrt(jnp.sum(jnp.square(dy))))
            for j in range(len(stages) - 2, -1, -1):
                gp, gx = pr["bwd"][stages[j]](params[j], xs[j], dy)
                acc[j] = add(acc[j], gp)
                dy = qdq(gx) if gx is not None else None
            loss_sum += float(loss)
        n_tok = used * tokens_per_mb
        out["loss"].append(loss_sum / n_tok)
        if step == 0:
            for j, s in enumerate(stages):
                out["grad"].update(leaf_norms(
                    jax.tree.map(lambda a: a / n_tok, acc[j]), f"s{s}"))
        for j in range(len(stages)):
            params[j], m[j], v[j] = pr["adam"](
                params[j], m[j], v[j], acc[j], jnp.float32(step + 1),
                jnp.float32(n_tok), hp)
        del acc
    del m, v
    start = make_params()
    for j, s in enumerate(stages):
        out["change"].update(change_norms(params[j], start[j], f"s{s}"))
    return out
