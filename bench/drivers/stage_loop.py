"""One peer of the last pipeline stage, driven as a trainer's last-stage
hop drives it (``repro.core.trainer``), with the runner's barrier step.

Per microbatch: ``run_fwd`` with labels (the trainer reads the loss),
``run_bwd``, ``accumulate`` (the loss read again), ``wire_bwd`` of the
input cotangent.  Every ``microbatches_per_step`` microbatches:
``export_grads``, the runner's barrier math (token mean, optimizer
update) jitted with the old state donated, ``adopt_step``.  The int8 wire
inputs are made once from the seed, one per microbatch of the checked
steps, and the window cycles through them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import ref_train, weights
from bench.data import SyntheticLM

INPUT_SALT, LABEL_SALT, WEIGHT_SALT = 2, 3, 10


def _stage(c: dict) -> int:
    stages = list(c["stages_run"])
    if len(stages) != 1 or stages[0] != c["n_stages"] - 1 or stages[0] == 0:
        raise ValueError("the stage loop runs one last stage after another")
    return stages[0]


def _inputs(ctx, n: int):
    t, c = ctx.traffic, ctx.c
    key = jax.random.PRNGKey(weights.sub_seed(ctx.seed, INPUT_SALT))
    return weights.normal_inputs(key, n, (t["microbatch"], t["seq_len"],
                                          c["d_model"]))


def _labels(ctx, n: int) -> list:
    t, c = ctx.traffic, ctx.c
    data = SyntheticLM(c["vocab_size"], t["seq_len"], t["microbatch"],
                       seed=weights.sub_seed(ctx.seed, LABEL_SALT))
    return [data.batch(i)["labels"] for i in range(n)]


def _params(ctx, s: int):
    return weights.make(ctx.model.param_specs(ctx.c, ctx.c["n_stages"], s),
                        weights.sub_seed(ctx.seed, WEIGHT_SALT + s))


def stages_trained(ctx) -> list:
    return [_stage(ctx.c)]


def run(ctx) -> dict:
    from repro.optim import adamw
    from repro.runtime import build_numeric_executors
    from repro.runtime.base import StageState

    t, c, hp = ctx.traffic, ctx.c, ctx.traffic["optimizer"]
    s, n_stages = _stage(c), c["n_stages"]
    per_step, checked = t["microbatches_per_step"], ctx.check_steps
    mb_tokens = t["microbatch"] * t["seq_len"]
    step_tokens = per_step * mb_tokens
    exs = build_numeric_executors(ctx.arch, n_stages, t["seq_len"],
                                  compress=t["codec"])
    ex = exs[s]
    opt = adamw(lr=hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                weight_decay=hp["weight_decay"], grad_clip=0.0)
    w = _params(ctx, s)
    ctx.check_layout(w, ex.prog.specs)
    state = StageState()
    ex.restore(state, {"params": w, "opt": jax.jit(opt.init)(w),
                       "version": 0})
    del w
    n_in = per_step * checked
    hs = _inputs(ctx, n_in)
    inputs = [exs[s - 1].wire_fwd(hs[i]) for i in range(n_in)]
    del hs
    labels = _labels(ctx, n_in)

    # the runner's barrier math (SwarmRunner._ar_plan) for one peer, with
    # the old params and optimizer state donated: two copies of a
    # full-width stage's state do not fit one chip
    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def barrier(grads, opt_state, params):
        g = jax.tree.map(lambda x: x / step_tokens, grads)
        upd, new_opt = opt.update(g, opt_state, params)
        return (jax.tree.map(lambda p, u: p + u.astype(p.dtype), params,
                             upd), new_opt)

    def microbatch(j: int):
        inp, lab = inputs[j % n_in], labels[j % n_in]
        with ctx.span("bench.run_fwd"):
            float(ex.run_fwd(state, inp, lab))
        with ctx.span("bench.run_bwd"):
            loss, gx, gp = ex.run_bwd(state, inp, labels=lab)
        with ctx.span("bench.accumulate"):
            ex.accumulate(state, gp, float(loss), mb_tokens)
        del gp
        with ctx.span("bench.wire_bwd"):
            return ex.wire_bwd(gx)

    def step():
        with ctx.span("bench.barrier"):
            new_params, new_opt = barrier(ex.export_grads(state), state.opt,
                                          state.params)
        with ctx.span("bench.adopt_step"):
            ex.adopt_step(state, new_params, new_opt)

    # the checked steps: set-up, through the window's own calls and feed
    prog = {"loss": [], "grad": {}, "change": {}, "wire": None}
    j = 0
    for k in range(checked):
        for _ in range(per_step):
            out = microbatch(j)
            if j == 0:
                prog["wire"] = float(jnp.sqrt(jnp.sum(jnp.square(
                    out.astype(jnp.float32)))))
            j += 1
        prog["loss"].append(state.loss_sum / state.token_count)
        step()
        if k == 0:
            prog["grad"] = ref_train.leaf_norms(jax.tree.map(
                lambda m: m / (1 - hp["b1"]), state.opt["m"]), f"s{s}")
    prog["change"] = ref_train.change_norms(state.params, _params(ctx, s),
                                            f"s{s}")
    jax.block_until_ready((state.params, out))

    # the window: whole optimizer steps until ``seconds`` have passed
    ctx.open_window()
    steps = mbs = 0
    while True:
        for _ in range(per_step):
            out = microbatch(j)
            j += 1
            mbs += 1
        step()
        steps += 1
        if ctx.window_done(steps):
            break
    jax.block_until_ready((state.params, out))
    ctx.close_window(mbs)
    peak = ctx.memory_peak()
    del state, ex, exs, inputs, labels, out
    return {"tokens": mbs * mb_tokens, "attempted": mbs, "failed": 0,
            "program": prog, "memory_peak_bytes": peak}


def reference(ctx, num, fault: str | None = None) -> dict:
    t, c = ctx.traffic, ctx.c
    s = _stage(c)
    per_step, checked = t["microbatches_per_step"], ctx.check_steps
    n_in = per_step * checked
    hs = _inputs(ctx, n_in)
    labels = _labels(ctx, n_in)
    qdq = jax.jit(lambda h: ref_qdq(h, t["quant_block"]))

    def feed(i):
        return qdq(hs[i]), labels[i]

    return ref_train.readings(
        ctx.model, c, num, lambda: [_params(ctx, s)], feed, checked,
        per_step, t["microbatch"] * t["seq_len"], t["optimizer"],
        wire_block=t["quant_block"], wire_reading=True,
        fault=fault)


def ref_qdq(h, block):
    from bench.plain import int8_qdq
    return int8_qdq(h.astype(jnp.float32), block)
