"""The elastic cell's training task without the swarm: one
``NumericExecutor`` per stage, driven in a plain loop.

Per microbatch, as a trainer's hops drive the stages
(``repro.core.trainer``, synchronous path): ``run_fwd`` of each stage
before the last and ``wire_fwd`` of its output; ``run_fwd`` of the last
stage with the labels (the loss read); its ``run_bwd``, ``accumulate``
and ``wire_bwd``; then ``run_bwd`` of each earlier stage with the
cotangent in hand, ``accumulate`` and ``wire_bwd``.  Every
``microbatches_per_step`` microbatches, per stage: the runner's barrier
math (token mean, optimizer update) jitted with the old state donated,
then ``adopt_step``.

Model, weights, data, optimizer, wire and tokens per step are the elastic
cell's (``swarm_elastic``), and so is the reference.  The batches of the
checked steps are made once from the seed and the window cycles through
them.  No sim, ledger, wiring or trainer runs: the pair of cells tells the
control plane's cost from the stage programs'.
"""
from __future__ import annotations

import functools

import jax

from bench import ref_train
from bench.drivers import swarm_elastic

_data, _params, reference = (swarm_elastic._data, swarm_elastic._params,
                             swarm_elastic.reference)


def stages_trained(ctx) -> list:
    return list(ctx.c["stages_run"])


def run(ctx) -> dict:
    from repro.optim import adamw
    from repro.runtime import build_numeric_executors
    from repro.runtime.base import StageState

    t, c, hp = ctx.traffic, ctx.c, ctx.traffic["optimizer"]
    n_stages = c["n_stages"]
    if list(c["stages_run"]) != list(range(n_stages)):
        raise ValueError("the stage chain runs every stage")
    per_step, checked = t["microbatches_per_step"], ctx.check_steps
    mb_tokens = t["microbatch"] * t["seq_len"]
    step_tokens = per_step * mb_tokens
    exs = build_numeric_executors(ctx.arch, n_stages, t["seq_len"],
                                  compress=t["codec"],
                                  quant_block=t["quant_block"])
    opt = adamw(lr=hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                weight_decay=hp["weight_decay"], grad_clip=0.0)
    w = _params(ctx)
    init_opt = jax.jit(opt.init)
    states = []
    for ex, p in zip(exs, w):
        ctx.check_layout(p, ex.prog.specs)
        st = StageState()
        ex.restore(st, {"params": p, "opt": init_opt(p), "version": 0})
        states.append(st)
    del w
    data = _data(ctx)
    batches = [data.batch(i) for i in range(per_step * checked)]
    last = n_stages - 1

    # the runner's barrier math (SwarmRunner._ar_plan) for one stage,
    # with the old params and optimizer state donated
    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def barrier(grads, opt_state, params):
        g = jax.tree.map(lambda x: x / step_tokens, grads)
        upd, new_opt = opt.update(g, opt_state, params)
        return (jax.tree.map(lambda p, u: p + u.astype(p.dtype), params,
                             upd), new_opt)

    def call(s, name, *a, **kw):
        with ctx.span(f"bench.stage{s}.{name}"):
            return getattr(exs[s], name)(*a, **kw)

    def microbatch(j: int):
        b = batches[j % len(batches)]
        x, inps = b["tokens"], []
        for s in range(last):
            inps.append(x)
            x = call(s, "wire_fwd", call(s, "run_fwd", states[s], x))
        float(call(last, "run_fwd", states[last], x, b["labels"]))
        loss, gx, gp = call(last, "run_bwd", states[last], x,
                            labels=b["labels"])
        call(last, "accumulate", states[last], gp, float(loss), mb_tokens)
        dy = call(last, "wire_bwd", gx)
        for s in range(last - 1, -1, -1):
            _, gx, gp = call(s, "run_bwd", states[s], inps[s], dy=dy)
            call(s, "accumulate", states[s], gp, None, mb_tokens)
            dy = call(s, "wire_bwd", gx)

    def step():
        for s in range(n_stages):
            with ctx.span(f"bench.stage{s}.barrier"):
                new = barrier(exs[s].export_grads(states[s]),
                              states[s].opt, states[s].params)
            call(s, "adopt_step", states[s], *new)

    def sync():
        jax.block_until_ready([(st.params, st.grad_acc) for st in states])

    # the checked steps: set-up, through the window's own calls and feed
    prog = {"loss": [], "grad": {}, "change": {}, "wire": None}
    j = 0
    for k in range(checked):
        for _ in range(per_step):
            microbatch(j)
            j += 1
        prog["loss"].append(states[last].loss_sum
                            / states[last].token_count)
        step()
        if k == 0:
            for s in range(n_stages):
                prog["grad"].update(ref_train.leaf_norms(jax.tree.map(
                    lambda m: m / (1 - hp["b1"]), states[s].opt["m"]),
                    f"s{s}"))
    start = _params(ctx)
    for s in range(n_stages):
        prog["change"].update(ref_train.change_norms(
            states[s].params, start[s], f"s{s}"))
    del start
    sync()

    # the window: whole optimizer steps until ``seconds`` have passed
    ctx.open_window()
    steps = mbs = 0
    while True:
        for _ in range(per_step):
            microbatch(j)
            j += 1
            mbs += 1
        step()
        steps += 1
        if ctx.window_done(steps):
            break
    sync()
    ctx.close_window(mbs)
    peak = ctx.memory_peak()
    del states, exs, batches
    return {"tokens": mbs * mb_tokens, "attempted": mbs, "failed": 0,
            "program": prog, "memory_peak_bytes": peak}
