"""The elastic swarm end to end: ``SwarmRunner(numeric=True)``.

The runner is built as the traffic file says (stages x peers, trainers,
codec, synchronous barrier, no rebalancing, no churn), its peers are given
the seed's weights through the executors' ``restore``, and one
``runner.run`` carries the checked steps and then the window.  The
benchmark watches the run through two public hooks of its own: the
trainers' ``microbatch_done`` (work completed) and the executors'
``adopt_step`` (the last install of a barrier ends an optimizer step).
The window opens at the end of the last checked step and closes at the
end of the first step that finishes ``seconds`` after it opened.
"""
from __future__ import annotations

import jax

from bench import ref_train, weights
from bench.data import SyntheticLM

DATA_SALT, RUNNER_SALT, WEIGHT_SALT = 1, 4, 10


def _data(ctx) -> SyntheticLM:
    t, c = ctx.traffic, ctx.c
    return SyntheticLM(c["vocab_size"], t["seq_len"], t["microbatch"],
                       seed=weights.sub_seed(ctx.seed, DATA_SALT))


def _params(ctx) -> list:
    c = ctx.c
    return [weights.make(ctx.model.param_specs(c, c["n_stages"], s),
                         weights.sub_seed(ctx.seed, WEIGHT_SALT + s))
            for s in c["stages_run"]]


def stages_trained(ctx) -> list:
    return list(ctx.c["stages_run"])


def run(ctx) -> dict:
    from repro.core import SwarmConfig, SwarmRunner
    from repro.optim import adamw

    t, c, hp = ctx.traffic, ctx.c, ctx.traffic["optimizer"]
    n_stages = c["n_stages"]
    if list(c["stages_run"]) != list(range(n_stages)):
        raise ValueError("the elastic swarm runs every stage")
    per_step = t["microbatches_per_step"]
    scfg = SwarmConfig(
        n_stages=n_stages, microbatch_size=t["microbatch"],
        seq_len=t["seq_len"], global_batch=per_step * t["microbatch"],
        n_trainers=t["trainers"], rebalance_period=0.0, codec=t["codec"],
        quant_block=t["quant_block"])
    opt = adamw(lr=hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                weight_decay=hp["weight_decay"], grad_clip=0.0)
    data = _data(ctx)
    runner = SwarmRunner(ctx.arch, scfg, opt, numeric=True,
                         seed=weights.sub_seed(ctx.seed, RUNNER_SALT),
                         data_fn=data.batch)
    runner.build(peers_per_stage=t["peers_per_stage"])
    w = _params(ctx)
    init_opt = jax.jit(opt.init)
    for peer in runner.peers.values():
        s = peer.stages.start
        ctx.check_layout(w[s], peer.state.params)
        peer.executor.restore(peer.state, {"params": w[s],
                                           "opt": init_opt(w[s]),
                                           "version": 0})
    del w
    n_peers = len(runner.peers)
    mb_tokens = t["microbatch"] * t["seq_len"]
    checked = ctx.check_steps
    prog = {"loss": [], "grad": {}, "change": {}, "wire": None}
    st = {"installs": 0, "steps": 0, "open": False, "ok": 0, "failed": 0,
          "wire0": 0.0, "wire": 0.0, "done": False}

    def peer_of(s):
        return next(p for p in runner.peers.values() if p.stages.start == s)

    def sync():
        jax.block_until_ready([(p.state.params, p.state.grad_acc)
                               for p in runner.peers.values()])

    def on_step():
        st["steps"] += 1
        k = st["steps"]
        if k == 1:
            for s in range(n_stages):
                prog["grad"].update(ref_train.leaf_norms(jax.tree.map(
                    lambda m: m / (1 - hp["b1"]), peer_of(s).state.opt["m"]),
                    f"s{s}"))
        if k == checked:
            prog["loss"] = [float(x) for x in runner.metrics["loss"][:checked]]
            start = _params(ctx)
            for s in range(n_stages):
                prog["change"].update(ref_train.change_norms(
                    peer_of(s).state.params, start[s], f"s{s}"))
            del start
            sync()
            st["wire0"] = runner.metrics["wire_bytes"]
            ctx.open_window()
            st["open"] = True
            return
        if st["open"] and ctx.window_done(k - checked):
            sync()
            ctx.close_window(st["ok"] + st["failed"])
            st["open"], st["done"] = False, True
            st["wire"] = runner.metrics["wire_bytes"] - st["wire0"]
            runner.stopped = True

    def hook_adopt(ex):
        adopt = ex.adopt_step

        def adopt_step(state, new_params, new_opt, stage=None):
            with ctx.span(f"bench.stage{ex.stage}.adopt_step"):
                adopt(state, new_params, new_opt, stage=stage)
            st["installs"] += 1
            if st["installs"] % n_peers == 0:
                on_step()
        ex.adopt_step = adopt_step

    def hook_span(ex, name):
        fn = getattr(ex, name)

        def call(*a, **kw):
            with ctx.span(f"bench.stage{ex.stage}.{name}"):
                return fn(*a, **kw)
        setattr(ex, name, call)

    for ex in runner.executors:
        hook_adopt(ex)
        for name in ("run_fwd", "run_bwd", "wire_fwd", "wire_bwd",
                     "accumulate", "export_grads"):
            hook_span(ex, name)
    done = runner.microbatch_done

    def microbatch_done(mb, ok):
        done(mb, ok)
        if st["open"]:
            st["ok" if ok else "failed"] += 1
    runner.microbatch_done = microbatch_done

    runner.run(until=t["virtual_horizon_s"])
    if not st["done"]:
        raise RuntimeError(
            f"the swarm stopped after {st['steps']} steps, before the "
            f"window closed (virtual clock {runner.sim.now})")
    ctx.counters["wire_bytes"] = st["wire"]
    peak = ctx.memory_peak()
    del runner
    return {"tokens": st["ok"] * mb_tokens,
            "attempted": st["ok"] + st["failed"], "failed": st["failed"],
            "program": prog, "memory_peak_bytes": peak}


def reference(ctx, num, fault: str | None = None) -> dict:
    t = ctx.traffic
    data = _data(ctx)

    def feed(i):
        b = data.batch(i)
        return b["tokens"], b["labels"]

    return ref_train.readings(
        ctx.model, ctx.c, num, lambda: _params(ctx), feed, ctx.check_steps,
        t["microbatches_per_step"], t["microbatch"] * t["seq_len"],
        t["optimizer"], wire_block=t["quant_block"],
        fault=fault)
