"""Bring-up smoke: the SWARM trainer and its Pallas stage path on a TPU.

    python chip_smoke.py              # one chip: phases 1 and 2
    python chip_smoke.py --chips 4    # four chips: phases 4a and 4b

One chip:
  1. the elastic trainer end to end — ``SwarmRunner(numeric=True)`` on
     xlstm-125m at its published widths (2 stages x 2 peers, int8
     boundary, Pallas kernels), held to the fault-free staged reference;
  2. the last stage of swarm-1b at full width (474M parameters) driven
     through ``NumericExecutor`` once with the Pallas kernels and once
     with the jnp path, on the same state and int8 wire input.
Four chips:
  4a. the GSPMD pipeline step of xlstm-125m on a (pod 2, data 2, model 1)
      mesh against the plain train step on the same batch;
  4b. a ``MeshExecutor`` peer spanning the four chips inside a
      ``SwarmRunner``, against the staged reference.

Lines starting ``bring-up`` are observations of this run (compile
seconds, peak device memory, step wall time up to ``block_until_ready``),
not benchmark numbers.  The last line is one JSON object naming the
device.  With no TPU the script exits non-zero and prints no result.
The phase functions take their configuration as an argument, so the
CPU tests drive them at reduced sizes; only ``main()`` demands a TPU.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import SwarmConfig, SwarmRunner, reference_losses  # noqa: E402
from repro.core.sim import Sleep  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.optim.adamw import Optimizer  # noqa: E402

# the churn/runtime tests' bound on |swarm loss - staged reference loss|
LOSS_ATOL = 2e-4
# phase 2 compares two backends that both compute in bfloat16: one bf16
# ulp is 2**-8 (3.9e-3) relative, the two paths round the attention
# output in a different order, and 16 applications of the shared layer
# compound it, so gradients are held to a relative L2 error and the loss
# to a relative error a few bf16 ulps wide
STAGE_LOSS_RTOL = 1e-2
STAGE_GRAD_RTOL = 5e-2
# phases 4a/4b run float32 at matmul precision "highest", so the only
# differences are cross-device reduction orders; the gradient and mesh
# peer bounds are those tests/test_distribution.py (pipeline) and
# tests/test_runtime.py (mesh peer, plain SGD) hold the same comparisons
# to.  The pipeline test's loss bound, 1e-4 absolute, is set at a loss
# near 5; the whole-model xlstm-125m at init (tied embedding, std 1)
# scores about 723, where 1e-4 is under two f32 ulps.  There the loss is
# held to the pairwise-summation bound of a mean over 16384 tokens
# reduced in a different order: log2(n) * eps(f32) = 14 * 6e-8 < 1e-6.
PIPE_LOSS_ATOL = 1e-4
PIPE_LOSS_RTOL = 1e-6
PIPE_GRAD_ATOL = 1e-3
MESH_LOSS_ATOL = 2e-3


class SmokeFailure(RuntimeError):
    """A phase's comparison or check did not hold."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _log(phase: str, **kv) -> None:
    print("bring-up", f"phase={phase}",
          " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def _grad_capture() -> Optimizer:
    """Optimizer that keeps the gradient itself in its state and leaves
    the params alone: comparisons read ``opt["g"]`` exactly, instead of
    a param delta rounded to one ulp of the params."""
    return Optimizer(
        init=lambda p: {"g": jax.tree.map(jnp.zeros_like, p)},
        update=lambda g, s, p: (jax.tree.map(jnp.zeros_like, g), {"g": g}))


def _rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _max_scaled(a, b) -> float:
    """max |a - b| over max |b| (the tests' scale-normalized error)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))


def _has_kernel(fn, *args) -> bool:
    """Does ``fn`` compiled at ``args`` contain a native Pallas kernel?
    (Interpret mode and the jnp fallback compile to plain HLO.)"""
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _step_clock(runner: SwarmRunner, walls: list):
    """Sim process: the wall time of each optimizer step, closed once the
    stepped params of every live peer are on the device."""
    seen, t = runner.step, time.perf_counter()
    while not runner.stopped:
        yield Sleep(0.05)
        if runner.step != seen:
            jax.block_until_ready([p.state.params
                                   for p in runner.peers.values()
                                   if p.alive])
            now = time.perf_counter()
            walls.append(now - t)
            seen, t = runner.step, now


# ------------------------------------------------------------ one chip
def phase_elastic(cfg, *, seq: int, mb: int, gb: int, steps: int,
                  seed: int = 0, lr: float = 1e-3) -> dict:
    """Phase 1: the numeric swarm (2 stages x 2 peers, int8 boundary)
    for ``steps`` optimizer steps, held to the staged reference."""
    scfg = SwarmConfig(n_stages=2, microbatch_size=mb, seq_len=seq,
                       global_batch=gb, n_trainers=2, rebalance_period=0.0,
                       codec="int8", max_steps=steps)
    opt = adamw(lr=lr, grad_clip=0.0)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=seed)
    runner.build(peers_per_stage=2)
    walls: list[float] = []
    runner.sim.spawn(_step_clock(runner, walls))
    m = runner.run(until=1e6)
    _check(runner.step == steps, f"ran {runner.step} of {steps} steps")
    losses = [float(x) for x in m["loss"]]
    _check(len(losses) == steps and all(np.isfinite(losses)),
           f"losses {losses}")
    ref = reference_losses(cfg, runner.programs, opt, seed, steps, seq, mb,
                           gb, executors=runner.executors)
    err = max(abs(a - b) for a, b in zip(losses, ref))
    _check(err <= LOSS_ATOL, f"swarm {losses} vs reference {ref}: "
           f"max |diff| {err} > {LOSS_ATOL}")
    y = jnp.zeros((mb, seq, cfg.d_model), cfg.compute_jdtype)
    has_kernel = _has_kernel(runner.executors[0].wire_fwd, y)
    return {"losses": losses, "reference": ref, "max_abs_diff": err,
            "step_wall_s": walls, "wire_kernel": has_kernel}


def _stage_pass(cfg, n_stages: int, stage: int, h, labels, key, opt,
                steps: int = 2):
    """``steps`` one-microbatch optimizer steps through stage ``stage``'s
    executor: fwd, bwd, accumulate, export, optimizer step, adopt.  The
    first step's wall time includes compilation, later ones do not.
    Returns the observations and the first step's gradient (host)."""
    from repro.runtime import build_numeric_executors
    seq = labels.shape[1]
    exs = build_numeric_executors(cfg, n_stages, seq, compress="int8")
    ex = exs[stage]
    inp = exs[stage - 1].wire_fwd(h)            # the int8 wire tensor
    state = ex.init_state(key)
    state.opt = opt.init(state.params)
    n_tok = int(labels.size)

    # the runner's barrier math (SwarmRunner._ar_plan) for one peer,
    # jitted with the old params and Adam state donated: two copies of a
    # full-width stage's state do not fit one chip
    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def apply_step(grads, opt_state, params):
        g = jax.tree.map(lambda x: x / n_tok, grads)
        upd, new_opt = opt.update(g, opt_state, params)
        return (jax.tree.map(lambda p, u: p + u.astype(p.dtype), params,
                             upd), new_opt)

    t = time.perf_counter()
    compiled = ex.prog.bwd.lower(state.params, inp, labels).compile()
    compile_s = time.perf_counter() - t
    mem = compiled.memory_analysis()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    del compiled

    losses, walls, host_grads = [], [], None
    for i in range(steps):
        t = time.perf_counter()
        fwd_loss = float(ex.run_fwd(state, inp, labels))
        loss, _gx, gp = ex.run_bwd(state, inp, labels=labels)
        loss = float(loss)
        ex.accumulate(state, gp, loss, n_tok)
        del gp, _gx
        grads = ex.export_grads(state)
        if i == 0:
            t_copy = time.perf_counter()
            host_grads = jax.device_get(grads)
            t += time.perf_counter() - t_copy   # the copy is not the step
        new_params, new_opt = apply_step(grads, state.opt, state.params)
        ex.adopt_step(state, new_params, new_opt)
        jax.block_until_ready(state.params)
        walls.append(time.perf_counter() - t)
        _check(np.isfinite(loss) and np.isfinite(fwd_loss),
               f"step {i}: loss {loss} / fwd {fwd_loss}")
        losses.append(loss)
    _check(all(np.all(np.isfinite(g)) for g in jax.tree.leaves(host_grads)),
           "non-finite gradient")
    _check(all(np.all(np.isfinite(np.asarray(p)))
               for p in jax.tree.leaves(state.params)),
           "non-finite params after the steps")
    obs = {"loss": losses[0], "losses": losses,
           "bwd_compile_s": compile_s,
           "step_wall_s": walls, "has_kernel": has_kernel,
           "bwd_temp_bytes": getattr(mem, "temp_size_in_bytes", None),
           "n_params": sum(int(np.prod(p.shape))
                           for p in jax.tree.leaves(state.params))}
    return obs, host_grads


def phase_stage(cfg, *, n_stages: int, stage: int, mb: int, seq: int,
                seed: int = 0, lr: float = 1e-4) -> dict:
    """Phase 2: one stage through ``NumericExecutor`` with
    ``kernels="pallas"`` and then ``"jnp"`` (the first freed before the
    second), on the same params and the same int8 wire input."""
    key = jax.random.PRNGKey(seed)
    h = jax.random.normal(jax.random.fold_in(key, 1),
                          (mb, seq, cfg.d_model)).astype(cfg.compute_jdtype)
    labels = jax.random.randint(jax.random.fold_in(key, 2), (mb, seq), 0,
                                cfg.vocab_size)
    opt = adamw(lr=lr, grad_clip=0.0)
    out, grads = {}, {}
    for kernels in ("pallas", "jnp"):
        out[kernels], grads[kernels] = _stage_pass(
            cfg.with_overrides(kernels=kernels), n_stages, stage, h, labels,
            key, opt)
        gc.collect()
        _log("stage", backend=kernels, **out[kernels])
    lp, lj = out["pallas"]["loss"], out["jnp"]["loss"]
    loss_rel = abs(lp - lj) / abs(lj)
    grad_rel = max(_rel_l2(a, b) for a, b in
                   zip(jax.tree.leaves(grads["pallas"]),
                       jax.tree.leaves(grads["jnp"])))
    _check(loss_rel <= STAGE_LOSS_RTOL,
           f"pallas loss {lp} vs jnp {lj}: rel {loss_rel}")
    _check(grad_rel <= STAGE_GRAD_RTOL,
           f"pallas vs jnp gradients: max per-leaf rel L2 {grad_rel}")
    return {"pallas": out["pallas"], "jnp": out["jnp"],
            "loss_rel_diff": loss_rel, "grad_max_rel_l2": grad_rel}


# ---------------------------------------------------------- four chips
def phase_gspmd_pipeline(cfg, *, seq: int, gb: int, n_micro: int,
                         seed: int = 0) -> dict:
    """Phase 4a: ``make_pipeline_train_step`` on a (pod 2, data 2,
    model 1) mesh against ``make_train_step`` on the same batch, float32
    at matmul precision "highest"."""
    from repro.data import make_batch
    from repro.dist.pipeline import make_pipeline_train_step
    from repro.train.steps import make_state, make_train_step
    cfg = cfg.with_overrides(compute_dtype="float32",
                             param_dtype="float32",
                             boundary_compression="none")
    opt = _grad_capture()
    state = make_state(cfg, opt, jax.random.PRNGKey(seed))
    batch = make_batch(cfg.vocab_size, seq, gb, seed=seed)
    mesh = jax.make_mesh((2, 2, 1), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    with jax.default_matmul_precision("highest"):
        ref_state, ref_m = jax.jit(make_train_step(cfg, opt))(state, batch)
        pipe = make_pipeline_train_step(cfg, opt, n_stages=2,
                                        n_microbatches=n_micro,
                                        compress="none")
        with jax.set_mesh(mesh):
            t = time.perf_counter()
            pipe_state, m = jax.jit(pipe)(state, batch)
            jax.block_until_ready(pipe_state)
            wall = time.perf_counter() - t
    loss, ref_loss = float(m["loss"]), float(ref_m["loss"])
    errs = [_max_scaled(a, b) for a, b in
            zip(jax.tree.leaves(pipe_state["opt"]["g"]),
                jax.tree.leaves(ref_state["opt"]["g"]))]
    _check(abs(loss - ref_loss) <= max(PIPE_LOSS_ATOL,
                                       PIPE_LOSS_RTOL * abs(ref_loss)),
           f"pipeline loss {loss} vs plain step {ref_loss}")
    _check(max(errs) < PIPE_GRAD_ATOL,
           f"pipeline grads: max scaled error {max(errs)}")
    return {"loss": loss, "ref_loss": ref_loss, "grad_max_scaled": max(errs),
            "first_step_wall_s": wall}


def phase_mesh_peer(cfg, *, n_devices: int, seq: int, mb: int, gb: int,
                    steps: int, seed: int = 0, lr: float = 1e-2) -> dict:
    """Phase 4b: one ``MeshExecutor`` peer per stage over
    ``make_peer_mesh(n_devices)`` beside one numeric peer per stage,
    plain SGD, float32 at matmul precision "highest", against the staged
    reference."""
    from repro.launch.mesh import make_peer_mesh
    from repro.runtime import MeshExecutor
    cfg = cfg.with_overrides(compute_dtype="float32",
                             param_dtype="float32")
    opt = Optimizer(init=lambda p: {"n": jnp.zeros(())},
                    update=lambda g, s, p: (
                        jax.tree.map(lambda x: -lr * x, g), s))
    scfg = SwarmConfig(n_stages=2, microbatch_size=mb, seq_len=seq,
                       global_batch=gb, n_trainers=2, rebalance_period=0.0,
                       codec="int8", max_steps=steps)
    mesh = make_peer_mesh(n_devices)
    with jax.default_matmul_precision("highest"):
        runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=seed)
        runner.build(peers_per_stage=1)
        for s in range(2):
            ex = MeshExecutor(cfg, 2, seq, s, mesh, compress="int8")
            _check(ex.device_count == n_devices,
                   f"mesh peer holds {ex.device_count} devices")
            runner.add_peer(s, executor=ex)
        walls: list[float] = []
        runner.sim.spawn(_step_clock(runner, walls))
        m = runner.run(until=1e6)
        _check(runner.step == steps, f"ran {runner.step} of {steps} steps")
        losses = [float(x) for x in m["loss"]]
        ref = reference_losses(cfg, runner.programs, opt, seed, steps, seq,
                               mb, gb, executors=runner.executors)
    err = max(abs(a - b) for a, b in zip(losses, ref))
    _check(all(np.isfinite(losses)) and err <= MESH_LOSS_ATOL,
           f"mesh swarm {losses} vs reference {ref}")
    return {"losses": losses, "reference": ref, "max_abs_diff": err,
            "step_wall_s": walls}


# ----------------------------------------------------------------- main
def _peaks() -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"no TPU: jax found {dev.platform}", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, jax "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    compile_s = [0.0]

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    print("bring-up", f"platform={dev.platform}", f"kind={dev.device_kind}",
          f"count={len(devs)}", f"compile_cache={cache}", flush=True)

    def run(name, fn, **kw):
        c0, t0 = compile_s[0], time.perf_counter()
        res = fn(**kw)
        _log(name, wall_s=time.perf_counter() - t0,
             backend_compile_s=compile_s[0] - c0, peak_bytes=_peaks(),
             **{k: v for k, v in res.items()
                if k not in ("pallas", "jnp")})
        return res

    xlstm = get_config("xlstm-125m")
    if args.chips == 1:
        res = run("elastic", phase_elastic,
                  cfg=xlstm.with_overrides(kernels="pallas",
                                           boundary_compression="int8"),
                  seq=2048, mb=4, gb=16, steps=3, seed=args.seed)
        _check(res["wire_kernel"], "int8 wire compiled without a kernel")
        gc.collect()
        res = run("stage", phase_stage, cfg=get_config("swarm-1b"),
                  n_stages=3, stage=2, mb=4, seq=1024, seed=args.seed)
        _check(res["pallas"]["has_kernel"],
               "pallas stage step compiled without tpu_custom_call")
    else:
        run("gspmd_pipeline", phase_gspmd_pipeline, cfg=xlstm, seq=2048,
            gb=8, n_micro=4, seed=args.seed)
        gc.collect()
        run("mesh_peer", phase_mesh_peer, cfg=xlstm, n_devices=4, seq=2048,
            mb=4, gb=8, steps=2, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
