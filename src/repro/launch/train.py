"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 100 \
        --batch 16 --seq 256 --ckpt-dir /tmp/ckpt [--reduced] [--accum 2] \
        [--remat 2level] [--dpu]

On this CPU container use ``--reduced`` (same-family tiny config); on a
real TPU fleet the full config shards over ``make_production_mesh()``.
Fault tolerance: the driver checkpoints every ``--ckpt-every`` steps and
resumes from the latest checkpoint on restart — combined with an external
supervisor (restart-on-failure), this is the slice-granular half of
SWARM's fault-tolerance story (DESIGN.md §3); the peer-granular half lives
in the simulator (`repro.core.swarm`).
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.data.synthetic import SyntheticLM
from repro.optim import adamw, lamb, delayed_parameter_updates
from repro.train.steps import make_train_step, make_state
from repro.ckpt import save_checkpoint, restore_checkpoint, latest_step
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", choices=["adamw", "lamb"],
                    default="adamw")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--remat", default="block",
                    choices=["block", "2level", "none"])
    ap.add_argument("--dpu", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    opt = (adamw(lr=args.lr) if args.optimizer == "adamw"
           else lamb(lr=args.lr))
    if args.dpu:
        opt = delayed_parameter_updates(opt)

    state = make_state(cfg, opt, jax.random.PRNGKey(args.seed))
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state, start = restore_checkpoint(args.ckpt_dir, state)
        print(f"resumed from step {start}")

    step_fn = jax.jit(make_train_step(cfg, opt, remat=args.remat,
                                      accum=args.accum))
    ds = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=17)
    n_hosts = jax.process_count()

    t0 = time.time()
    for i in range(start, args.steps):
        batch = ds.batch(i, host_index=jax.process_index(),
                         host_count=n_hosts)
        if cfg.rope == "mrope":
            import jax.numpy as jnp
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(args.seq), (3, batch["tokens"].shape[0],
                                       args.seq))
        if cfg.family == "audio":
            import jax.numpy as jnp
            batch["audio_embed"] = jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(7), i),
                (batch["tokens"].shape[0], cfg.encoder_max_len,
                 cfg.d_model), cfg.compute_jdtype)
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss), f"loss diverged at step {i}"
        if i % 5 == 0 or i == args.steps - 1:
            dt = (time.time() - t0) / max(i - start + 1, 1)
            print(f"step {i:5d}  loss {loss:8.4f}  {dt:6.2f}s/step")
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i + 1, state)
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, state)
    print("done")


if __name__ == "__main__":
    main()
