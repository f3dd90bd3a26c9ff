"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per benchmark plus the roofline
tables derived from the dry-run artifacts (if present).

    PYTHONPATH=src python -m benchmarks.run [--only <name>]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def smoke() -> None:
    """CI import-rot guard: one real train step, then import every module
    under ``src/repro`` and every benchmark suite.

    The train step runs FIRST so the jax backend initializes with the
    default device view — ``repro.launch.dryrun`` mutates XLA_FLAGS (the
    512-device override) at import, which must not leak into the step.
    """
    import importlib
    import pkgutil

    import jax

    from repro.data import make_batch
    from repro.models.config import ArchConfig
    from repro.optim import adamw
    from repro.train.steps import make_state, make_train_step

    cfg = ArchConfig(name="smoke", family="dense", n_layers=2, d_model=32,
                     n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128,
                     head_dim=16, compute_dtype="float32",
                     param_dtype="float32")
    opt = adamw(lr=1e-3)
    state = make_state(cfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, opt, remat=False))
    _, m = step(state, make_batch(cfg.vocab_size, 16, 2))
    print(f"smoke_train_step,loss,{float(m['loss']):.4f}")

    import benchmarks
    import repro
    failed = []
    for pkg in (repro, benchmarks):
        for info in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + "."):
            try:
                importlib.import_module(info.name)
            except Exception as e:
                failed.append((info.name, f"{type(e).__name__}: {e}"))
    for name, err in failed:
        print(f"# IMPORT FAILED {name}: {err}", file=sys.stderr)
    print(f"smoke_imports,modules_ok,{'FAIL' if failed else 'OK'}")
    sys.exit(1 if failed else 0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="1-step import-rot guard (CI): no full suites")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.smoke:
        smoke()
        return

    from benchmarks import (bench_square_cube, bench_throughput,
                            bench_rebalance, bench_scaling,
                            bench_compression, bench_cost, bench_swarm,
                            bench_serve, bench_control, bench_kernels,
                            roofline)
    suites = {
        "kernels": bench_kernels.run,             # pallas vs jnp per-kernel
        "square_cube": bench_square_cube.run,     # Fig.3 / Table 1
        "throughput": bench_throughput.run,       # Table 2
        "rebalance": bench_rebalance.run,         # Table 5 / Fig.5 / Fig.7
        "scaling": bench_scaling.run,             # Fig.6 / Tables 3-4
        "compression": bench_compression.run,     # Table 7/8
        "cost": bench_cost.run,                   # Table 9
        "swarm": bench_swarm.run,                 # runtime layer: compile
                                                  # cache + BENCH_swarm.json
        "serve": bench_serve.run,                 # serving layer: tokens/s,
                                                  # p99, churn recovery
        "control": bench_control.run,             # control plane at 1000-peer
                                                  # scale + leak audit
    }
    failed = []
    for name, fn in suites.items():
        if args.only and args.only != name:
            continue
        t0 = time.time()
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
        print(f"# {name} took {time.time() - t0:.1f}s\n")

    if not args.only or args.only == "roofline":
        try:
            print("# roofline (single-pod baseline, from dry-run artifacts)")
            roofline.main("single")
        except Exception:
            failed.append("roofline")
            traceback.print_exc()

    if failed:
        print(f"# FAILED suites: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
