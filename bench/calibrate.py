"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 7,8,9] [--out readings.jsonl]

In one process, for each seed: the program's checked steps (set-up of a
normal run, then a window of one step) against the plain float32
reference.  For each control seed besides: the control (the reference in
the program's place with float8 matmul operands, the precision below the
configuration's bfloat16) and the planted faults (the reference in the
program's place with half of each step's batch left out, and with the
largest leaf of the last stage's gradient doubled), each against the
float32 reference.  One JSON line per reading, with ``correct`` as the
harness decides it from the cell's limits (``check.decide``).  The benchmark's own runs
never run this; it needs the chip like they do.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    harness.use_checkout_paths()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    cell = harness.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return harness.NO_DEVICE
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from bench import check, plain
    arch = harness.arch_config(cell["config"])
    driver = cell["driver"]
    out = open(args.out, "a") if args.out else sys.stdout

    limits = cell["workload"]["check"]["limits"]

    def emit(**rec):
        """One reading, with the verdict the harness's own comparison
        gives it under the cell's limits."""
        rec["correct"] = check.decide(rec["numbers"], limits)[0]
        out.write(json.dumps(rec) + "\n")
        out.flush()

    for seed in seeds + [s for s in control if s not in seeds]:
        ctx = harness.Context(cell, seed, 0.0, None, arch)
        t = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            ref_t = time.perf_counter()
            ref = driver.reference(ctx, plain.Numerics("f32"))
            ref_s = time.perf_counter() - ref_t
        if seed in seeds:
            res = driver.run(ctx)
            gc.collect()
            emit(cell=args.workload, seed=seed, kind="program",
                 numbers=check.numbers(res["program"], ref),
                 worst_grad=check.worst_leaves(res["program"], ref, "grad"),
                 worst_change=check.worst_leaves(res["program"], ref,
                                                 "change"),
                 program=res["program"]["loss"], reference=ref["loss"],
                 reference_s=ref_s, seconds=time.perf_counter() - t)
        if seed in control:
            runs = [("control", plain.Numerics("fp8"), None)] + [
                (f, plain.Numerics("f32"), f)
                for f in ("half_batch", "grad_doubled")]
            for kind, num, fault in runs:
                t = time.perf_counter()
                with jax.default_matmul_precision("highest"):
                    got = driver.reference(ctx, num, fault=fault)
                emit(cell=args.workload, seed=seed, kind=kind,
                     numbers=check.numbers(got, ref), program=got["loss"],
                     worst_grad=check.worst_leaves(got, ref, "grad"),
                     reference=ref["loss"], seconds=time.perf_counter() - t)
        del ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
