import os
# Compile-only on 512 forced host devices: pinned to the CPU before jax
# starts, so neither this process nor its --jobs children ever take an
# accelerator another process holds.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax-importing module (device count locks on first init).

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with full configs as ShapeDtypeStructs (no allocation), record
memory/cost analysis + the collective schedule, and emit one JSON artifact
per cell for the roofline analysis (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--jobs 8]
"""

import argparse
import json
import re
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (REGISTRY, ASSIGNED, SHAPES, get_config,
                           cell_supported, ShapeSpec)
from repro.launch.mesh import make_production_mesh
from repro.launch import hlo_analysis
from repro.models.config import ArchConfig
from repro.models import probe as probe_lib
from repro.optim import adamw
from repro.train import steps as steps_lib
from repro.dist import sharding as sh
from repro.dist import pipeline as pipe_lib

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "..", "..", "..", "artifacts", "dryrun")

PIPELINE_MICROBATCHES = 8


def _pod_axes(mesh) -> bool:
    return "pod" in mesh.axis_names


def build_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, remat="block",
               accum=1, opt_bf16=False, full_logits=False,
               strategy="auto"):
    """Returns (fn, args, in_shardings, donate) for this cell."""
    multipod = _pod_axes(mesh)
    batch_axis = ("pod", "data") if multipod else "data"
    specs = steps_lib.input_specs(cfg, shape)
    if strategy == "dp":
        # small-model strategy: replicate parameters, shard the batch over
        # BOTH axes — kills every TP psum/all-gather; the only collective
        # left is one gradient all-reduce (EXPERIMENTS.md §Perf, xlstm)
        batch_axis = (("pod", "data", "model") if multipod
                      else ("data", "model"))
        # keep the vocab shard: a replicated LM head re-multiplies the
        # full [T,d]x[d,V] on every chip (xlstm iter-1 lesson: +2.3x flops)
        dp_rules = {k: None for k in sh.DEFAULT_RULES.rules}
        dp_rules["vocab"] = "model"
        sh_kw = dict(rules=sh.ShardingRules(rules=dp_rules))
    else:
        sh_kw = {}

    if shape.kind == "train":
        opt = adamw(state_dtype=jnp.bfloat16 if opt_bf16 else jnp.float32)
        if opt_bf16:
            st = specs["state"]
            st["opt"]["m"] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
                st["opt"]["m"])
            st["opt"]["v"] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
                st["opt"]["v"])
        pipeline = multipod and pipe_lib.stage_periodic(cfg, mesh.shape["pod"])
        if pipeline:
            step = pipe_lib.make_pipeline_train_step(
                cfg, opt, mesh.shape["pod"], PIPELINE_MICROBATCHES)
            st_sh = sh.state_shardings(cfg, mesh, pipeline=True)
            b_axis = "data"      # microbatching consumes the pod axis
        else:
            step = steps_lib.make_train_step(cfg, opt, remat=remat,
                                             accum=accum)
            st_sh = sh.state_shardings(cfg, mesh, **sh_kw)
            b_axis = batch_axis
        in_sh = (st_sh, sh.batch_shardings(cfg, mesh, specs["batch"],
                                           batch_axis=b_axis))
        scalar = jax.sharding.NamedSharding(mesh,
                                            jax.sharding.PartitionSpec())
        out_sh = (st_sh, {"loss": scalar, "ce": scalar})
        return (step, (specs["state"], specs["batch"]), in_sh, (0,),
                pipeline, out_sh)

    if shape.kind == "prefill":
        step = steps_lib.make_prefill_step(cfg, last_only=not full_logits)
        p_sh = sh.param_shardings(cfg, mesh)
        in_sh = (p_sh, sh.batch_shardings(cfg, mesh, specs["batch"],
                                          batch_axis=batch_axis))
        # emitted decode caches must land sharded, not replicated
        cache_sh = sh.cache_shardings_from_specs(
            cfg, mesh, steps_lib.decode_cache_param_specs(cfg, shape),
            batch_axis=batch_axis)
        tok_sh = sh.batch_shardings(
            cfg, mesh,
            {"t": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)},
            batch_axis=batch_axis)["t"]
        out_sh = (tok_sh, cache_sh)
        return (step, (specs["params"], specs["batch"]), in_sh, (), False,
                out_sh)

    # decode
    step = steps_lib.make_serve_step(cfg)
    p_sh = sh.param_shardings(cfg, mesh)
    cache_param_specs = steps_lib.decode_cache_param_specs(cfg, shape)
    c_sh = sh.cache_shardings_from_specs(cfg, mesh, cache_param_specs,
                                         batch_axis=batch_axis)
    tok_sh = sh.batch_shardings(
        cfg, mesh, {"tokens": specs["token"]}, batch_axis=batch_axis
    )["tokens"]
    scalar = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    in_sh = (p_sh, c_sh, tok_sh, scalar)
    args = (specs["params"], specs["caches"], specs["token"], specs["pos"])
    return step, args, in_sh, (1,), False, (tok_sh, c_sh)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             skip_probe: bool = False, remat: str = "block",
             accum: int = 1, cf: float = 0.0,
             opt_bf16: bool = False, full_logits: bool = False,
             strategy: str = "auto") -> dict:
    cfg = get_config(arch)
    if cf and cfg.moe is not None:
        import dataclasses as _dc
        cfg = cfg.with_overrides(
            moe=_dc.replace(cfg.moe, capacity_factor=cf))
    shape = SHAPES[shape_name]
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                    "time": time.strftime("%Y-%m-%d %H:%M:%S")}
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        return record

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    step, args, in_sh, donate, pipeline, out_sh = build_cell(
        cfg, shape, mesh, remat=remat, accum=accum, opt_bf16=opt_bf16,
        full_logits=full_logits, strategy=strategy)
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = hlo_analysis.collective_bytes(hlo)
    record.update({
        "status": "ok",
        "remat": remat,
        "accum": accum,
        "capacity_factor": cf or None,
        "pipeline": bool(pipeline),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "hlo_flops_per_device_raw": cost.get("flops", 0.0),
        "hlo_bytes_per_device_raw": cost.get("bytes accessed", 0.0),
        "collectives": colls,
    })

    if shape.kind != "train" or not skip_probe:
        try:
            probe = hlo_analysis.layer_flop_probe(cfg, shape)
            record["probe"] = probe
        except Exception as e:           # probe is best-effort
            record["probe_error"] = f"{type(e).__name__}: {e}"
    return record


def artifact_path(arch: str, shape: str, mesh: str) -> str:
    d = os.path.abspath(ARTIFACT_DIR)
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{mesh}__{arch}__{shape}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(REGISTRY), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1,
                    help="parallel worker processes for --all")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--remat", default="block",
                    choices=["block", "2level", "none"])
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--opt-bf16", action="store_true")
    ap.add_argument("--full-logits", action="store_true",
                    help="paper-naive prefill emitting [B,S,V] logits")
    ap.add_argument("--strategy", default="auto", choices=["auto", "dp"])
    ap.add_argument("--cf", type=float, default=0.0)
    ap.add_argument("--tag", default="",
                    help="artifact name suffix (hillclimb iterations)")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [(a, s, m) for m in meshes for a in ASSIGNED
                 for s in SHAPES]
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape, m) for m in meshes]

    if args.jobs > 1 and len(cells) > 1:
        pending = [(a, s, m) for (a, s, m) in cells
                   if args.force or not os.path.exists(artifact_path(a, s, m))]
        print(f"{len(pending)} cells to run, {args.jobs} workers")
        procs: list = []
        n_fail = 0
        while pending or procs:
            while pending and len(procs) < args.jobs:
                a, s, m = pending.pop(0)
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", a, "--shape", s, "--mesh", m]
                procs.append(((a, s, m), subprocess.Popen(
                    cmd, stdout=subprocess.DEVNULL,
                    stderr=subprocess.PIPE)))
            done = []
            for i, (cell, p) in enumerate(procs):
                if p.poll() is not None:
                    done.append(i)
                    tag = "OK" if p.returncode == 0 else "FAIL"
                    print(f"[{tag}] {cell}")
                    if p.returncode != 0:
                        n_fail += 1
                        sys.stderr.write(p.stderr.read().decode()[-2000:])
            for i in reversed(done):
                procs.pop(i)
            time.sleep(0.5)
        # propagate worker failures so CI lanes (the weekly --all sweep)
        # actually gate on the sweep, mirroring the sequential branch below
        sys.exit(1 if n_fail else 0)

    n_fail = 0
    for a, s, m in cells:
        path = artifact_path(a, s, m + args.tag if args.tag else m)
        if not args.force and os.path.exists(path) and args.all:
            print(f"[cached] {m}/{a}/{s}")
            continue
        try:
            rec = run_cell(a, s, m, remat=args.remat, accum=args.accum,
                           cf=args.cf, opt_bf16=args.opt_bf16,
                           full_logits=args.full_logits,
                           strategy=args.strategy)
        except Exception as e:
            rec = {"arch": a, "shape": s, "mesh": m, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
            n_fail += 1
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        stat = rec["status"]
        extra = ""
        if stat == "ok":
            extra = (f" compile={rec['compile_s']}s "
                     f"peak/dev={rec['memory']['peak_per_device']/2**30:.2f}GiB"
                     f" flops/dev={rec['hlo_flops_per_device_raw']:.3g}")
        elif stat == "error":
            extra = " " + rec["error"][:160]
        print(f"[{stat}] {m}/{a}/{s}{extra}")
        # memory_analysis + cost_analysis proof lines (spec step 3)
        sys.stdout.flush()
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
