"""The control comes out not correct: the plain reference put in the
program's place with float8 (e4m3, per-tensor scaled) matmul operands,
the precision below the configurations' bfloat16, held to each committed
cell's limits at a size a test run can hold."""
from __future__ import annotations

import json
import os

import pytest

from bench_tiny import ROOT, tiny_root  # noqa: F401
from test_bench_harness import SEED


@pytest.mark.parametrize("cell,real", [
    ("tiny-swarm.stage", "swarm-1b.stage2")])
def test_control_is_not_correct(tiny_root, cell, real):
    from bench import check, plain, run
    with open(os.path.join(ROOT, "bench", "workloads", real + ".json")) as f:
        limits = json.load(f)["check"]["limits"]
    c = run.load_cell(cell, tiny_root)
    ctx = run.Context(c, SEED, 0.0, None, run.arch_config(c["config"]))
    ref = c["driver"].reference(ctx, plain.Numerics("f32"))
    ctl = c["driver"].reference(ctx, plain.Numerics("fp8"))
    ok, checks = check.decide(check.numbers(ctl, ref), limits)
    assert not ok, checks
    ok, _ = check.decide(check.numbers(ref, ref), limits)
    assert ok
