"""The direct cell's driver (``bench/drivers/stage_chain.py``) on the CPU:
the elastic cell's task at tiny widths through one executor per stage,
end to end through the harness, beside ``tiny-xlstm.elastic``; correct
on the program, not correct under each planted fault."""
from __future__ import annotations

import json
import os

import pytest

from bench_tiny import OPT, ROOT, TINY_XLSTM, build_root
from test_bench_faults import FAULTS
from test_bench_harness import _well_formed, run_tiny

CELL = "tiny-xlstm.direct"
# the elastic tiny cell's task (``bench_tiny.TRAFFIC["elastic"]``) without
# its swarm: the same seq, microbatch, step and wire
DIRECT = {"driver": "stage_chain", "codec": "int8", "quant_block": 64,
          "seq_len": 32, "microbatch": 2, "microbatches_per_step": 4,
          "optimizer": OPT}


@pytest.fixture(scope="module")
def direct_root(tmp_path_factory):
    """The tiny cells' checkout with ``tiny-xlstm.direct`` added by files
    alone, as a new cell is added."""
    root = build_root(str(tmp_path_factory.mktemp("tiny-direct")))
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "traffic", "direct.json"), "w") as f:
        json.dump(DIRECT, f)
    with open(os.path.join(bench, "workloads",
                           "tiny-xlstm.elastic.json")) as f:
        wl = json.load(f)
    with open(os.path.join(bench, "workloads", CELL + ".json"), "w") as f:
        json.dump(dict(wl, traffic="direct"), f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": CELL, "config": TINY_XLSTM["name"],
                              "traffic": "direct", "chips": 1,
                              "why": "tiny CPU cell"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def test_direct_cell_runs_end_to_end(direct_root):
    res = run_tiny(direct_root, CELL)
    _well_formed(res, ["tokens_per_s", "mfu", "setup_s"])
    assert res["correct"] is True, res["checks"]
    # the elastic cell's reference: no cotangent leaves the first stage
    assert set(res["checks"]) == {"loss_gap", "grad_norm_gap",
                                  "step_change_gap"}


def test_direct_and_elastic_train_alike(direct_root):
    """Same seed, same task: the two drivers' checked steps read the
    same losses and gradients (float32 at this size; they differ only
    in the order the swarm's two peers of a stage sum a step)."""
    from bench import plain, run
    got = {}
    for cell in (CELL, "tiny-xlstm.elastic"):
        c = run.load_cell(cell, direct_root)
        ctx = run.Context(c, 7, 0.0, None, run.arch_config(c["config"]))
        got[cell] = c["driver"].run(ctx)["program"]
        assert c["driver"].stages_trained(ctx) == [0, 1]
    a, b = got[CELL], got["tiny-xlstm.elastic"]
    assert a["loss"] == pytest.approx(b["loss"], rel=1e-5)
    assert set(a["grad"]) == set(b["grad"])
    for k in a["grad"]:
        assert a["grad"][k] == pytest.approx(b["grad"][k], rel=1e-4, abs=1e-9)
    ref = c["driver"].reference(ctx, plain.Numerics("f32"))
    assert set(ref["grad"]) == set(a["grad"])


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_direct_broken_timed_path_is_not_correct(direct_root, monkeypatch,
                                                 fault):
    FAULTS[fault](monkeypatch)
    res = run_tiny(direct_root, CELL)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("real", ["xlstm-125m.elastic", "xlstm-125m.direct"])
def test_control_is_not_correct_under_the_xlstm_limits(direct_root, real):
    """The float8 control in the program's place, held to each xlstm
    cell's committed limits, comes out not correct; the reference held
    to them against itself is correct."""
    from bench import check, plain, run
    with open(os.path.join(ROOT, "bench", "workloads", real + ".json")) as f:
        limits = json.load(f)["check"]["limits"]
    c = run.load_cell(CELL, direct_root)
    ctx = run.Context(c, 11, 0.0, None, run.arch_config(c["config"]))
    ref = c["driver"].reference(ctx, plain.Numerics("f32"))
    ctl = c["driver"].reference(ctx, plain.Numerics("fp8"))
    ok, checks = check.decide(check.numbers(ctl, ref), limits)
    assert not ok, checks
    assert check.decide(check.numbers(ref, ref), limits)[0]
