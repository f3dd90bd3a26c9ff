"""The harness on the CPU: each driver end to end at tiny widths, a cell
added from files alone, and no result without a TPU."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT, tiny_root  # noqa: F401

SEED = 2**31 + 4097          # wider than 32 signed bits


def run_tiny(root, cell, trace=False, seed=SEED):
    from bench import run
    return run.run_cell(cell, seed, 0.5, trace, root=root,
                        require_tpu=False, peaks_kind="TPU v5 lite")


def _digest(path):
    h = hashlib.sha256()
    for base, _, files in sorted(os.walk(path)):
        for f in sorted(files):
            if f.endswith(".pyc"):
                continue
            with open(os.path.join(base, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    return h.hexdigest()


def _well_formed(res, names):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(names)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] >= 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(res["device"])
    for ck in res["checks"].values():
        assert set(ck) == {"value", "limit"}
    json.dumps(res)


@pytest.mark.parametrize("cell", ["tiny-xlstm.elastic", "tiny-swarm.stage"])
def test_cell_from_new_files_runs_end_to_end(tiny_root, cell):
    before = _digest(os.path.join(ROOT, "bench"))
    res = run_tiny(tiny_root, cell)
    _well_formed(res, ["tokens_per_s", "mfu", "setup_s"])
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["setup_s"]["value"] > 0
    assert _digest(os.path.join(ROOT, "bench")) == before


def test_traced_run_reports_per_layer_metrics(tiny_root):
    res = run_tiny(tiny_root, "tiny-swarm.stage", trace=True)
    assert res["correct"] is True
    assert res["metrics"]["executor.compiles_in_window"]["value"] == 0
    assert "tokens_per_s" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    from bench.run import NO_DEVICE
    p = _cli(ROOT)
    assert p.returncode == NO_DEVICE, p.stderr
    assert p.stdout == ""


def test_bare_checkout_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    (no program) exits non-zero and prints nothing."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout == ""
