"""The benchmark's copied operation counts against the program's own, at
each cell's shapes, and the table of peaks."""
from __future__ import annotations

import json
import os

import pytest

from bench_tiny import ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_xlstm_forward_per_token_matches_the_program():
    from bench import flops
    from repro.configs import get_config
    from repro.models import flops as F
    c = _cfg("xlstm-125m")
    ours = sum(flops.stage_forward_flops(c, 2, s, 2048) for s in (0, 1))
    assert ours == pytest.approx(163.4e6, rel=1e-3)
    assert ours == pytest.approx(
        F.forward_flops_per_token(get_config("xlstm-125m"), 2048), rel=1e-12)
    assert flops.train_flops_per_token(c, 2, [0, 1], 2048) == \
        pytest.approx(3 * ours)


def test_swarm1b_stage2_forward_per_token_matches_the_program():
    from bench import flops
    from repro.configs import get_config
    from repro.models import flops as F
    c = _cfg("swarm-1b")
    ours = flops.stage_forward_flops(c, 3, 2, 1024)
    assert ours == pytest.approx(9.136e9, rel=1e-3)
    assert ours == pytest.approx(
        F.stage_flops_per_token(get_config("swarm-1b"), 3, 2, 1024),
        rel=1e-12)


def test_peaks_known_and_unknown():
    from bench import flops
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_flash_counts_only_unmasked_pairs():
    from bench import flops
    full, nbytes = flops.flash_fwd_cost(1, 1, 4, 2, causal=False)
    half, _ = flops.flash_fwd_cost(1, 1, 4, 2, causal=True)
    assert full == 4 * 2 * 16 and half == 4 * 2 * 10
    assert nbytes == 4 * 4 * 2 * 2 + 4 * 4


def test_roofline_names_its_bound():
    from bench import flops
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_share(100.0, 1.0, 2.0, p) == (50.0, "compute")
    assert flops.roofline_share(1.0, 100.0, 20.0, p) == (50.0, "bytes")
