"""BENCHMARK.json and the files it names: every piece found by name, every
name of the allowed characters, every configuration as the program runs it."""
from __future__ import annotations

import dataclasses
import json
import os
import re

import pytest

from bench_tiny import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench", "tests/bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_and_units(bench):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
                assert entry["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_every_piece_found_by_name(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        wl = _json("bench", "workloads", w["name"] + ".json")
        assert {k: wl[k] for k in ("config", "traffic", "chips", "why")} == \
            {k: w[k] for k in ("config", "traffic", "chips", "why")}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        traffic = _json("bench", "traffic", w["traffic"] + ".json")
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "drivers", traffic["driver"] + ".py"))
        assert set(wl["check"]["limits"]) >= {
            "loss_gap", "grad_norm_gap", "step_change_gap"}
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert os.path.isfile(os.path.join(ROOT, "bench", "configs",
                                           c["name"] + ".py"))
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "layer_metrics", m["name"] + ".py"))
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


@pytest.mark.parametrize("name", ["xlstm-125m", "swarm-1b"])
def test_config_file_is_what_the_program_runs(name):
    from bench.run import arch_config
    c = _json("bench", "configs", name + ".json")
    arch = arch_config(c)
    for f in dataclasses.fields(arch):
        if f.name in c and f.name != "name":
            got = getattr(arch, f.name)
            want = c[f.name]
            if dataclasses.is_dataclass(got):
                got = {k: getattr(got, k) for k in want}
            elif isinstance(got, tuple):
                got = list(got)
            assert got == want, f.name


@pytest.mark.parametrize("name", ["xlstm-125m", "swarm-1b"])
def test_reference_tree_matches_the_program(name):
    """The reference declares the program's parameter layout, leaf for
    leaf, at the published widths (shapes only, nothing allocated)."""
    import jax
    from bench.run import arch_config, load_module
    from bench.weights import is_init
    from repro.runtime.stage_model import build_stage_programs
    c = _json("bench", "configs", name + ".json")
    model = load_module(os.path.join(ROOT, "bench", "configs", name + ".py"),
                        "ref_" + name.replace("-", "_"))
    progs = build_stage_programs(arch_config(c), c["n_stages"], 128,
                                 compress="int8")
    from repro.models import params as P
    for s in c["stages_run"]:
        want = jax.tree.map(lambda x: tuple(x.shape), progs[s].specs,
                            is_leaf=P.is_spec)
        got = jax.tree.map(lambda x: tuple(x.shape),
                           model.param_specs(c, c["n_stages"], s),
                           is_leaf=is_init)
        assert jax.tree.structure(want) == jax.tree.structure(got)
        assert jax.tree.leaves(want) == jax.tree.leaves(got)
