"""``chip_smoke.py`` on the CPU: its phase functions at reduced sizes, and
its refusal to report anything without a TPU.

Phases 1 and 2 run in this process (interpret-mode kernels); phases 4a
and 4b need four devices, so they run in a subprocess with forced host
devices, as ``tests/test_distribution.py`` does.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.config import reduced

ROOT = os.path.join(os.path.dirname(__file__), "..")
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _xlstm():
    """Reduced xlstm-125m keeping both block kinds in each stage."""
    return reduced(get_config("xlstm-125m")).with_overrides(
        n_layers=4, block_pattern=("mlstm", "slstm") * 2)


def test_phase_elastic_reduced(smoke):
    cfg = _xlstm().with_overrides(kernels="pallas",
                                  boundary_compression="int8")
    res = smoke.phase_elastic(cfg, seq=32, mb=2, gb=8, steps=3)
    assert len(res["losses"]) == 3 == len(res["step_wall_s"])
    assert res["max_abs_diff"] <= smoke.LOSS_ATOL
    assert max(res["losses"]) - min(res["losses"]) > 0   # params move


def test_phase_stage_reduced(smoke):
    """swarm-1b's last stage at reduced width, bf16 compute as on the
    chip: three shared-layer stages, two reps each."""
    cfg = reduced(get_config("swarm-1b")).with_overrides(
        n_layers=6, share_groups=3, compute_dtype="bfloat16")
    res = smoke.phase_stage(cfg, n_stages=3, stage=2, mb=2, seq=32)
    for backend in ("pallas", "jnp"):
        obs = res[backend]
        assert len(obs["losses"]) == 2 and obs["n_params"] > 0
        assert np.isfinite(obs["losses"]).all()
    assert res["loss_rel_diff"] <= smoke.STAGE_LOSS_RTOL
    assert res["grad_max_rel_l2"] <= smoke.STAGE_GRAD_RTOL


_FOUR_DEVICES = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    import jax
    assert len(jax.devices()) == 4
    from repro.configs import get_config
    from repro.models.config import reduced
    cfg = reduced(get_config("xlstm-125m")).with_overrides(
        n_layers=4, block_pattern=("mlstm", "slstm") * 2)
    a = smoke.phase_gspmd_pipeline(cfg, seq=32, gb=8, n_micro=4)
    b = smoke.phase_mesh_peer(cfg, n_devices=4, seq=32, mb=4, gb=8,
                              steps=2)
    assert len(b["losses"]) == 2
    print("FOUR_DEVICE_PHASES_OK", a["grad_max_scaled"], b["max_abs_diff"])
""")


def test_four_device_phases_reduced():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICES],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FOUR_DEVICE_PHASES_OK" in r.stdout


def _no_result(r: subprocess.CompletedProcess) -> bool:
    for line in r.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_main_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SMOKE], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert _no_result(r)


def test_script_alone_fails(tmp_path):
    """Copied out of the repo, the script has no program to run."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, env=env,
                       cwd=tmp_path, timeout=300)
    assert r.returncode != 0
    assert _no_result(r)


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache
    sits at <repo>/.jax_cache."""
    from repro.launch.compile_cache import REPO_ROOT, use_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert use_compile_cache() == os.path.join(REPO_ROOT,
                                                       ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == \
                os.path.join(REPO_ROOT, ".jax_cache")
            assert os.path.samefile(REPO_ROOT, ROOT)
        else:
            path = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
            assert use_compile_cache() == path
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
