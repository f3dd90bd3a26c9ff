"""Distribution layer: sharding rules + the GSPMD SWARM pipeline.

Multi-device cases run in a subprocess so the main test process keeps the
single-device view (the 512-device override is dryrun-only by design).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist.sharding import DEFAULT_RULES
from repro.dist.pipeline import stage_periodic


def test_rules_divisibility_fallback():
    """kv_heads=4 on a 16-way model axis must fall back to replication."""
    class M:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    spec = DEFAULT_RULES.spec_for(("embed", "kv_heads", "head_dim"),
                                  (4096, 4, 128), M())
    assert tuple(spec) == ("data",)          # kv_heads dim dropped


def test_rules_no_double_axis_use():
    class M:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    spec = DEFAULT_RULES.spec_for(("mlp", "embed2"), (4096, 4096), M())
    # both map to 'model'; only the first may take it
    assert list(spec).count("model") <= 1


def test_stage_periodicity():
    assert stage_periodic(get_config("yi-6b"), 2)
    assert stage_periodic(get_config("xlstm-125m"), 2)       # (5m,1s)x2
    assert not stage_periodic(get_config("whisper-large-v3"), 2)
    assert not stage_periodic(get_config("swarm-1b"), 2)     # share_groups
    assert not stage_periodic(get_config("yi-6b"), 7)        # 32 % 7


_PIPELINE_EQUIV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.config import ArchConfig
    from repro.optim import adamw
    from repro.train.steps import make_train_step, make_state
    from repro.dist.pipeline import make_pipeline_train_step
    from repro.data import make_batch

    from repro.optim.adamw import Optimizer
    from repro.train.steps import make_loss_fn
    cfg = ArchConfig(name="tiny", family="dense", n_layers=4, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                     head_dim=16, compute_dtype="float32",
                     param_dtype="float32", boundary_compression="none")
    # gradient-extractor optimizer: updated params = params + grads, so we
    # compare raw gradients (an adam step sign-normalizes tiny grads and
    # amplifies f32 reduction noise to O(lr))
    grad_opt = Optimizer(init=lambda p: {"z": jnp.zeros(())},
                         update=lambda g, s, p: (g, s))
    state = make_state(cfg, grad_opt, jax.random.PRNGKey(0))
    batch = make_batch(cfg.vocab_size, 32, 8)

    loss_fn = make_loss_fn(cfg, remat=False)
    (ref_loss, _), ref_g = jax.value_and_grad(loss_fn, has_aux=True)(
        state["params"], batch)

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    pipe_step = make_pipeline_train_step(cfg, grad_opt, n_stages=2,
                                         n_microbatches=4, remat=False,
                                         compress="none")
    with jax.set_mesh(mesh):
        out_state, m = jax.jit(pipe_step)(state, batch)
    print("ref", float(ref_loss), "pipe", float(m["loss"]))
    assert abs(float(ref_loss) - float(m["loss"])) < 1e-4
    pipe_g = jax.tree.map(lambda pn, p0: pn - p0, out_state["params"],
                          state["params"])
    for a, b in zip(jax.tree.leaves(ref_g), jax.tree.leaves(pipe_g)):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=1e-3)
    print("PIPELINE_EQUIV_OK")
""")


@pytest.mark.slow
def test_pipeline_train_step_equals_reference():
    """The GSPMD shifting-buffer pipeline computes the SAME step as the
    plain train step (loss and updated params) on a 2x2x2 mesh."""
    r = subprocess.run([sys.executable, "-c", _PIPELINE_EQUIV],
                       capture_output=True, text=True,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       timeout=600)
    assert "PIPELINE_EQUIV_OK" in r.stdout, r.stdout + r.stderr


_MIXED_EQUIV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.config import ArchConfig, SSMConfig
    from repro.optim.adamw import Optimizer
    from repro.train.steps import make_state, make_loss_fn
    from repro.dist.pipeline import make_pipeline_train_step
    from repro.data import make_batch

    # xlstm-style mixed-kind periodic stack: the per-stage params take the
    # slice-and-stack path, which the homogeneous tests never touch
    cfg = ArchConfig(name="tiny-x", family="ssm", n_layers=6, d_model=64,
                     n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=256,
                     head_dim=16, rope="none", act="gelu", norm="layernorm",
                     block_pattern=("mlstm", "mlstm", "slstm") * 2,
                     ssm=SSMConfig(state_dim=8, chunk=16),
                     compute_dtype="float32", param_dtype="float32",
                     boundary_compression="none")
    grad_opt = Optimizer(init=lambda p: {"z": jnp.zeros(())},
                         update=lambda g, s, p: (g, s))
    state = make_state(cfg, grad_opt, jax.random.PRNGKey(0))
    batch = make_batch(cfg.vocab_size, 32, 8)
    (ref_loss, _), ref_g = jax.value_and_grad(
        make_loss_fn(cfg, remat=False), has_aux=True)(state["params"], batch)

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    pipe_step = make_pipeline_train_step(cfg, grad_opt, n_stages=2,
                                         n_microbatches=4, remat=False,
                                         compress="none")
    with jax.set_mesh(mesh):
        out_state, m = jax.jit(pipe_step)(state, batch)
    print("ref", float(ref_loss), "pipe", float(m["loss"]))
    assert abs(float(ref_loss) - float(m["loss"])) < 1e-4
    pipe_g = jax.tree.map(lambda pn, p0: pn - p0, out_state["params"],
                          state["params"])
    for a, b in zip(jax.tree.leaves(ref_g), jax.tree.leaves(pipe_g)):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=1e-3)
    print("MIXED_EQUIV_OK")
""")


@pytest.mark.slow
def test_pipeline_mixed_kind_equals_reference():
    """Mixed-kind periodic stacks (xlstm-style) must pipeline exactly too:
    guards the per-stage slice-and-stack path, a concatenate sharded
    along its concat dim (``pod``), which older XLA SPMD partitioners
    miscompiled."""
    r = subprocess.run([sys.executable, "-c", _MIXED_EQUIV],
                       capture_output=True, text=True,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       timeout=600)
    assert "MIXED_EQUIV_OK" in r.stdout, r.stdout + r.stderr


_SPAN_MIXED_EQUIV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.config import ArchConfig, SSMConfig
    from repro.runtime.stage_model import (build_span_program,
                                           build_stage_programs,
                                           init_stage_params)
    from repro.data import make_batch

    # mixed-kind periodic stack, 4 stages of (mlstm, slstm): the span
    # [1, 3) covers TWO structurally identical interior stages, so the
    # span builder stacks their param trees and scans over the stage
    # dim — a concatenate sharded along its concat dim (stacked leaves
    # constrained over "pod")
    cfg = ArchConfig(name="tiny-x", family="ssm", n_layers=8, d_model=64,
                     n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=256,
                     head_dim=16, rope="none", act="gelu", norm="layernorm",
                     block_pattern=("mlstm", "slstm") * 4,
                     ssm=SSMConfig(state_dim=8, chunk=16),
                     compute_dtype="float32", param_dtype="float32",
                     boundary_compression="none")
    SEQ = 32
    progs = build_stage_programs(cfg, 4, SEQ)
    params = init_stage_params(progs, jax.random.PRNGKey(0))
    span = build_span_program(cfg, 4, SEQ, (1, 3))
    batch = make_batch(cfg.vocab_size, SEQ, 4)

    x1 = progs[0].fwd(params[0], batch["tokens"])
    # single-device reference: the chained per-stage programs
    x2 = progs[1].fwd(params[1], x1)
    x3_ref = progs[2].fwd(params[2], x2)
    loss_ref, gx3, gp3 = progs[3].bwd(params[3], x3_ref, batch["labels"])
    gx2_ref, gp2 = progs[2].bwd(params[2], x2, gx3)
    gx1_ref, gp1 = progs[1].bwd(params[1], x1, gx2_ref)

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    with jax.set_mesh(mesh):
        x3 = span.fwd(tuple(params[1:3]), x1)
        gx1, gp = span.bwd(tuple(params[1:3]), x1, gx3)
    # a miscompiled sharded concatenate corrupts stage s > 0 of the
    # stack at ~3e-2; legitimate whole-graph fusion noise sits at f32-ulp
    # scale
    np.testing.assert_allclose(np.asarray(x3), np.asarray(x3_ref),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx1_ref),
                               atol=1e-4)
    for a, b in zip(jax.tree.leaves((gp1, gp2)), jax.tree.leaves(gp)):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=1e-3)
    print("SPAN_MIXED_EQUIV_OK")
""")


@pytest.mark.slow
def test_span_program_mixed_kind_equals_reference():
    """The span builder's stack-and-scan path (structurally identical
    interior stages stacked over the leading dim, constrained to "pod")
    must match the chained single-stage programs on a mesh with a real
    pod axis: guards the sharded-concatenate partitioning on the span
    path, the second place per-stage params are stacked over "pod"."""
    r = subprocess.run([sys.executable, "-c", _SPAN_MIXED_EQUIV],
                       capture_output=True, text=True,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       timeout=600)
    assert "SPAN_MIXED_EQUIV_OK" in r.stdout, r.stdout + r.stderr


_INT8_PIPELINE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.config import ArchConfig
    from repro.optim import adamw
    from repro.train.steps import make_train_step, make_state
    from repro.dist.pipeline import make_pipeline_train_step
    from repro.data import make_batch

    cfg = ArchConfig(name="tiny", family="dense", n_layers=4, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                     head_dim=16, compute_dtype="float32",
                     param_dtype="float32")
    opt = adamw(lr=1e-2, grad_clip=0.0)
    state = make_state(cfg, opt, jax.random.PRNGKey(0))
    batch = make_batch(cfg.vocab_size, 32, 8)
    ref_step = jax.jit(make_train_step(cfg, opt, remat=False))
    _, ref_m = ref_step(state, batch)
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    step = make_pipeline_train_step(cfg, opt, 2, 4, remat=False,
                                    compress="int8")
    with jax.set_mesh(mesh):
        _, m = jax.jit(step)(state, batch)
    d = abs(float(ref_m["loss"]) - float(m["loss"]))
    print("loss delta under int8 boundaries:", d)
    assert d < 0.05          # paper App. J: 8-bit barely perturbs
    assert d > 0.0           # but it IS quantized
    print("INT8_PIPE_OK")
""")


@pytest.mark.slow
def test_pipeline_int8_boundary_compression():
    r = subprocess.run([sys.executable, "-c", _INT8_PIPELINE],
                       capture_output=True, text=True,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       timeout=600)
    assert "INT8_PIPE_OK" in r.stdout, r.stdout + r.stderr
