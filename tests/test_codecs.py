"""Learned boundary codecs (paper App. J) end-to-end on both execution
paths, and the honest compression cost model.

Multi-device pipeline cases run in a subprocess so the main test process
keeps the single-device view (same pattern as tests/test_distribution.py).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense_config
from repro.compression import codecs
from repro.compression.quant8 import BLOCK, compressed_bytes
from repro.core import SwarmRunner, SwarmConfig
from repro.runtime import build_stage_programs, init_stage_params
from repro.models import flops as F
from repro.optim import adamw


# ------------------------------------------------------------ cost model
def test_boundary_bytes_int8_delegates_to_quant8():
    """One source of truth: the analytic int8 wire size must equal
    quant8.compressed_bytes exactly — including ceil-divided partial
    blocks, which the old ``n + 4 * n/64`` formula got wrong."""
    cfg = tiny_dense_config(d_model=100)      # 100 * 3 * 7 % BLOCK != 0
    x = jnp.zeros((3, 7, 100))
    assert F.boundary_bytes(cfg, 3, 7, "int8") == compressed_bytes(x)
    assert (3 * 7 * 100) % BLOCK != 0         # the padding case is hit


def test_boundary_bytes_real_codec_ratio():
    """Learned-codec bytes follow cfg.bottleneck_dim / maxout k, not a
    hardcoded 2x."""
    cfg = tiny_dense_config(bottleneck_dim=16)           # 64 -> 16: 4x
    assert F.boundary_bytes(cfg, 2, 8, "none") == 2 * 8 * 64 * 2
    assert F.boundary_bytes(cfg, 2, 8, "bottleneck") == 2 * 8 * 16 * 2
    cfg4 = tiny_dense_config(maxout_k=4)                 # 64 -> 16: 4x
    assert F.boundary_bytes(cfg4, 2, 8, "maxout") == 2 * 8 * 16 * 2
    # changing the config changes the bytes (the old bug: it didn't)
    wide = tiny_dense_config(bottleneck_dim=32)
    assert (F.boundary_bytes(wide, 2, 8, "bottleneck")
            == 2 * F.boundary_bytes(cfg, 2, 8, "bottleneck"))


def test_swarm_boundary_nbytes_matches_flops():
    """The sim charges exactly the analytic per-mode wire bytes."""
    cfg = tiny_dense_config(bottleneck_dim=16, maxout_k=4)
    for mode in codecs.MODES:
        scfg = SwarmConfig(n_stages=2, seq_len=32, codec=mode)
        r = SwarmRunner(cfg, scfg, adamw(), numeric=False)
        mb = r.next_microbatch()
        assert r.boundary_nbytes(mb) == F.boundary_bytes(
            cfg, mb.size, 32, mode)
    # booleans keep their historical meaning
    r = SwarmRunner(cfg, SwarmConfig(n_stages=2, seq_len=32, codec="int8"),
                    adamw(), numeric=False)
    assert r.compress_mode == "int8"


def test_baselines_see_codec_wire_bytes():
    """Fewer boundary bytes -> strictly higher pipeline throughput in the
    baseline cost model (the fixed formula propagates)."""
    from repro.core.baselines import gpipe
    from repro.core.peer import T4
    cfg = tiny_dense_config(bottleneck_dim=8)
    thr = {m: gpipe(cfg, T4, seq=512, compress=m).throughput
           for m in ("none", "bottleneck")}
    assert thr["bottleneck"] > thr["none"]


# ------------------------------------------------------------ elastic path
def test_elastic_codec_wire_shape_and_gradient_flow():
    """Stage programs emit the c-dim wire tensor, and w_c/w_d receive
    nonzero gradients through one fwd+bwd chain."""
    cfg = tiny_dense_config(bottleneck_dim=16, maxout_k=4)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 256)
    for mode in ("bottleneck", "maxout"):
        progs = build_stage_programs(cfg, 2, 32, compress=mode)
        params = init_stage_params(progs, jax.random.PRNGKey(0))
        y = progs[0].fwd(params[0], tokens)
        assert y.shape[-1] == codecs.wire_dim(cfg, mode) == 16
        loss, gx, gp1 = progs[1].bwd(params[1], y, labels)
        _, gp0 = progs[0].bwd(params[0], tokens, gx)
        assert np.isfinite(float(loss))
        assert gx.shape == y.shape          # backward wire is c-dim too
        assert float(jnp.max(jnp.abs(gp1["boundary"]["w_d"]))) > 0
        if mode == "bottleneck":
            assert float(jnp.max(jnp.abs(gp0["boundary"]["w_c"]))) > 0
        else:
            assert "boundary" not in params[0]   # maxout sender: param-free


def test_swarm_trains_with_learned_codecs():
    """Full elastic system: learned codecs train end-to-end and the
    optimizer updates the codec params (one step on the elastic path)."""
    cfg = tiny_dense_config(n_layers=2, bottleneck_dim=16)
    for mode in ("bottleneck", "maxout"):
        scfg = SwarmConfig(n_stages=2, microbatch_size=2, seq_len=32,
                           global_batch=4, n_trainers=2,
                           rebalance_period=0.0, codec=mode, max_steps=2)
        r = SwarmRunner(cfg, scfg, adamw(lr=1e-2, grad_clip=0.0),
                        numeric=True, seed=0)
        r.build(peers_per_stage=1)
        recv = next(p for p in r.peers.values() if p.stage == 1)
        w0 = np.asarray(recv.state.params["boundary"]["w_d"]).copy()
        m = r.run(until=1e6)
        assert len(m["loss"]) == 2 and all(np.isfinite(m["loss"]))
        w1 = np.asarray(recv.state.params["boundary"]["w_d"])
        assert np.abs(w1 - w0).max() > 0     # codec params were updated


def _reference_losses(cfg, opt, programs, n_steps, seq, mb, gb, seed=0,
                      data_seed=17):
    """Sequential twin of the elastic run: same stage programs (codec
    included), same data order, same token-weighted averaging."""
    from repro.data.synthetic import SyntheticLM
    params = init_stage_params(programs, jax.random.PRNGKey(seed))
    opt_states = [opt.init(p) for p in params]
    ds = SyntheticLM(cfg.vocab_size, seq, mb, seed=data_seed)
    idx, losses = 0, []
    for _ in range(n_steps):
        grads = [jax.tree.map(jnp.zeros_like, p) for p in params]
        loss_sum, tok = 0.0, 0
        for _ in range(gb // mb):
            b = ds.batch(idx)
            idx += 1
            x = programs[0].fwd(params[0], b["tokens"])
            loss, gx, gp1 = programs[1].bwd(params[1], x, b["labels"])
            _, gp0 = programs[0].bwd(params[0], b["tokens"], gx)
            grads[0] = jax.tree.map(jnp.add, grads[0], gp0)
            grads[1] = jax.tree.map(jnp.add, grads[1], gp1)
            loss_sum += float(loss)
            tok += mb * seq
        losses.append(loss_sum / tok)
        for s in range(2):
            gm = jax.tree.map(lambda g: g / tok, grads[s])
            upd, opt_states[s] = opt.update(gm, opt_states[s], params[s])
            params[s] = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                     params[s], upd)
    return losses


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["bottleneck", "maxout"])
def test_elastic_codec_equals_reference(mode):
    """App. E equivalence holds under learned codecs: the stochastic
    elastic run reproduces the sequential reference loss trajectory."""
    cfg = tiny_dense_config(bottleneck_dim=16, maxout_k=4)
    opt = adamw(lr=1e-2, grad_clip=0.0)
    scfg = SwarmConfig(n_stages=2, microbatch_size=2, seq_len=32,
                       global_batch=8, n_trainers=3, rebalance_period=0.0,
                       codec=mode, max_steps=3)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0)
    runner.build(peers_per_stage=2)
    metrics = runner.run(until=1e6)
    programs = build_stage_programs(cfg, 2, 32, compress=mode)
    ref = _reference_losses(cfg, opt, programs, 3, 32, 2, 8)
    assert len(metrics["loss"]) == 3
    np.testing.assert_allclose(metrics["loss"], ref, atol=2e-4)


# ------------------------------------------------------------ GSPMD path
_CODEC_PIPELINE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.config import ArchConfig
    from repro.optim.adamw import Optimizer
    from repro.train.steps import make_state
    from repro.dist.pipeline import (make_pipeline_train_step,
                                     make_reference_loss_fn)
    from repro.data import make_batch

    MODE = {mode!r}
    cfg = ArchConfig(name="tiny", family="dense", n_layers=4, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                     head_dim=16, compute_dtype="float32",
                     param_dtype="float32", boundary_compression=MODE,
                     bottleneck_dim=16, maxout_k=4, pipeline_stages=2)
    grad_opt = Optimizer(init=lambda p: {{"z": jnp.zeros(())}},
                         update=lambda g, s, p: (g, s))
    state = make_state(cfg, grad_opt, jax.random.PRNGKey(0))
    assert "boundary" in state["params"]
    batch = make_batch(cfg.vocab_size, 32, 8)

    # staged sequential reference: SAME codec roundtrip per boundary, no
    # pipeline machinery (see dist/pipeline.py::make_reference_loss_fn)
    ref_fn = make_reference_loss_fn(cfg, 2, 4)
    (ref_loss, _), ref_g = jax.value_and_grad(ref_fn, has_aux=True)(
        state["params"], batch)

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    pipe_step = make_pipeline_train_step(cfg, grad_opt, n_stages=2,
                                         n_microbatches=4, remat=False)
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
    # gradient-flow: w_c/w_d receive nonzero grads after one step
    for k, g in pipe_g["boundary"].items():
        assert float(jnp.max(jnp.abs(g))) > 0, k
    print("CODEC_PIPE_OK")
""")


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["bottleneck", "maxout"])
def test_pipeline_codec_equals_staged_reference(mode):
    """The GSPMD pipeline with a learned codec computes the SAME step as
    the sequential staged reference on a 2x2x2 mesh — loss, layer grads,
    and nonzero codec grads (the wire buffer carries the c-dim tensor)."""
    r = subprocess.run([sys.executable, "-c",
                        _CODEC_PIPELINE.format(mode=mode)],
                       capture_output=True, text=True,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       timeout=600)
    assert "CODEC_PIPE_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["bottleneck", "maxout"])
def test_pipeline_codec_training_trajectory(mode):
    """Acceptance: the pipelined step trains end-to-end with a real
    optimizer and tracks the staged reference loss trajectory within the
    suite's compression tolerance (per-step math is exact — see
    test_pipeline_codec_equals_staged_reference; adamw amplifies f32
    reduction noise to O(lr), hence the loose bound here)."""
    from repro.data import make_batch
    from repro.dist.pipeline import (make_pipeline_train_step,
                                     make_reference_loss_fn)
    from repro.train.steps import make_state
    cfg = tiny_dense_config(boundary_compression=mode, bottleneck_dim=16,
                            maxout_k=4, pipeline_stages=2)
    opt = adamw(lr=1e-2, grad_clip=0.0)
    state_p = make_state(cfg, opt, jax.random.PRNGKey(0))
    state_r = jax.tree.map(lambda x: x, state_p)
    pipe = jax.jit(make_pipeline_train_step(cfg, opt, 2, 4, remat=False))
    ref_fn = make_reference_loss_fn(cfg, 2, 4)

    @jax.jit
    def ref_step(state, batch):
        (loss, _), g = jax.value_and_grad(ref_fn, has_aux=True)(
            state["params"], batch)
        upd, o = opt.update(g, state["opt"], state["params"])
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                              state["params"], upd)
        return {"params": params, "opt": o,
                "step": state["step"] + 1}, loss

    traj_p, traj_r = [], []
    for i in range(4):
        batch = make_batch(cfg.vocab_size, 32, 8, seed=i)
        state_p, m = pipe(state_p, batch)
        state_r, rl = ref_step(state_r, batch)
        traj_p.append(float(m["loss"]))
        traj_r.append(float(rl))
    np.testing.assert_allclose(traj_p, traj_r, atol=0.05)
    assert traj_p[-1] < traj_p[0]        # it actually learns


def test_pipeline_learned_codec_requires_declared_stages():
    """Clear error when the config doesn't carry the codec params."""
    from repro.dist.pipeline import make_pipeline_train_step
    cfg = tiny_dense_config(boundary_compression="bottleneck",
                            bottleneck_dim=16)    # pipeline_stages unset
    with pytest.raises(ValueError, match="pipeline_stages"):
        make_pipeline_train_step(cfg, adamw(), n_stages=2, n_microbatches=4)
