"""Stage-runtime layer: executor protocol, mesh-backed peers, the shared
compile cache, and checkpoint-backed elastic resume.

The tentpole property is heterogeneity (paper §3; Diskin et al.'s pooled
hardware): a swarm mixing single-device (NumericExecutor) and mesh-backed
(MeshExecutor) peers, under churn and with a *learned* boundary codec,
must reproduce the fault-free reference loss trajectory — same tolerance
as tests/test_churn.py.
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
from repro.core import SwarmRunner, SwarmConfig, TraceEvent
from repro.core.sim import Sleep
from repro.launch.mesh import make_peer_mesh
from repro.optim import adamw
from repro.runtime import (MeshExecutor, NumericExecutor, StageExecutor,
                           build_numeric_executors, compile_stats,
                           get_stage_programs, reset_compile_stats)

SEQ, MB, GB, STEPS = 32, 2, 8, 3


def _codec_cfg():
    return tiny_dense_config(boundary_compression="bottleneck",
                             bottleneck_dim=16)


def _reference_losses(cfg, programs, opt, seed, steps=STEPS):
    """Fault-free sequential twin (repro.core.reference_losses)."""
    from repro.core import reference_losses
    return reference_losses(cfg, programs, opt, seed, steps, SEQ, MB, GB)


# ------------------------------------------------- mixed-backend swarm
def test_mixed_mesh_numeric_churn_equals_reference():
    """A churn trace on a heterogeneous swarm — mesh-backed peers at both
    stages next to numeric peers, learned bottleneck codec on — matches
    the fault-free reference trajectory within the churn tolerance."""
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=3, rebalance_period=0.0,
                       codec="bottleneck", max_steps=STEPS)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0,
                         record_accumulation=True)
    runner.build(peers_per_stage=2)
    mesh = make_peer_mesh()
    for s in range(2):
        runner.add_peer(s, executor=MeshExecutor(
            cfg, 2, SEQ, s, mesh, compress="bottleneck"))
    assert any(isinstance(p.executor, MeshExecutor)
               for p in runner.peers.values())
    runner.apply_trace([TraceEvent(0.02, -1), TraceEvent(0.05, -1),
                        TraceEvent(0.25, +1)])
    m = runner.run(until=1e6)
    assert runner.step == STEPS
    assert m["failures"] == 2 and m["joins"] == 1
    # mesh peers actually accumulated gradients (they served, not idled)
    mesh_ids = {p.id for p in runner.peers.values()
                if isinstance(p.executor, MeshExecutor)}
    assert any(kind == "acc" and pid in mesh_ids
               for (kind, *_r, pid) in runner.ledger_log)
    ref = _reference_losses(cfg, runner.programs, opt, seed=0)
    np.testing.assert_allclose(m["loss"], ref, atol=2e-4)
    from test_churn import _assert_exactly_once
    _assert_exactly_once(runner, 2, GB // MB)


def test_mesh_numeric_snapshot_restore_roundtrip():
    """State downloads cross backends: numeric -> mesh -> numeric via the
    executors' snapshot/restore wire format, bitwise."""
    cfg = _codec_cfg()
    execs = build_numeric_executors(cfg, 2, SEQ, compress="bottleneck")
    mesh_ex = MeshExecutor(cfg, 2, SEQ, 0, make_peer_mesh(),
                           compress="bottleneck")
    st = execs[0].init_state(jax.random.PRNGKey(3))
    st.opt = adamw().init(st.params)
    st.version = 7
    snap = execs[0].snapshot(st)
    mesh_st = mesh_ex.init_state(jax.random.PRNGKey(4))
    mesh_ex.restore(mesh_st, snap)
    assert mesh_st.version == 7
    back = mesh_ex.snapshot(mesh_st)
    for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st2 = execs[0].init_state(jax.random.PRNGKey(5))
    execs[0].restore(st2, back)
    for a, b in zip(jax.tree.leaves(st.params), jax.tree.leaves(st2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # grad accumulators come back zeroed: a download never imports grads
    assert all(float(jnp.max(jnp.abs(x))) == 0.0
               for x in jax.tree.leaves(st2.grad_acc))


def test_executors_satisfy_protocol():
    cfg = _codec_cfg()
    num = build_numeric_executors(cfg, 2, SEQ, compress="bottleneck")[0]
    msh = MeshExecutor(cfg, 2, SEQ, 0, make_peer_mesh(),
                       compress="bottleneck")
    assert isinstance(num, StageExecutor)
    assert isinstance(msh, StageExecutor)
    assert num.for_stage(1).stage == 1
    assert msh.for_stage(1).stage == 1 and msh.for_stage(0) is msh


# ------------------------------------------------- shared compile cache
def test_compile_cache_one_trace_per_stage_shape_and_codec():
    """N peers of one stage trigger exactly ONE compile per (stage, kind,
    shape, codec mode) — and a second runner with the same configuration
    re-traces nothing (process-wide cache)."""
    reset_compile_stats()
    cfg = tiny_dense_config()
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=3, rebalance_period=0.0,
                       codec="none", max_steps=1)
    opt = adamw(lr=1e-2, grad_clip=0.0)
    r1 = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0)
    r1.build(peers_per_stage=4)                 # 4 peers x 2 stages
    r1.run(until=1e6)
    st = compile_stats()
    assert st["per_key"], "no traces recorded"
    assert all(v == 1 for v in st["per_key"].values()), st["per_key"]
    # stage 0 traces one fwd and one bwd; the last stage one fwd_save
    # and one bwd_saved, and its recompute bwd once because the seed-0
    # run's interleaved trainers make one last-stage backward miss its
    # forward's residuals: 5 jits total, not peers x stages x 2
    assert st["traces"] == 5, st["per_key"]
    assert sorted(k[-3:-1] for k in st["per_key"]) == [
        (0, "bwd"), (0, "fwd"), (1, "bwd"), (1, "bwd_saved"),
        (1, "fwd_save")]
    r2 = SwarmRunner(cfg, scfg, opt, numeric=True, seed=1)
    r2.build(peers_per_stage=4)
    r2.run(until=1e6)
    assert compile_stats()["traces"] == 5       # zero new traces


def test_codec_mode_is_part_of_the_cache_key():
    cfg = _codec_cfg()
    p_none = get_stage_programs(cfg, 2, SEQ, "none")
    p_btl = get_stage_programs(cfg, 2, SEQ, "bottleneck")
    assert p_none is not p_btl
    assert p_btl is get_stage_programs(cfg, 2, SEQ, "bottleneck")


# ------------------------------------------------- checkpoint resume
def _strand_stage(runner, stage, at):
    yield Sleep(at)
    for p in [p for p in runner.peers.values()
              if p.alive and p.stage == stage]:
        runner._fail_peer(p)


def test_stage_resumes_from_latest_checkpoint(tmp_path):
    """A stage that loses ALL its peers resumes from the latest completed
    step's checkpoint (repro.ckpt via executor snapshot/restore), not the
    step-0 reference — and the loss trajectory continues exactly as
    fault-free training (the checkpoint IS the post-step state)."""
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)
    # 4 steps: kill lands after the early checkpoints, leaving post-kill
    # steps inside the PR 3 churn tolerance (f32 accumulation-order noise
    # compounds through adam beyond that horizon regardless of churn)
    total = STEPS + 1
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=3, rebalance_period=0.0,
                       codec="bottleneck", max_steps=total,
                       ckpt_dir=str(tmp_path))
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0)
    runner.build(peers_per_stage=2)
    # both stage-1 peers die in one instant mid-run; a fresh join later
    # finds no donors and must fall back to the on-disk checkpoint
    t_kill = 0.30
    runner.sim.spawn(_strand_stage(runner, stage=1, at=t_kill))
    runner.apply_trace([TraceEvent(t_kill + 0.2, +1)])
    m = runner.run(until=1e6)
    assert runner.step == total
    assert m["failures"] == 2 and m["joins"] == 1
    # the join restored stage 1 from a completed step > 0
    restores = [r for r in m["ckpt_restores"] if r[0] == 1]
    assert restores, "join did not restore from the checkpoint"
    resumed_step = restores[-1][1]
    assert resumed_step >= 1
    from repro.ckpt import latest_step, stage_dir
    assert latest_step(stage_dir(str(tmp_path), 1)) == total
    # loss continuity: the full trajectory (including the steps AFTER the
    # stage was wiped) equals fault-free training
    ref = _reference_losses(cfg, runner.programs, opt, seed=0, steps=total)
    assert len(m["loss"]) == total
    np.testing.assert_allclose(m["loss"], ref, atol=2e-4)


def test_stale_checkpoint_triggers_global_rollback(tmp_path):
    """ckpt_period=2: a stage stranded one step past the latest
    checkpoint must NOT resume alone from the older step (that would be
    a mixed-version pipeline) — the runner rewinds the whole pipeline to
    the checkpoint, replays the lost steps on the same sample indices,
    and the final trajectory still equals fault-free training."""
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)
    total = 4
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=3, rebalance_period=0.0,
                       codec="bottleneck", max_steps=total,
                       ckpt_dir=str(tmp_path), ckpt_period=2)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0)
    runner.build(peers_per_stage=2)

    def script(r):
        # strand stage 1 right after step 3 completes: latest on-disk
        # checkpoint is step 2 (period 2), one step behind the pipeline
        while (r.step < 3 or r._dispatch_paused) and not r.stopped:
            yield Sleep(0.01)
        if r.stopped:
            return
        for p in [p for p in r.peers.values()
                  if p.alive and p.stage == 1]:
            r._fail_peer(p)
        yield Sleep(0.1)
        yield from r._join_new_peer()

    runner.sim.spawn(script(runner))
    m = runner.run(until=1e6)
    assert runner.step == total
    assert m["rollbacks"] == [(3, 2)], m["rollbacks"]
    # every stage was rewound to step 2 (not just the stranded one)
    assert {s for s, k in m["ckpt_restores"] if k == 2} == {0, 1}
    ref = _reference_losses(cfg, runner.programs, opt, seed=0, steps=total)
    assert len(m["loss"]) == total
    np.testing.assert_allclose(m["loss"], ref, atol=2e-4)


def test_rollback_after_cold_resume_truncates_relative_losses(tmp_path):
    """Rollback inside a RESUMED runner: its loss list starts at the
    resume step, so the rollback must truncate by offset (a bug here
    leaves a duplicate loss entry and desyncs the trajectory)."""
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)

    def make(max_steps, period):
        scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                           global_batch=GB, n_trainers=3,
                           rebalance_period=0.0, codec="bottleneck",
                           max_steps=max_steps, ckpt_dir=str(tmp_path),
                           ckpt_period=period)
        r = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0)
        r.build(peers_per_stage=2)
        return r

    r1 = make(2, 1)
    m1 = r1.run(until=1e6)
    r2 = make(4, 2)                    # resumes at step 2; saves at 4
    assert r2.step == 2

    def script(r):
        # strand stage 1 after step 3: latest cut is still step 2
        while (r.step < 3 or r._dispatch_paused) and not r.stopped:
            yield Sleep(0.01)
        if r.stopped:
            return
        for p in [p for p in r.peers.values()
                  if p.alive and p.stage == 1]:
            r._fail_peer(p)
        yield Sleep(0.1)
        yield from r._join_new_peer()

    r2.sim.spawn(script(r2))
    m2 = r2.run(until=1e6)
    assert r2.step == 4
    assert m2["rollbacks"] == [(3, 2)]
    assert len(m2["loss"]) == 2        # steps 3 and 4, no duplicates
    ref = _reference_losses(cfg, r2.programs, opt, seed=0, steps=4)
    np.testing.assert_allclose(m1["loss"] + m2["loss"], ref, atol=2e-4)


def test_runner_cold_start_resumes_previous_run(tmp_path):
    """A new SwarmRunner constructed over a non-empty ckpt_dir CONTINUES
    that run: step counter and data cursor adopt the latest consistent
    cut, so the combined trajectory equals one uninterrupted run (and
    later saves aren't pruned in favor of the stale older-run ones)."""
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)

    def make(max_steps):
        scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                           global_batch=GB, n_trainers=3,
                           rebalance_period=0.0, codec="bottleneck",
                           max_steps=max_steps, ckpt_dir=str(tmp_path))
        r = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0)
        r.build(peers_per_stage=2)
        return r

    r1 = make(max_steps=2)
    m1 = r1.run(until=1e6)
    assert r1.step == 2
    r2 = make(max_steps=4)          # fresh process stand-in, same dir
    assert r2.step == 2             # adopted the latest cut, not step 0
    m2 = r2.run(until=1e6)
    assert r2.step == 4
    from repro.ckpt import latest_step, stage_dir
    assert latest_step(stage_dir(str(tmp_path), 0)) == 4   # not stale-pruned
    ref = _reference_losses(cfg, r1.programs, opt, seed=0, steps=4)
    np.testing.assert_allclose(m1["loss"] + m2["loss"], ref, atol=2e-4)


def test_without_ckpt_dir_falls_back_to_step0_reference():
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=2, rebalance_period=0.0,
                       codec="bottleneck", max_steps=1)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0)
    runner.build(peers_per_stage=1)
    peer = runner.add_peer(0)
    runner._restore_from_checkpoint(peer, 0)
    assert runner.metrics["ckpt_restores"] == []
    for a, b in zip(jax.tree.leaves(peer.state.params),
                    jax.tree.leaves(runner._ref_params[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_MULTIDEV_MIXED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src"); sys.path.insert(0, "tests")
    import jax, jax.numpy as jnp, numpy as np
    from conftest import tiny_dense_config
    from repro.core import SwarmRunner, SwarmConfig, TraceEvent
    from repro.data.synthetic import SyntheticLM
    from repro.launch.mesh import make_peer_mesh
    from repro.dist.sharding import DEFAULT_RULES, ShardingRules
    from repro.optim.adamw import Optimizer
    from repro.runtime import MeshExecutor, build_numeric_executors

    SEQ, MB, GB, STEPS = 32, 4, 16, 3
    cfg = tiny_dense_config(boundary_compression="bottleneck",
                            bottleneck_dim=16)
    mesh = make_peer_mesh(4)                     # a REAL 4-device slice

    # ---- (1) replicated-rules mesh bwd is BITWISE equal to numeric:
    # the executor plumbing (placement, codec wire, host crossing) adds
    # no numerics of its own.  Microbatch of 2 on 4 devices: 2 % 4 != 0,
    # so the divisibility fallback replicates the batch too — nothing is
    # distributed, hence bitwise is the right bar here
    repl = ShardingRules(rules={k: None for k in DEFAULT_RULES.rules})
    num = build_numeric_executors(cfg, 2, SEQ, compress="bottleneck")
    st_n = [e.init_state(jax.random.PRNGKey(0)) for e in num]
    b = SyntheticLM(cfg.vocab_size, SEQ, 2, seed=17).batch(0)
    w = num[0].wire_fwd(num[0].run_fwd(st_n[0], b["tokens"]))
    loss_n, gx_n, gp_n = num[1].run_bwd(st_n[1], w, labels=b["labels"])
    mex = MeshExecutor(cfg, 2, SEQ, 1, mesh, compress="bottleneck",
                       rules=repl)
    st_m = mex.init_state(jax.random.PRNGKey(9))
    mex.restore(st_m, num[1].snapshot(st_n[1]))
    loss_m, gx_m, gp_m = mex.run_bwd(st_m, w, labels=b["labels"])
    assert float(loss_n) == float(loss_m)
    for a, c in zip(jax.tree.leaves(gp_n), jax.tree.leaves(gp_m)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    # ---- (2) sharded-rules mixed swarm under churn: params FSDP over
    # the peer's data axis, microbatch (4) genuinely split over the 4
    # devices.  Cross-device reduction order makes gradients differ
    # from single-device at f32-noise scale (~1e-5 relative), so the
    # trajectory criterion is loss-scale closeness with plain SGD (no
    # adam sign-normalization, which amplifies bit noise to O(lr))
    lr = 1e-2
    opt = Optimizer(init=lambda p: {"n": jnp.zeros(())},
                    update=lambda g, s, p: (
                        jax.tree.map(lambda x: -lr * x, g), s))
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=3, rebalance_period=0.0,
                       codec="bottleneck", max_steps=STEPS)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0)
    runner.build(peers_per_stage=1)
    for s in range(2):
        ex = MeshExecutor(cfg, 2, SEQ, s, mesh, compress="bottleneck")
        assert ex.device_count == 4
        runner.add_peer(s, executor=ex)
    runner.apply_trace([TraceEvent(0.05, -1)])   # churn on top
    m = runner.run(until=1e6)
    assert runner.step == STEPS

    from repro.core import reference_losses
    losses = reference_losses(cfg, runner.programs, opt, 0, STEPS,
                              SEQ, MB, GB)
    assert max(losses) - min(losses) > 1e-3      # params actually move
    np.testing.assert_allclose(m["loss"], losses, atol=2e-3)
    print("MULTIDEV_MIXED_OK", m["loss"])
""")


@pytest.mark.slow
def test_mixed_swarm_with_real_multidevice_mesh_peer():
    """Subprocess (needs its own XLA device-count override): peers backed
    by a genuine 4-device mesh, mixed with single-device peers and churn.
    Asserts (1) bitwise executor equivalence under replicated placement
    and (2) trajectory closeness under real FSDP sharding + split batch."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _MULTIDEV_MIXED],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "MULTIDEV_MIXED_OK" in r.stdout


# ------------------------------------------------- swarm-level fairness
def test_faster_peer_receives_proportionally_more_microbatches():
    """Alg. 1 end-to-end: with one 2x-faster device serving the same
    stage, the wiring routes it ~2x the microbatches (loose bound: the
    sim adds network time on top of compute)."""
    from repro.core.peer import DeviceProfile, MBPS
    # slow enough that compute (not network latency) dominates response
    # time — the regime where IWRR's throughput-weighting shows
    slow = DeviceProfile("slow", 2e9, 800 * MBPS, 800 * MBPS, 1e-4)
    fast = DeviceProfile("fast", 4e9, 800 * MBPS, 800 * MBPS, 1e-4)
    cfg = tiny_dense_config(n_layers=2)
    scfg = SwarmConfig(n_stages=1, microbatch_size=1, seq_len=512,
                       global_batch=64, n_trainers=4, rebalance_period=0.0,
                       codec="none", max_steps=6)
    r = SwarmRunner(cfg, scfg, adamw(), numeric=False, seed=0,
                    profile_fn=lambda i: (fast, slow)[i % 2],
                    record_accumulation=True)
    r.build(peers_per_stage=2)
    r.run(until=1e6)
    counts = {}
    for kind, _step, _s, _i, _a, pid in r.ledger_log:
        if kind == "acc":
            counts[pid] = counts.get(pid, 0) + 1
    by_profile = {p.id: p.profile.name for p in r.peers.values()}
    n_fast = sum(c for pid, c in counts.items()
                 if by_profile[pid] == "fast")
    n_slow = sum(c for pid, c in counts.items()
                 if by_profile[pid] == "slow")
    assert n_slow > 0
    ratio = n_fast / n_slow
    assert 1.5 <= ratio <= 2.8, (n_fast, n_slow, ratio)
