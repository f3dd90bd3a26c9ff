"""Async stage execution (ISSUE 7): in-flight boundary transfers +
dispatch/collect + bounded-staleness All-Reduce.

The load-bearing properties:

* **delay=0 is bitwise** — turning overlap on changes WHEN boundary
  bytes move (they occupy the NIC links, not the compute queue), never
  WHAT is computed: with deterministic routing (one trainer, one peer
  per stage slot) the loss trajectory is float-for-float identical to
  the blocking tick, on the numeric, mesh, span, and mesh-span
  backends alike;
* **delay=1 is DPU** — a ``staleness=1`` runner (which wraps its
  optimizer in delayed parameter updates internally) reproduces the
  sequential DPU reference exactly (ATOM-style staleness accounting,
  paper §3.2);
* **churn equivalence survives overlap** — the test_churn trace
  (failures + warm join + forced migration) on an async swarm still
  matches the fault-free DPU reference at 2e-4, exactly-once accounted;
* **mesh spans** — ``MeshExecutor.for_span`` with width > 1 yields a
  device-placed span executor whose snapshots interop with single-stage
  executors and whose mixed-swarm trajectory matches the reference;
* **overlap never loses** — the rebalancer prices an overlapped edge at
  ``max(compute, wire)`` <= ``compute + wire`` serial.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import tiny_dense_config
from repro.core import SwarmRunner, SwarmConfig, TraceEvent, \
    reference_losses
from repro.core.rebalance import pipeline_throughput
from repro.launch.mesh import make_peer_mesh
from repro.optim import adamw, delayed_parameter_updates
from repro.runtime import (MeshExecutor, MeshSpanExecutor,
                           PipelineExecutor, build_stage_programs)
from test_churn import _assert_exactly_once, _force_migration

SEQ, MB, GB, STEPS = 32, 2, 8, 3

BACKENDS = ("numeric", "mesh", "span", "mesh_span")


def _scfg(**kw):
    # one trainer: deterministic microbatch routing, so sync and async
    # runs see the identical (peer, sample) schedule — the precondition
    # for bitwise comparison (multi-trainer closeness is the churn test)
    base = dict(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                global_batch=GB, n_trainers=1, rebalance_period=0.0,
                codec="none", max_steps=STEPS)
    base.update(kw)
    return SwarmConfig(**base)


def _run(backend, seed, **scfg_kw):
    cfg = tiny_dense_config()
    r = SwarmRunner(cfg, _scfg(**scfg_kw), adamw(lr=1e-2, grad_clip=0.0),
                    numeric=True, seed=seed)
    if backend == "numeric":
        r.build(peers_per_stage=1)
    elif backend == "mesh":
        mesh = make_peer_mesh()
        for s in range(2):
            r.add_peer(s, executor=MeshExecutor(cfg, 2, SEQ, s, mesh))
        r.build(peers_per_stage=0)
    elif backend == "span":
        r.add_peer(range(0, 2), executor=PipelineExecutor(
            cfg, 2, SEQ, (0, 2)))
        r.build(peers_per_stage=0)
    else:                                    # mesh_span: for_span width 2
        base = MeshExecutor(cfg, 2, SEQ, 0, make_peer_mesh())
        r.add_peer(range(0, 2), executor=base.for_span(range(0, 2)))
        r.build(peers_per_stage=0)
    m = r.run(until=1e6)
    assert r.step == STEPS
    return r, m


# ------------------------------------------------- delay=0: bitwise
@pytest.mark.parametrize("backend", BACKENDS)
def test_overlap_delay0_bitwise_equals_sync(backend):
    """overlap=True, staleness=0 reorders only the virtual clock: the
    loss floats are IDENTICAL to the blocking tick on every backend."""
    _, sync = _run(backend, seed=0)
    ra, asy = _run(backend, seed=0, overlap=True)
    assert asy["loss"] == sync["loss"], (backend, asy["loss"], sync["loss"])
    # and the async run genuinely put boundary bytes in flight
    assert asy["inflight_bytes"] > 0
    assert asy["overlap_fraction"] >= 0
    if backend in ("numeric", "mesh"):
        # a whole-pipe span peer has no peer-to-peer edge to hide, so a
        # positive hidden fraction is only guaranteed with >= 2 peers
        assert asy["overlap_fraction"] > 0
    assert all(v >= 0.0 for v in asy["peer_idle_s"].values())


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10_000))
def test_overlap_delay0_bitwise_property(seed):
    """Hypothesis sweep of the bitwise property over init seeds."""
    seed %= 997
    _, sync = _run("numeric", seed=seed)
    _, asy = _run("numeric", seed=seed, overlap=True)
    assert asy["loss"] == sync["loss"]


def test_overlap_finishes_no_later_than_sync():
    """Hiding wire behind compute can only shrink the virtual makespan."""
    rs, _ = _run("numeric", seed=0)
    ra, _ = _run("numeric", seed=0, overlap=True)
    assert ra.sim.now <= rs.sim.now + 1e-9, (ra.sim.now, rs.sim.now)


# ------------------------------------------------- delay=1: DPU
def test_staleness1_equals_sequential_dpu_reference():
    """A staleness=1 runner wraps its optimizer in DPU internally; its
    trajectory equals the sequential reference driven by an explicitly
    DPU-wrapped optimizer — staleness accounting is exact, not lossy."""
    cfg = tiny_dense_config()
    _, m = _run("numeric", seed=0, overlap=True, staleness=1)
    programs = build_stage_programs(cfg, 2, SEQ)
    ref_opt = delayed_parameter_updates(adamw(lr=1e-2, grad_clip=0.0),
                                        delay=1)
    ref = reference_losses(cfg, programs, ref_opt, 0, STEPS, SEQ, MB, GB)
    np.testing.assert_array_equal(m["loss"], ref)


def test_dpu_flag_implies_staleness():
    scfg = _scfg(dpu=True)
    assert scfg.staleness == 1
    with pytest.raises(ValueError):
        _scfg(staleness=-1)


# ------------------------------------------------- churn equivalence
@pytest.mark.parametrize("seed", [0, 1])
def test_async_churn_equals_dpu_reference(seed):
    """The test_churn trace (2 failures, a warm join, a forced
    migration) on an OVERLAPPED, staleness=1 swarm still reproduces the
    fault-free sequential DPU trajectory at 2e-4 — the exactly-once
    ledger is oblivious to transfers being in flight."""
    cfg = tiny_dense_config()
    programs = build_stage_programs(cfg, 2, SEQ)
    opt = adamw(lr=1e-2, grad_clip=0.0)
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=3, rebalance_period=0.0,
                       codec="none", max_steps=STEPS, overlap=True,
                       staleness=1)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=seed,
                         programs=programs, record_accumulation=True)
    runner.build(peers_per_stage=3)
    runner.apply_trace([TraceEvent(0.01 + 0.01 * seed, -1),
                        TraceEvent(0.05, -1),
                        TraceEvent(0.22, +1)])
    runner.sim.spawn(_force_migration(runner, at=0.12))
    m = runner.run(until=1e6)
    assert runner.step == STEPS
    assert m["failures"] == 2 and m["joins"] == 1
    ref = reference_losses(
        cfg, programs, delayed_parameter_updates(opt, delay=1), seed,
        STEPS, SEQ, MB, GB)
    np.testing.assert_allclose(m["loss"], ref, atol=2e-4)
    _assert_exactly_once(runner, 2, GB // MB)


# ------------------------------------------------- mesh spans (width > 1)
def test_mesh_for_span_widths():
    cfg = tiny_dense_config()
    mex = MeshExecutor(cfg, 2, SEQ, 0, make_peer_mesh())
    wide = mex.for_span(range(0, 2))
    assert isinstance(wide, MeshSpanExecutor)
    assert wide.stages == range(0, 2)
    assert wide.for_span(range(0, 2)) is wide
    narrow = wide.for_span(range(1, 2))
    assert isinstance(narrow, MeshExecutor) and narrow.stage == 1
    assert mex.for_span(range(0, 1)) is mex


def test_mesh_span_snapshot_interop_with_singles():
    """Per-stage snapshots cross MeshSpanExecutor <-> single-stage
    executors bitwise, and the whole-state snapshot round-trips."""
    from repro.runtime import build_numeric_executors
    cfg = tiny_dense_config()
    num = build_numeric_executors(cfg, 2, SEQ)
    mspan = MeshExecutor(cfg, 2, SEQ, 0,
                         make_peer_mesh()).for_span(range(0, 2))
    sts = [e.init_state(jax.random.PRNGKey(3)) for e in num]
    for st_ in sts:
        st_.opt = adamw().init(st_.params)
        st_.version = 5
    pst = mspan.init_state(jax.random.PRNGKey(4))
    for s in range(2):
        mspan.restore(pst, num[s].snapshot(sts[s]), stage=s)
    assert pst.stage_view(0).version == 5
    for s in range(2):
        back = mspan.snapshot(pst, stage=s)
        st2 = num[s].init_state(jax.random.PRNGKey(9))
        num[s].restore(st2, back)
        for a, b in zip(jax.tree.leaves(st2.params),
                        jax.tree.leaves(sts[s].params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert all(float(jnp.max(jnp.abs(x))) == 0.0
                   for x in jax.tree.leaves(st2.grad_acc))
    whole = mspan.snapshot(pst)
    pst2 = mspan.init_state(jax.random.PRNGKey(11))
    mspan.restore(pst2, whole)
    for s in range(2):
        for a, b in zip(jax.tree.leaves(pst2.stage_view(s).params),
                        jax.tree.leaves(sts[s].params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mesh_span_in_mixed_swarm_equals_reference():
    """A MeshExecutor.for_span(width=2) peer next to single-stage numeric
    peers, under the async tick, matches the fault-free reference."""
    cfg = tiny_dense_config()
    opt = adamw(lr=1e-2, grad_clip=0.0)
    scfg = _scfg(n_trainers=3, overlap=True)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0,
                         record_accumulation=True)
    runner.build(peers_per_stage=2)
    base = MeshExecutor(cfg, 2, SEQ, 0, make_peer_mesh())
    span_peer = runner.add_peer(range(0, 2),
                                executor=base.for_span(range(0, 2)))
    m = runner.run(until=1e6)
    assert runner.step == STEPS
    span_accs = {s for (k, _t, s, _i, _a, pid) in runner.ledger_log
                 if k == "acc" and pid == span_peer.id}
    assert span_accs == {0, 1}, span_accs
    ref = reference_losses(cfg, runner.programs, opt, 0, STEPS, SEQ,
                           MB, GB)
    np.testing.assert_allclose(m["loss"], ref, atol=2e-4)
    _assert_exactly_once(runner, 2, GB // MB)


# ------------------------------------------------- rebalance pricing
def test_rebalance_prices_overlapped_wire():
    """max(compute, wire) per span edge: overlapped throughput dominates
    serial, and they coincide exactly when the wire is free."""
    spans = [(0, 2), (2, 3)]
    costs = [1.0, 1.0, 1.0]
    serial = pipeline_throughput(spans, 1.0, stage_costs=costs,
                                 boundary_cost=0.5)
    overlapped = pipeline_throughput(spans, 1.0, stage_costs=costs,
                                     boundary_cost=0.5, overlap_wire=True)
    assert overlapped > serial
    for bc in (0.0, 0.25, 1.0, 4.0):
        s = pipeline_throughput(spans, 1.0, stage_costs=costs,
                                boundary_cost=bc)
        o = pipeline_throughput(spans, 1.0, stage_costs=costs,
                                boundary_cost=bc, overlap_wire=True)
        assert o >= s
        if bc == 0.0:
            assert o == s
