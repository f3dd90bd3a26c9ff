"""Span-aware swarms: the PipelineExecutor backend (ISSUE 5 tentpole).

A peer may serve a contiguous span of stages [lo, hi) fused in one jit
(square-cube, paper §3.1; Varuna's stage fusion).  The load-bearing
properties:

* **churn equivalence** — a swarm mixing span peers with single-stage
  peers, learned bottleneck codec on, reproduces the all-single-stage
  fault-free reference trajectory at 2e-4 (the acceptance criterion),
  including a mid-run span SPLIT into single-stage peers and a MERGE
  back (Varuna-style re-partitioning);
* **exactly-once over spans** — a span peer holds one ledger row per
  covered stage; a re-issued attempt after a span-peer kill folds only
  the stages whose gradients died, skipping survivors;
* **state interop** — span ↔ single hand-offs move ordinary
  single-stage snapshots, so checkpoint cuts and peer downloads are
  span-agnostic;
* **compile accounting** — one fwd + one bwd jit per (span, codec)
  process-wide; wire codecs (int8) apply at span edges only.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense_config
from repro.core import SwarmRunner, SwarmConfig, TraceEvent, \
    reference_losses
from repro.core.sim import Sleep
from repro.optim import adamw
from repro.runtime import (PipelineExecutor, StageExecutor,
                           build_numeric_executors, compile_stats,
                           get_span_program, reset_compile_stats)
from test_churn import _assert_exactly_once

SEQ, MB, GB, STEPS = 32, 2, 8, 3


def _codec_cfg():
    return tiny_dense_config(boundary_compression="bottleneck",
                             bottleneck_dim=16)


def _scfg(n_stages, max_steps=STEPS, **kw):
    return SwarmConfig(n_stages=n_stages, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=3, rebalance_period=0.0,
                       codec="bottleneck", max_steps=max_steps, **kw)


def _span_peer(runner, lo, hi):
    cfg, n = runner.cfg, runner.n_stages
    return runner.add_peer(range(lo, hi), executor=PipelineExecutor(
        cfg, n, SEQ, (lo, hi), compress="bottleneck"))


# --------------------------------------------------- mixed-swarm churn
def test_span_peer_in_mixed_swarm_equals_reference():
    """ISSUE 5 acceptance: a peer serving stages [0, 2) via
    PipelineExecutor in a mixed swarm (single-stage peers at both
    stages), learned codec on, under churn, matches the all-single-stage
    reference trajectory at 2e-4 — and is exactly-once accounted."""
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)
    runner = SwarmRunner(cfg, _scfg(2), opt, numeric=True, seed=0,
                         record_accumulation=True)
    runner.build(peers_per_stage=2)
    span_peer = _span_peer(runner, 0, 2)
    runner.apply_trace([TraceEvent(0.02, -1), TraceEvent(0.25, +1)])
    m = runner.run(until=1e6)
    assert runner.step == STEPS
    assert m["failures"] == 1 and m["joins"] == 1
    # the span peer genuinely served (accumulated under BOTH stages)
    span_accs = {s for (k, _t, s, _i, _a, pid) in runner.ledger_log
                 if k == "acc" and pid == span_peer.id}
    assert span_accs == {0, 1}, span_accs
    ref = reference_losses(cfg, runner.programs, opt, 0, STEPS, SEQ, MB, GB)
    np.testing.assert_allclose(m["loss"], ref, atol=2e-4)
    _assert_exactly_once(runner, 2, GB // MB)


def test_span_split_and_merge_equals_reference():
    """Satellite 1: a 2-peer swarm — spans [0, 2) and [2, 4) over a
    4-stage pipeline — reproduces the 4x-single-stage fault-free
    reference at 2e-4 with the bottleneck codec on, through a mid-run
    migration that SPLITS the first span into two single-stage peers
    and a MERGE back."""
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)
    runner = SwarmRunner(cfg, _scfg(4), opt, numeric=True, seed=0,
                         record_accumulation=True)
    A = _span_peer(runner, 0, 2)
    B = _span_peer(runner, 2, 4)
    runner.build(peers_per_stage=0)          # trainers only

    def script(r):
        yield Sleep(0.10)
        # split: a fresh peer warm-joins on [1, 2) (downloading stage 1
        # FROM the span peer), then A shrinks to [0, 1)
        yield from r.split_span(A, at=1)
        assert A.stages == range(0, 1), A.stages
        yield Sleep(0.10)
        C = next(p for p in r.peers.values()
                 if p.alive and p.serving and p.stages == range(1, 2))
        # merge back: A re-absorbs stage 1 (downloading it from C)
        yield from r.merge_spans(A, range(0, 2))
        assert A.stages == range(0, 2), A.stages
        # C leaving afterwards is safe — A covers stage 1 again
        r._fail_peer(C)

    runner.sim.spawn(script(runner))
    m = runner.run(until=1e6)
    assert runner.step == STEPS
    assert m["span_changes"] == 2 and m["joins"] == 1
    assert m["failures"] == 1
    ref = reference_losses(cfg, runner.programs, opt, 0, STEPS, SEQ, MB, GB)
    np.testing.assert_allclose(m["loss"], ref, atol=2e-4)
    _assert_exactly_once(runner, 4, GB // MB)


def test_span_peer_killed_midrun_recovers():
    """A dying span peer releases one ledger row per covered stage; its
    stages' state survives on the other peers (or re-joins via the span
    hand-off path) and the trajectory still matches the reference."""
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)
    runner = SwarmRunner(cfg, _scfg(2), opt, numeric=True, seed=0,
                         record_accumulation=True)
    runner.build(peers_per_stage=1)          # singles keep coverage
    span_peer = _span_peer(runner, 0, 2)

    def script(r):
        yield Sleep(0.06)
        r._fail_peer(span_peer)

    runner.sim.spawn(script(runner))
    m = runner.run(until=1e6)
    assert runner.step == STEPS and m["failures"] == 1
    rel = {(s, i) for (k, _t, s, i, _a, pid) in runner.ledger_log
           if k == "rel" and pid == span_peer.id}
    if rel:                                 # it held grads when it died
        assert {s for s, _ in rel} <= {0, 1}
    ref = reference_losses(cfg, runner.programs, opt, 0, STEPS, SEQ, MB, GB)
    np.testing.assert_allclose(m["loss"], ref, atol=2e-4)
    _assert_exactly_once(runner, 2, GB // MB)


# --------------------------------------------------- wire accounting
def test_span_swarm_moves_fewer_host_bytes():
    """All-span peers vs all-single peers on the same seed: identical
    loss trajectory, strictly fewer (here: zero) boundary bytes through
    the host — the saved bytes the square-cube rebalancing buys."""
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)

    def run(span: bool):
        r = SwarmRunner(cfg, _scfg(2), opt, numeric=True, seed=0)
        if span:
            _span_peer(r, 0, 2)
            _span_peer(r, 0, 2)
            r.build(peers_per_stage=0)
        else:
            r.build(peers_per_stage=2)
        m = r.run(until=1e6)
        assert r.step == STEPS
        return m

    single = run(span=False)
    span = run(span=True)
    np.testing.assert_allclose(span["loss"], single["loss"], atol=2e-4)
    assert span["wire_bytes"] == 0.0
    assert single["wire_bytes"] > 0.0


# --------------------------------------------------- protocol / interop
def test_span_executor_protocol_and_for_span():
    cfg = _codec_cfg()
    pex = PipelineExecutor(cfg, 4, SEQ, (1, 3), compress="bottleneck")
    assert isinstance(pex, StageExecutor)
    assert pex.stages == range(1, 3) and pex.stage == 1
    assert pex.for_span(range(1, 3)) is pex
    assert pex.for_span(range(2, 3)).stages == range(2, 3)
    assert pex.for_stage(0).stages == range(0, 1)
    wide = pex.for_span(range(0, 4))
    assert isinstance(wide, PipelineExecutor)
    num = build_numeric_executors(cfg, 4, SEQ, compress="bottleneck")[0]
    assert num.for_span(range(0, 1)) is num
    grown = num.for_span(range(0, 2))
    assert isinstance(grown, PipelineExecutor)
    assert grown.stages == range(0, 2)


def test_span_snapshot_restore_interop_with_singles():
    """Per-stage snapshots cross span <-> single executors bitwise, and a
    span's whole-state snapshot round-trips."""
    cfg = _codec_cfg()
    num = build_numeric_executors(cfg, 2, SEQ, compress="bottleneck")
    pex = PipelineExecutor(cfg, 2, SEQ, (0, 2), compress="bottleneck")
    sts = [e.init_state(jax.random.PRNGKey(3)) for e in num]
    for st in sts:
        st.opt = adamw().init(st.params)
        st.version = 5
    pst = pex.init_state(jax.random.PRNGKey(4))
    for s in range(2):
        pex.restore(pst, num[s].snapshot(sts[s]), stage=s)
    assert pst.stage_view(0).version == 5
    for s in range(2):
        back = pex.snapshot(pst, stage=s)
        st2 = num[s].init_state(jax.random.PRNGKey(9))
        num[s].restore(st2, back)
        for a, b in zip(jax.tree.leaves(st2.params),
                        jax.tree.leaves(sts[s].params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # a download never imports grads
        assert all(float(jnp.max(jnp.abs(x))) == 0.0
                   for x in jax.tree.leaves(st2.grad_acc))
    whole = pex.snapshot(pst)
    pst2 = pex.init_state(jax.random.PRNGKey(11))
    pex.restore(pst2, whole)
    for s in range(2):
        for a, b in zip(jax.tree.leaves(pst2.stage_view(s).params),
                        jax.tree.leaves(sts[s].params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_span_matches_single_stage_chain():
    """One span fwd/bwd == the chained single-stage programs (identical
    math — the codec round-trip fuses on-device; only XLA's whole-graph
    fusion may reassociate at f32-ulp scale, hence the tight rtol, far
    below anything a wrong boundary/codec wiring would produce)."""
    cfg = _codec_cfg()
    from repro.data.synthetic import SyntheticLM
    num = build_numeric_executors(cfg, 2, SEQ, compress="bottleneck")
    pex = PipelineExecutor(cfg, 2, SEQ, (0, 2), compress="bottleneck")
    sts = [e.init_state(jax.random.PRNGKey(0)) for e in num]
    pst = pex.init_state(jax.random.PRNGKey(1))
    for s in range(2):
        pex.restore(pst, num[s].snapshot(sts[s]), stage=s)
    b = SyntheticLM(cfg.vocab_size, SEQ, MB, seed=17).batch(0)
    w = num[0].wire_fwd(num[0].run_fwd(sts[0], b["tokens"]))
    loss_ref = float(num[1].run_fwd(sts[1], w, b["labels"]))
    loss_span = float(pex.run_fwd(pst, b["tokens"], b["labels"]))
    np.testing.assert_allclose(loss_span, loss_ref, rtol=1e-6)
    loss, gx, gp = pex.run_bwd(pst, b["tokens"], labels=b["labels"])
    np.testing.assert_allclose(float(loss), loss_ref, rtol=1e-6)
    assert gx is None
    assert set(gp) == {0, 1}
    _, gx1, gp1 = num[1].run_bwd(sts[1], w, labels=b["labels"])
    _, gp0 = num[0].prog.bwd(sts[0].params, b["tokens"], gx1)
    for ref_t, got_t in ((gp0, gp[0]), (gp1, gp[1])):
        for a, c in zip(jax.tree.leaves(ref_t), jax.tree.leaves(got_t)):
            scale = float(jnp.max(jnp.abs(a))) + 1e-9
            np.testing.assert_allclose(np.asarray(a) / scale,
                                       np.asarray(c) / scale, atol=1e-5)


def test_int8_wire_codec_applies_at_span_edges_only():
    """A [0, 2) span of a 4-stage int8 pipeline quantizes its outbound
    edge (stage 1 -> 2) but NOT the fused 0 -> 1 boundary: its fwd equals
    the un-quantized two-stage chain, and its wire output the edge
    round-trip."""
    cfg = tiny_dense_config()            # int8 is cfg-default
    from repro.compression.quant8 import _roundtrip
    from repro.data.synthetic import SyntheticLM
    num = build_numeric_executors(cfg, 4, SEQ, compress="int8")
    pex = PipelineExecutor(cfg, 4, SEQ, (0, 2), compress="int8")
    sts = [e.init_state(jax.random.PRNGKey(0)) for e in num]
    pst = pex.init_state(jax.random.PRNGKey(1))
    for s in range(2):
        pex.restore(pst, num[s].snapshot(sts[s]), stage=s)
    b = SyntheticLM(cfg.vocab_size, SEQ, MB, seed=17).batch(0)
    y = pex.run_fwd(pst, b["tokens"])
    # fused boundary un-quantized: equals chaining raw stage fwds
    raw = num[1].run_fwd(sts[1], num[0].run_fwd(sts[0], b["tokens"]))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(raw))
    # ...and differs from the single-stage path, which quantizes 0 -> 1
    quant = num[1].run_fwd(
        sts[1], num[0].wire_fwd(num[0].run_fwd(sts[0], b["tokens"])))
    assert float(jnp.max(jnp.abs(raw - quant))) > 0.0
    # the span's outbound EDGE is quantized like any wire crossing
    np.testing.assert_array_equal(
        np.asarray(pex.wire_fwd(y)),
        np.asarray(_roundtrip(y, pex.quant_block)))


# --------------------------------------------------- span rebalancing
def test_rebalance_loop_shrinks_span_peer_onto_bottleneck():
    """SwarmConfig(spans=True): Alg. 2 proposes a span change and the
    runner executes it — with stage 1 genuinely hot (slow single-stage
    peers backing up behind it), the span peer covering it concentrates
    onto the bottleneck stage (its dropped stage keeps cover), the
    remaining layout still routes, and exactly-once accounting holds."""
    from repro.core import rebalance as rb
    from repro.core.peer import DeviceProfile, MBPS
    slow = DeviceProfile("slow", 5e8, 800 * MBPS, 800 * MBPS, 1e-4)
    fast = DeviceProfile("fast", 40e9, 800 * MBPS, 800 * MBPS, 1e-4)
    cfg = tiny_dense_config()
    scfg = SwarmConfig(n_stages=2, microbatch_size=1, seq_len=512,
                       global_batch=16, n_trainers=6,
                       rebalance_period=0.5, codec="none",
                       max_steps=30, spans=True)
    r = SwarmRunner(cfg, scfg, adamw(), numeric=False, seed=0,
                    record_accumulation=True)
    r.build(peers_per_stage=2)
    for p in r.peers.values():               # stage-1 singles: the
        p.profile = slow if p.stage == 1 else fast   # bottleneck
    wide = r.add_peer(range(0, 2), profile=fast)
    r.run(until=60.0)
    assert r.metrics["span_changes"] >= 1
    assert wide.alive and len(wide.stages) == 1   # shrunk onto one stage
    # whatever sequence of moves ran, the serving layout still tiles
    layout = [(p.stages.start, p.stages.stop) for p in r.peers.values()
              if p.alive and p.serving]
    assert rb.spans_route(2, layout)
    _assert_exactly_once(r, 2, 16)


# --------------------------------------------------- compile accounting
def test_one_jit_per_span_and_codec():
    """N span peers of one (span, codec) share ONE fwd + ONE bwd jit;
    a second same-shape runner re-traces nothing."""
    reset_compile_stats()
    cfg = _codec_cfg()
    opt = adamw(lr=1e-2, grad_clip=0.0)

    def run(seed):
        r = SwarmRunner(cfg, _scfg(2, max_steps=1), opt, numeric=True,
                        seed=seed)
        _span_peer(r, 0, 2)
        _span_peer(r, 0, 2)
        r.build(peers_per_stage=0)
        r.run(until=1e6)

    run(seed=0)
    st = compile_stats()
    span_keys = {k: v for k, v in st["per_key"].items()
                 if (0, 2) in k}
    assert {k[-2] for k in span_keys} == {"fwd", "bwd"}
    assert all(v == 1 for v in span_keys.values()), span_keys
    run(seed=1)
    st2 = compile_stats()
    span_keys2 = {k: v for k, v in st2["per_key"].items()
                  if (0, 2) in k}
    assert span_keys2 == span_keys            # zero new traces
    # ...and the program object itself is cache-shared
    assert get_span_program(cfg, 2, SEQ, (0, 2), "bottleneck") is \
        get_span_program(cfg, 2, SEQ, (0, 2), "bottleneck")
