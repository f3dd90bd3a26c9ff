"""Exactly-once elastic gradient accounting under churn (paper App. A).

The load-bearing correctness property of elastic training (cf. Varuna,
arXiv:2111.04007; DeDLOC, arXiv:2106.10207): every optimizer step
averages exactly ``global_batch`` samples even while peers fail, join,
and migrate — gradients lost with dead peers are recomputed by
survivors, and nothing is ever double-counted.  The churn-equivalence
tests assert the strong form: a numeric SwarmRunner replaying a
preemption trace (failures + a warm join + a migration) reproduces the
*fault-free* reference loss trajectory on the same sample set.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import tiny_dense_config
from repro.core import SwarmRunner, SwarmConfig, TraceEvent, \
    MicrobatchLedger, reference_losses
from repro.core.faults import synth_preemptible_trace
from repro.core.sim import Sleep
from repro.runtime import build_stage_programs
from repro.optim import adamw

SEQ, MB, GB, STEPS = 32, 2, 8, 3


# ------------------------------------------------------------ ledger unit
def test_ledger_exactly_once_and_reissue():
    led = MicrobatchLedger(2)
    led.open_round([0, 1])
    assert led.next_index() == (0, 1)
    assert led.record(0, 0, "a")
    assert not led.record(0, 0, "a")        # double accumulation refused
    assert not led.record(0, 0, "b")        # also on another peer
    assert led.record(1, 0, "b")
    led.settle(0)
    assert led.next_index() == (1, 1)
    assert led.record(0, 1, "a") and led.record(1, 1, "b")
    led.settle(1)
    assert led.complete()
    assert led.next_index() is None
    # peer b dies: exactly its indices re-issue, as attempt 2
    assert sorted(led.release_peer(1, "b")) == [0, 1]
    assert not led.complete()
    assert led.next_index() == (0, 2)
    assert not led.record(0, 0, "c")        # stage 0 still holds it
    assert led.record(1, 0, "c")            # stage 1 recomputes
    led.settle(0)
    assert led.next_index() == (1, 2)
    assert led.record(1, 1, "c")
    led.settle(1)
    assert led.complete()


def test_ledger_release_during_flight_requeues_on_settle():
    led = MicrobatchLedger(2)
    led.open_round([5])
    assert led.next_index() == (5, 1)
    led.record(1, 5, "b")
    led.release_peer(1, "b")                # holder dies mid-flight
    assert led.next_index() is None         # still in flight: no re-issue
    led.record(0, 5, "a")
    led.settle(5)                           # flight ends -> stage 1 short
    assert led.next_index() == (5, 2)


def test_ledger_rejects_stale_round_indices():
    led = MicrobatchLedger(1)
    led.open_round([0, 1])
    led.next_index()
    led.open_round([2, 3])
    assert not led.record(0, 0, "a")        # previous round's index
    assert led.record(0, 2, "a")


# ------------------------------------------------------- span peers
def test_ledger_span_peer_holds_one_row_per_covered_stage():
    """A span peer admits each covered (stage, microbatch) pair exactly
    once — and a re-issued attempt after the span peer's death folds
    ONLY the stages whose grads died with it, skipping survivors."""
    led = MicrobatchLedger(3)
    led.open_round([0])
    assert led.next_index() == (0, 1)
    # stage 0 held by a single-stage survivor; the span peer covers
    # [1, 3) and records one row per covered stage
    assert led.record(0, 0, "single")
    assert led.record(1, 0, "span") and led.record(2, 0, "span")
    assert not led.record(1, 0, "span")     # exactly once per pair
    assert not led.record(2, 0, "other")
    led.settle(0)
    assert led.complete()
    # the span peer dies: exactly ITS rows release (both covered stages)
    assert sorted(led.release_all("span")) == [(1, 0), (2, 0)]
    assert led.next_index() == (0, 2)       # re-issued, attempt 2
    # the re-issue skips the surviving stage-0 gradient...
    assert not led.record(0, 0, "other")
    # ...and recomputes exactly the span's lost stages
    assert led.record(1, 0, "other") and led.record(2, 0, "other")
    led.settle(0)
    assert led.complete()


def test_swarm_accumulate_spans_all_covered_stages_exactly_once():
    """SwarmRunner.accumulate with a span peer: one ledger row + one
    fold per covered stage per microbatch, refused on re-delivery, and
    partial-fold when another peer already holds one covered stage."""
    cfg = tiny_dense_config()
    scfg = SwarmConfig(n_stages=2, microbatch_size=1, seq_len=64,
                       global_batch=4, n_trainers=0, rebalance_period=0.0,
                       codec="none", max_steps=1)
    r = SwarmRunner(cfg, scfg, adamw(), numeric=False, seed=0,
                    record_accumulation=True)
    span_peer = r.add_peer(range(0, 2))      # timing-mode span peer
    single = r.add_peer(1)
    from repro.core.trainer import Microbatch
    mb = Microbatch(index=r.ledger.round_indices[0], size=1, n_tokens=64)
    assert r.accumulate(span_peer, None, mb, loss=1.0)
    assert r.ledger.acc[0][mb.index] == span_peer.id
    assert r.ledger.acc[1][mb.index] == span_peer.id
    # per-stage bookkeeping on the span state; loss lands on the LAST
    # stage only (the swarm's loss metric reads stage S-1)
    assert span_peer.state.stage_view(0).token_count == 64
    assert span_peer.state.stage_view(1).token_count == 64
    assert span_peer.state.stage_view(0).loss_sum == 0.0
    assert span_peer.state.stage_view(1).loss_sum == 1.0
    # re-delivery folds nothing anywhere
    assert not r.accumulate(span_peer, None, mb, loss=1.0)
    assert span_peer.state.stage_view(1).token_count == 64
    # a second microbatch partially held elsewhere: the span peer folds
    # only its missing stage
    mb2 = Microbatch(index=r.ledger.round_indices[1], size=1, n_tokens=64)
    assert r.accumulate(single, None, mb2, loss=None, stage=1)
    assert r.accumulate(span_peer, None, mb2, loss=2.0)
    assert r.ledger.acc[1][mb2.index] == single.id      # survivor kept
    assert r.ledger.acc[0][mb2.index] == span_peer.id
    assert span_peer.state.stage_view(0).token_count == 128
    assert span_peer.state.stage_view(1).token_count == 64
    assert span_peer.state.stage_view(1).loss_sum == 1.0  # loss skipped


def test_span_peer_kill_reissues_only_lost_stages_under_churn():
    """Runner-level: kill a span peer mid-round; every re-issued
    accumulation (attempt > 1) lands on a previously-released (stage,
    index) pair — stages whose grads survived on other peers are never
    folded twice (replayed from the audit trail)."""
    cfg = tiny_dense_config()
    scfg = SwarmConfig(n_stages=2, microbatch_size=1, seq_len=512,
                       global_batch=8, n_trainers=4, rebalance_period=0.0,
                       codec="none", max_steps=6)
    r = SwarmRunner(cfg, scfg, adamw(), numeric=False, seed=3,
                    record_accumulation=True)
    r.build(peers_per_stage=2)
    span_peer = r.add_peer(range(0, 2))
    from repro.core.sim import Sleep as _Sleep

    def killer(rr, victim):
        # strike only while the victim HOLDS gradients of the open round
        # (a kill between rounds releases nothing and tests nothing)
        while not rr.stopped and victim.alive:
            if not rr._dispatch_paused and any(
                    victim.id in d.values() for d in rr.ledger.acc):
                rr._fail_peer(victim)
                return
            yield _Sleep(0.01)

    r.sim.spawn(killer(r, span_peer))
    r.run(until=60.0)
    assert r.step > 0 and r.metrics["failures"] == 1
    released = set()
    for kind, step, stage, idx, attempt, pid in r.ledger_log:
        key = (step, stage, idx)
        if kind == "rel":
            released.add(key)
        elif kind == "acc" and attempt > 1:
            # a recompute may only land where a gradient was lost
            assert key in released, (key, pid)
    assert any(pid == span_peer.id and kind == "rel"
               for kind, *_x, pid in r.ledger_log)
    _assert_exactly_once(r, 2, 8)


# ------------------------------------------------- churn equivalence
@pytest.fixture(scope="module")
def churn_setup():
    cfg = tiny_dense_config()
    programs = build_stage_programs(cfg, 2, SEQ)
    opt = adamw(lr=1e-2, grad_clip=0.0)
    return cfg, programs, opt


def _reference_losses(cfg, programs, opt, seed):
    """Fault-free sequential twin (repro.core.reference_losses)."""
    return reference_losses(cfg, programs, opt, seed, STEPS, SEQ, MB, GB)


def _force_migration(runner, at):
    """Deterministically migrate one peer out of a >1-serving stage."""
    yield Sleep(at)
    if runner.stopped:
        return
    for s in range(runner.n_stages):
        group = sorted((p for p in runner.peers.values()
                        if p.alive and p.serving and p.stage == s),
                       key=lambda p: p.id)
        if len(group) > 1:
            yield from runner._migrate(group[0],
                                       (s + 1) % runner.n_stages)
            return


def _assert_exactly_once(runner, n_stages, K):
    """Replay the ledger audit trail: a (round, stage, index) pair is
    never HELD twice (an accumulation while a prior one is still live is
    a double count; re-accumulating after a release is the recompute
    path and exact), and at each All-Reduce barrier every stage holds
    exactly the round's K indices."""
    held = set()
    for kind, step, stage, idx, attempt, pid in runner.ledger_log:
        key = (step, stage, idx)
        if kind == "acc":
            assert key not in held, \
                f"double accumulation: {key} attempt={attempt} peer={pid}"
            held.add(key)
        elif kind == "rel":
            assert key in held, f"release of unheld {key}"
            held.discard(key)
        else:                           # "step": the All-Reduce barrier
            for s in range(n_stages):
                n = sum(1 for (t, sg, _i) in held
                        if t == step and sg == s)
                assert n == K, (step, s, n)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_churn_equals_fault_free_reference(churn_setup, seed):
    """Failures + a warm join + a drained migration leave the loss
    trajectory bitwise-accounted: identical sample set per step, every
    lost gradient recomputed exactly once (mirrors
    test_swarm_equals_synchronous_training, but under churn)."""
    cfg, programs, opt = churn_setup
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=3, rebalance_period=0.0,
                       codec="none", max_steps=STEPS)
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
    ref = _reference_losses(cfg, programs, opt, seed)
    np.testing.assert_allclose(m["loss"], ref, atol=2e-4)
    _assert_exactly_once(runner, 2, GB // MB)


def test_revived_peer_serves_again(churn_setup):
    """Peer.revive wired into the trace joins: a dead peer object comes
    back warm — announced, un-banned, and accumulating."""
    cfg, programs, opt = churn_setup
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=2, rebalance_period=0.0,
                       codec="none", max_steps=STEPS)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0,
                         programs=programs, record_accumulation=True)
    runner.build(peers_per_stage=2)
    runner.apply_trace([TraceEvent(0.02, -1), TraceEvent(0.1, +1)])
    m = runner.run(until=1e6)
    assert m["failures"] == 1 and m["joins"] == 1
    dead_then_back = [p for p in runner.peers.values() if p.alive
                      and p._generation > 0]
    assert len(dead_then_back) == 1          # the SAME object rejoined
    peer = dead_then_back[0]
    assert peer.serving
    # it re-entered the DHT (raw store: TTLs all lapse once the virtual
    # clock jumps to `until` at run end) and did real work after reviving
    assert any(peer.id in runner.dht._store.get(
        runner.dht.stage_key(s), {}) for s in range(runner.n_stages))
    assert any(kind == "acc" and pid == peer.id
               for (kind, *_rest, pid) in runner.ledger_log)
    np.testing.assert_allclose(
        m["loss"], _reference_losses(cfg, programs, opt, 0), atol=2e-4)


# ------------------------------------------------- invariant under heavy churn
def _run_throughput_churn(seed):
    cfg = tiny_dense_config()
    # impatient trainers (max_retries=2): attempts fail wholesale after
    # partial backward accumulation, exercising the re-issue path where
    # the pre-fix code double-counted surviving stages' gradients
    scfg = SwarmConfig(n_stages=2, microbatch_size=1, seq_len=512,
                       global_batch=16, n_trainers=6, rebalance_period=1.0,
                       codec="int8", max_steps=20, trainer_max_retries=2)
    r = SwarmRunner(cfg, scfg, adamw(), numeric=False, seed=seed,
                    record_accumulation=True)
    r.build(peers_per_stage=3)
    # rounds last ~0.2 virtual seconds: a 3 s mean lifetime makes the
    # trace bite several times within the 20-step run
    r.apply_trace(synth_preemptible_trace(
        horizon_s=60.0, target_peers=6, mean_lifetime_s=3.0, seed=seed))
    r.run(until=120.0)
    return r


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ledger_invariant_under_heavy_churn(seed):
    """No (stage, microbatch) pair is ever accumulated twice, and every
    completed round holds the full global batch at every stage — under a
    hostile trace (mean lifetime 3 s) with rebalancing on."""
    r = _run_throughput_churn(seed)
    assert r.metrics["failures"] > 0         # the trace actually bites
    assert r.step > 0
    _assert_exactly_once(r, 2, 16)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_ledger_invariant_property(seed):
    """Hypothesis sweep of the same invariant over random traces."""
    r = _run_throughput_churn(seed % 997)
    _assert_exactly_once(r, 2, 16)
