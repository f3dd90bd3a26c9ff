"""Heterogeneous stage kinds, end-to-end (the StagePlan fast lane).

One pipeline, many block kinds: the :class:`~repro.models.stage_plan
.StagePlan` computed in ``models/`` must drive every layer identically —
stage programs (``runtime/``), the reference loss (``dist/``), swarm
pricing (``core/``) — for three workloads the paper's uniform-stack
tests never exercise:

* **mixed attention + SSM** decoder stacks (per-kind stage runs),
* **whisper encoder-decoder** with the encoder pod placed exactly at
  the cross-attention boundary,
* **recurrent-state (mamba) serving** whose carry must survive span-peer
  death through the keyed slot ledger.

Plus the compile discipline the plan exists to guarantee: one jit per
(stage, kind-run), zero re-traces for a second same-shape runner.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense_config
from repro.core import SwarmRunner, SwarmConfig, TraceEvent, \
    reference_losses
from repro.models.config import ArchConfig, MoEConfig, SSMConfig
from repro.models.stage_plan import get_stage_plan, make_stage_plan
from repro.optim import adamw
from repro.runtime import build_stage_programs, init_stage_params
from repro.runtime.stage_model import split_whisper_params

SEQ, MB, GB, STEPS = 32, 2, 8, 3


def mixed_config(**kw):
    """2 attention layers feeding 2 mamba layers — a 2-stage split puts
    one kind per stage, a 4-stage split one layer per stage."""
    base = dict(name="tiny-mixed",
                block_pattern=("attn", "attn", "mamba", "mamba"),
                ssm=SSMConfig(state_dim=8, chunk=16))
    base.update(kw)
    return tiny_dense_config(**base)


def whisper_config():
    return ArchConfig(name="tiny-whisper", family="audio", n_layers=4,
                      d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                      vocab_size=256, head_dim=16, encoder_layers=2,
                      encoder_max_len=8, compute_dtype="float32",
                      param_dtype="float32")


# -------------------------------------------------------------- the plan
class TestStagePlan:
    def test_mixed_runs_slots_and_fusion(self):
        plan = get_stage_plan(mixed_config(), 2)
        assert plan.stages[0].runs == (("attn", 2),)
        assert plan.stages[1].runs == (("mamba", 2),)
        assert plan.stages[0].aux_slots == ()
        assert plan.stages[1].aux_slots == ("kv",)   # recurrent carry
        assert not plan.periodic
        # the kind boundary between stages 0 and 1 never fuses
        assert plan.fusion_groups((0, 2)) == [(0, 1), (1, 1)]

    def test_whisper_pod_at_cross_attention_boundary(self):
        cfg = whisper_config()
        plan = get_stage_plan(cfg, 3)
        assert plan.is_encdec and not plan.periodic
        assert plan.stages[0].runs == (("whisper_enc", 2),)
        assert not plan.stages[0].owns_embed          # token embed is
        assert plan.stages[1].owns_embed              # the decoder's
        assert plan.stages[2].owns_head
        assert plan.stages[1].aux_slots == ("kv",)
        # boundary 0 (the pod hand-off) ships encoder output + token
        # ids; interior boundaries additionally ship the hidden state
        b0 = plan.boundary_bytes(0, MB, SEQ)
        b1 = plan.boundary_bytes(1, MB, SEQ)
        enc = 2.0 * MB * cfg.encoder_max_len * cfg.d_model
        tok = 4.0 * MB * SEQ
        assert b0 == pytest.approx(enc + tok)
        assert b1 == pytest.approx(b0 + 2.0 * MB * SEQ * cfg.d_model)

    def test_expert_sharded_moe_prices_routed_tokens(self):
        cfg = mixed_config(
            block_pattern=("attn", "attn", "moe", "moe"),
            moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                          expert_sharded=True))
        plan = get_stage_plan(cfg, 4)
        dense = dataclasses.replace(cfg.moe, expert_sharded=False)
        base = get_stage_plan(
            dataclasses.replace(cfg, moe=dense), 4).boundary_bytes(
                0, MB, SEQ)
        # entering a MoE stage: top_k routed copies of every token
        assert plan.boundary_bytes(1, MB, SEQ) == pytest.approx(2 * base)
        assert plan.boundary_bytes(2, MB, SEQ) == pytest.approx(2 * base)
        # attn -> attn boundary keeps the uniform price
        assert plan.boundary_bytes(0, MB, SEQ) == pytest.approx(base)

    def test_share_groups_with_mixed_kinds_is_rejected(self):
        from repro.models import model as model_lib
        cfg = mixed_config(share_groups=2)
        with pytest.raises(ValueError, match="share_groups"):
            make_stage_plan(cfg, 2)
        with pytest.raises(ValueError, match="share_groups"):
            model_lib.lm_specs(cfg)


# ------------------------------------------------- mixed-kind training
@pytest.fixture(scope="module")
def mixed_setup():
    cfg = mixed_config()
    programs = build_stage_programs(cfg, 2, SEQ)
    opt = adamw(lr=1e-2, grad_clip=0.0)
    return cfg, programs, opt


class TestMixedKindSwarm:
    def test_fault_free_equals_reference(self, mixed_setup):
        """An attention-stage + mamba-stage swarm reproduces the
        sequential fault-free trajectory token for token."""
        cfg, programs, opt = mixed_setup
        scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                           global_batch=GB, n_trainers=3,
                           rebalance_period=0.0, codec="none",
                           max_steps=STEPS)
        r = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0,
                        programs=programs)
        r.build(peers_per_stage=2)
        m = r.run(until=1e6)
        ref = reference_losses(cfg, programs, opt, 0, STEPS, SEQ, MB, GB)
        assert r.step == STEPS
        np.testing.assert_allclose(m["loss"], ref, atol=2e-4)

    def test_churn_equals_reference(self, mixed_setup):
        """Failures + a warm join leave the mixed-kind trajectory within
        2e-4 of the fault-free oracle (exactly-once under churn holds
        across kind boundaries)."""
        cfg, programs, opt = mixed_setup
        scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                           global_batch=GB, n_trainers=3,
                           rebalance_period=0.0, codec="none",
                           max_steps=STEPS)
        r = SwarmRunner(cfg, scfg, opt, numeric=True, seed=1,
                        programs=programs)
        r.build(peers_per_stage=3)
        r.apply_trace([TraceEvent(0.02, -1), TraceEvent(0.05, -1),
                       TraceEvent(0.22, +1)])
        m = r.run(until=1e6)
        assert r.step == STEPS
        assert m["failures"] == 2 and m["joins"] == 1
        ref = reference_losses(cfg, programs, opt, 1, STEPS, SEQ, MB, GB)
        np.testing.assert_allclose(m["loss"], ref, atol=2e-4)


# ------------------------------------------------------ whisper staged
W_SEQ, W_MB, W_GB, W_STEPS = 16, 2, 4, 2


def _whisper_batch(cfg, idx, b=W_MB, seq=W_SEQ):
    rng = np.random.default_rng(1000 + idx)
    audio = rng.standard_normal(
        (b, cfg.encoder_max_len, cfg.d_model)).astype(np.float32)
    tok = rng.integers(0, cfg.vocab_size, size=(b, seq),
                       dtype=np.int32)
    lab = rng.integers(0, cfg.vocab_size, size=(b, seq),
                       dtype=np.int32)
    return {"tokens": {"audio": audio, "tok": tok}, "labels": lab}


def _whisper_reference(cfg, programs, opt, seed, steps=W_STEPS,
                       seq=W_SEQ, mb=W_MB, gb=W_GB):
    """repro.core.reference_losses with whisper's tree-valued boundaries
    and audio+token data (same accumulation conventions)."""
    S = len(programs)
    params = init_stage_params(programs, jax.random.PRNGKey(seed))
    opt_states = [opt.init(p) for p in params]
    idx, losses = 0, []
    for _ in range(steps):
        grads = [jax.tree.map(jnp.zeros_like, p) for p in params]
        loss_sum, tok = 0.0, 0
        for _ in range(gb // mb):
            b = _whisper_batch(cfg, idx)
            idx += 1
            xs = [b["tokens"]]
            for s in range(S - 1):
                xs.append(programs[s].fwd(params[s], xs[-1]))
            loss, gx, gp = programs[S - 1].bwd(params[S - 1], xs[-1],
                                               b["labels"])
            grads[S - 1] = jax.tree.map(jnp.add, grads[S - 1], gp)
            for s in range(S - 2, 0, -1):
                gx, gp = programs[s].bwd(params[s], xs[s], gx)
                grads[s] = jax.tree.map(jnp.add, grads[s], gp)
            _, gp = programs[0].bwd(params[0], xs[0], gx)
            grads[0] = jax.tree.map(jnp.add, grads[0], gp)
            loss_sum += float(loss)
            tok += mb * seq
        losses.append(loss_sum / tok)
        for s in range(S):
            gm = jax.tree.map(lambda g: g / tok, grads[s])
            upd, opt_states[s] = opt.update(gm, opt_states[s], params[s])
            params[s] = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                     params[s], upd)
    return losses


class TestWhisperStaged:
    def test_staged_chain_matches_whisper_apply(self):
        """Stage programs sliced out of a full whisper tree reproduce
        the whole-model loss exactly (the pod hand-off and payload-tree
        boundaries lose nothing)."""
        from repro.models import params as P
        from repro.models import whisper as W
        from repro.train import steps as steps_lib
        cfg = whisper_config()
        programs = build_stage_programs(cfg, 3, W_SEQ)
        full = P.init(jax.random.PRNGKey(0), W.whisper_specs(cfg))
        staged = split_whisper_params(cfg, 3, full)
        b = _whisper_batch(cfg, 0)
        x = b["tokens"]
        for s in range(2):
            x = programs[s].fwd(staged[s], x)
        loss, _, _ = programs[2].bwd(staged[2], x, b["labels"])
        logits, _ = W.whisper_apply(
            cfg, full, {"audio_embed": b["tokens"]["audio"],
                        "tokens": b["tokens"]["tok"]})
        ref = steps_lib.cross_entropy(logits, b["labels"])  # token mean
        np.testing.assert_allclose(float(loss) / (W_MB * W_SEQ),
                                   float(ref), rtol=1e-6)

    def test_whisper_swarm_trains_elastic(self):
        """A 3-stage whisper swarm (encoder pod + 2 decoder stages)
        trains through a failure + warm join, matching the fault-free
        reference trajectory."""
        cfg = whisper_config()
        programs = build_stage_programs(cfg, 3, W_SEQ)
        opt = adamw(lr=1e-2, grad_clip=0.0)
        scfg = SwarmConfig(n_stages=3, microbatch_size=W_MB,
                           seq_len=W_SEQ, global_batch=W_GB,
                           n_trainers=2, rebalance_period=0.0,
                           codec="none", max_steps=W_STEPS)
        r = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0,
                        programs=programs,
                        data_fn=lambda i: _whisper_batch(cfg, i))
        r.build(peers_per_stage=2)
        r.apply_trace([TraceEvent(0.03, -1), TraceEvent(0.2, +1)])
        m = r.run(until=1e6)
        assert r.step == W_STEPS
        assert m["failures"] == 1 and m["joins"] == 1
        ref = _whisper_reference(cfg, programs, opt, 0)
        np.testing.assert_allclose(m["loss"], ref, atol=2e-4)


# ----------------------------------------------- recurrent serving carry
class TestRecurrentServing:
    def test_mamba_carry_survives_span_death(self):
        """Kill a decode span peer serving mamba stages mid-generation:
        the recurrent carry is NOT recomputable from a KV ring, so the
        replacement re-prefills exactly the dead span's stages from the
        recorded boundary history — greedy outputs stay token-for-token
        equal to the single-process reference, and the strict slot
        ledger (raises on double prefill) proves exactly-once."""
        from repro.serve import ServeConfig, ServeRunner
        from repro.serve.runner import reference_generate
        cfg = tiny_dense_config(name="tiny-mamba",
                                block_pattern=("mamba",) * 4,
                                ssm=SSMConfig(state_dim=8, chunk=16))
        plan = get_stage_plan(cfg, 4)
        assert all(s.aux_slots == ("kv",) for s in plan.stages)
        r = ServeRunner(cfg, ServeConfig(n_stages=4, max_batch=2,
                                         max_sessions=1), seed=0)
        for name, span in (("d0a", (0, 2)), ("d1a", (2, 4)),
                           ("d0b", (0, 2)), ("d1b", (2, 4))):
            r.add_peer(span, pool="decode", name=name)
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size, size=(4, 8))
        reqs = [r.submit(p, 6) for p in prompts]
        r.schedule_fail(0.045, "d1a")               # lands mid-decode
        summary = r.run()
        ref = reference_generate(cfg, r.params, prompts, 6)
        np.testing.assert_array_equal(
            np.stack([q.tokens for q in reqs]), ref)
        assert summary["failed"] == 0
        assert summary["reprefills"] >= 1
        assert summary["reprefilled_stages"] == 2 * summary["reprefills"]
        assert all(c == 0 for c in r.kv.stage_counts())


# --------------------------------------------------- compile discipline
class TestCompileDiscipline:
    def test_one_jit_per_stage_kind_and_no_retraces(self):
        """A mixed-kind swarm compiles each (stage, fwd/bwd, shapes)
        exactly once, and a second identical runner re-traces nothing
        (the process-wide program cache keyed on the plan's inputs)."""
        from repro.runtime.numeric import compile_stats, \
            reset_compile_stats
        cfg = mixed_config()
        opt = adamw(lr=1e-2, grad_clip=0.0)
        scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                           global_batch=GB, n_trainers=2,
                           rebalance_period=0.0, codec="none",
                           max_steps=2)
        reset_compile_stats()
        r1 = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0)
        r1.build(peers_per_stage=2)
        r1.run(until=1e6)
        s1 = compile_stats()
        assert s1["traces"] > 0
        assert all(n == 1 for n in s1["per_key"].values()), s1["per_key"]
        r2 = SwarmRunner(cfg, scfg, opt, numeric=True, seed=1)
        r2.build(peers_per_stage=2)
        r2.run(until=1e6)
        s2 = compile_stats()
        assert s2["traces"] == s1["traces"]          # zero re-traces
        assert s2["per_key"] == s1["per_key"]
