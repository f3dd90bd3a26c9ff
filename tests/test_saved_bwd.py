"""The last stage's loss forward keeps its residuals, and the backward
that follows on the same objects consumes them instead of running the
forward again (``StageProgram.fwd_save``/``bwd_saved``, driven by
``NumericExecutor.run_fwd``/``run_bwd``).

A hit gives what the recompute backward gives; every other call order is
a miss that recomputes, exactly as before.  The pending forward lives on
the ``StageState``, one at most, and no state install or snapshot carries
it.  ``repro.obs`` counts both outcomes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense_config
from repro import obs
from repro.core import SwarmConfig, SwarmRunner, reference_losses
from repro.core.peer import A100, Peer
from repro.core.sim import Sim
from repro.models.config import ArchConfig
from repro.optim import adamw, delayed_parameter_updates
from repro.runtime import build_numeric_executors, build_stage_programs

SEQ, MB = 16, 2


def _cfg(codec: str):
    if codec == "bottleneck":
        return tiny_dense_config(n_layers=6, boundary_compression=codec,
                                 bottleneck_dim=16)
    return tiny_dense_config(n_layers=6)


def _chain(n_stages: int, codec: str, seed: int = 0):
    """The last stage's executor and fresh state, the wire tensor the
    stages before it send it (token ids for a lone stage), and labels."""
    cfg = _cfg(codec)
    exs = build_numeric_executors(cfg, n_stages, SEQ, compress=codec)
    key = jax.random.PRNGKey(seed)
    x = jax.random.randint(key, (MB, SEQ), 0, cfg.vocab_size)
    for s, ex in enumerate(exs[:-1]):
        st = ex.init_state(jax.random.fold_in(key, 10 + s))
        x = ex.wire_fwd(ex.run_fwd(st, x))
    labels = jax.random.randint(jax.random.fold_in(key, 1), (MB, SEQ), 0,
                                cfg.vocab_size)
    ex = exs[-1]
    return ex, ex.init_state(jax.random.fold_in(key, 2)), x, labels


def _counts(stage: int) -> tuple[int, int]:
    c = obs.counters()
    return (c.get(("exec.bwd_saved", stage), 0),
            c.get(("exec.bwd_recomputed", stage), 0))


def _assert_equal(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("codec", ["none", "int8", "bottleneck"])
@pytest.mark.parametrize("n_stages", [1, 2, 3])
def test_a_hit_equals_the_recompute_backward(n_stages, codec):
    """A hit's gradients are the recompute backward's bit for bit, so a
    hit and a miss feed the same gradients into the fold whatever the
    order of the trainers' calls.  Its loss is the forward's own, which
    may differ from the recompute backward's in the last bit (the two
    programs fuse the loss's sum differently)."""
    ex, state, inp, labels = _chain(n_stages, codec)
    assert ex.prog.fwd_save is not None and ex.prog.bwd_saved is not None
    want_loss, want_gx, want_gp = ex.prog.bwd(state.params, inp, labels)
    before = _counts(ex.stage)
    loss = ex.run_fwd(state, inp, labels)
    assert state.saved_fwd is not None and state.saved_fwd.loss is loss
    got_loss, gx, gp = ex.run_bwd(state, inp, labels=labels)
    assert got_loss is loss                      # the forward's own loss
    assert state.saved_fwd is None               # consumed
    assert _counts(ex.stage) == (before[0] + 1, before[1])
    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-6)
    assert (gx is None) == (want_gx is None) == (n_stages == 1)
    if gx is not None:
        assert gx.shape == inp.shape and gx.dtype == want_gx.dtype
        _assert_equal(gx, want_gx)
    assert jax.tree.structure(gp) == jax.tree.structure(want_gp)
    _assert_equal(gp, want_gp)


def test_saved_residuals_hold_no_parameter_and_no_logits():
    """The stage's params go back in as arguments; none of them is
    returned again among the residuals.  Nor are the head's logits: the
    backward runs the head again."""
    ex, state, inp, labels = _chain(2, "none")
    ex.run_fwd(state, inp, labels)
    saved = state.saved_fwd.saved
    param_bytes = sum(a.nbytes for a in jax.tree.leaves(state.params))
    assert sum(a.nbytes for a in saved) < param_bytes
    params = {id(a) for a in jax.tree.leaves(state.params)}
    assert not params & {id(a) for a in saved}
    vocab = state.params["head"].shape[-1]
    assert not any(vocab in a.shape for a in saved)


def _recomputes(ex, state, inp, labels, bwd_inp, bwd_labels):
    """Run a backward that must miss: it runs the recompute backward
    program itself, so it returns what that returns bit for bit (what a
    mesh or pipeline executor's backward of the stage is held to), and
    is counted as recomputed."""
    want = ex.prog.bwd(state.params, bwd_inp, bwd_labels)
    before = _counts(ex.stage)
    got = ex.run_bwd(state, bwd_inp, labels=bwd_labels)
    assert _counts(ex.stage) == (before[0], before[1] + 1)
    _assert_equal(got, want)


def test_another_inp_object_misses():
    ex, state, inp, labels = _chain(2, "int8")
    ex.run_fwd(state, inp, labels)
    entry = state.saved_fwd
    _recomputes(ex, state, inp, labels, inp + 0, labels)
    assert state.saved_fwd is entry              # a miss leaves it be
    ex.run_bwd(state, inp, labels=labels)        # and it still hits
    assert state.saved_fwd is None


def test_other_labels_miss():
    ex, state, inp, labels = _chain(2, "none")
    ex.run_fwd(state, inp, labels)
    _recomputes(ex, state, inp, labels, inp, (labels + 1) % 256)
    assert state.saved_fwd is not None


def test_adopt_step_between_drops_the_entry_and_misses():
    ex, state, inp, labels = _chain(2, "none")
    ex.run_fwd(state, inp, labels)
    new = jax.tree.map(lambda p: p * 0.5, state.params)
    ex.adopt_step(state, new, None)
    assert state.saved_fwd is None
    _recomputes(ex, state, inp, labels, inp, labels)


def test_a_second_forward_replaces_the_first():
    ex, state, inp, labels = _chain(2, "none")
    other = inp * 2.0
    ex.run_fwd(state, inp, labels)
    ex.run_fwd(state, other, labels)
    assert state.saved_fwd.inp is other
    _recomputes(ex, state, inp, labels, inp, labels)
    before = _counts(ex.stage)
    ex.run_bwd(state, other, labels=labels)
    assert _counts(ex.stage) == (before[0] + 1, before[1])


def test_restore_drops_the_entry_and_snapshot_carries_none():
    ex, state, inp, labels = _chain(2, "int8")
    ex.run_fwd(state, inp, labels)
    n_saved = len(state.saved_fwd.saved)
    snap = ex.snapshot(state, slots=("kv", "saved_fwd"))
    assert set(snap) == {"params", "opt", "version"}
    assert jax.tree.structure(snap["params"]) == \
        jax.tree.structure(state.params)
    assert snap["opt"] is None
    assert len(state.saved_fwd.saved) == n_saved   # snapshot keeps it
    ex.restore(state, snap)
    assert state.saved_fwd is None
    _recomputes(ex, state, inp, labels, inp, labels)


def test_non_last_stages_keep_no_entry():
    cfg = _cfg("int8")
    exs = build_numeric_executors(cfg, 3, SEQ, compress="int8")
    assert all(ex.prog.fwd_save is None and ex.prog.bwd_saved is None
               for ex in exs[:-1])
    tokens = jnp.zeros((MB, SEQ), jnp.int32)
    before = obs.counters()
    x = tokens
    for s, ex in enumerate(exs[:-1]):
        st = ex.init_state(jax.random.PRNGKey(s))
        y = ex.run_fwd(st, x)
        assert st.saved_fwd is None
        ex.run_bwd(st, x, dy=jnp.ones_like(y))
        x = ex.wire_fwd(y)
    after = obs.counters()
    for s in (0, 1):
        for kind in ("exec.bwd_saved", "exec.bwd_recomputed"):
            assert after.get((kind, s), 0) == before.get((kind, s), 0)


def test_an_encoder_decoder_last_stage_keeps_no_entry():
    cfg = ArchConfig(name="tiny-whisper", family="audio", n_layers=4,
                     d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                     vocab_size=256, head_dim=16, encoder_layers=2,
                     encoder_max_len=8, compute_dtype="float32",
                     param_dtype="float32")
    exs = build_numeric_executors(cfg, 2, SEQ, compress="none")
    assert all(ex.prog.fwd_save is None for ex in exs)
    rng = np.random.default_rng(0)
    x = {"audio": rng.standard_normal((MB, cfg.encoder_max_len,
                                       cfg.d_model)).astype(np.float32),
         "tok": rng.integers(0, cfg.vocab_size, (MB, SEQ), np.int32)}
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (MB, SEQ),
                                      np.int32))
    st0 = exs[0].init_state(jax.random.PRNGKey(0))
    inp = exs[0].run_fwd(st0, x)
    ex = exs[1]
    st = ex.init_state(jax.random.PRNGKey(1))
    loss = ex.run_fwd(st, inp, labels)
    assert st.saved_fwd is None
    before = _counts(1)
    got_loss, _, gp = ex.run_bwd(st, inp, labels=labels)
    assert _counts(1) == (before[0], before[1] + 1)
    np.testing.assert_allclose(float(got_loss), float(loss), rtol=1e-5)
    assert jax.tree.structure(gp) == jax.tree.structure(st.params)


def test_adopting_a_peers_state_drops_the_entry():
    """Peers of a stage share one executor and adopt each other's state
    by aliasing it; a forward of the adopter's old params is never a
    backward's."""
    ex, state, inp, labels = _chain(2, "none")
    sim = Sim()
    donor, peer = (Peer(sim, A100, 1, executor=ex) for _ in range(2))
    donor.state = ex.init_state(jax.random.PRNGKey(7))
    peer.state = state
    ex.run_fwd(state, inp, labels)
    assert state.saved_fwd is not None
    peer.adopt_state_from(donor)
    assert peer.state.saved_fwd is None
    _recomputes(ex, peer.state, inp, labels, inp, labels)


STEPS, GB = 4, 8


@pytest.mark.parametrize("staleness", [0, 1])
def test_swarm_matches_a_twin_whose_last_stage_recomputes(staleness):
    """Over optimizer steps, the swarm (whose last stage runs the saved
    pair) tracks the sequential twin whose last stage runs the recompute
    ``bwd``, a program this mechanism does not touch.  The two may differ
    only in how XLA fuses the same math (last bits of the loss and
    gradients; none on the CPU), so the losses agree to 1e-5 relative,
    while an additive gradient fault of 1e-4 of the mean moves them by
    4e-5 to 6e-5."""
    cfg = tiny_dense_config()
    opt = adamw(lr=1e-2, grad_clip=0.0)
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=1, rebalance_period=0.0,
                       codec="none", max_steps=STEPS, overlap=True,
                       staleness=staleness)
    before = _counts(1)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0)
    runner.build(peers_per_stage=1)
    m = runner.run(until=1e6)
    assert runner.step == STEPS
    n_mb = STEPS * GB // MB
    assert _counts(1) == (before[0] + n_mb, before[1])
    programs = build_stage_programs(cfg, 2, SEQ)
    twin = [*programs[:-1], dataclasses.replace(
        programs[-1], fwd_save=None, bwd_saved=None)]
    ref_opt = (delayed_parameter_updates(opt, delay=1) if staleness
               else opt)
    ref = reference_losses(cfg, twin, ref_opt, 0, STEPS, SEQ, MB, GB)
    np.testing.assert_allclose(m["loss"], ref, rtol=1e-5)
