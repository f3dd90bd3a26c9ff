"""The Pallas hot path (``cfg.kernels="pallas"``) is a pure backend
switch: kernel-vs-oracle equivalence for every fused op, gradient
equality across backends (the fused ops share one jnp backward), full
train-step equivalence on both shipping pipeline paths (GSPMD +
elastic), grad-flow through the fused boundary codec, and exactly-once
accounting under churn with the fused wire-quantized crossing on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense_config
from repro.core import SwarmRunner, SwarmConfig, TraceEvent, \
    reference_losses
from repro.optim import adamw
from repro.runtime import build_stage_programs

SEQ, MB, GB, STEPS = 32, 2, 8, 2

CODEC_KW = dict(boundary_compression="bottleneck", bottleneck_dim=16,
                pipeline_stages=2)


def _cfg_pair(**kw):
    """(jnp, pallas) configs differing ONLY in the kernels flag."""
    return (tiny_dense_config(**kw),
            tiny_dense_config(kernels="pallas", **kw))


# ----------------------------------------------------- backend detection
def test_default_interpret_auto_detects_cpu():
    from repro.kernels.backend import default_interpret, resolve_interpret
    assert jax.default_backend() == "cpu"
    assert default_interpret() is True       # no TPU/GPU -> interpret
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


def test_quant8_ops_interpret_default_is_backend_aware():
    """quant8 wrappers no longer hard-code interpret=True: the default
    resolves from the backend (interpret on CPU), and an explicit policy
    threads through to the same numbers."""
    from repro.kernels.quant8.ops import roundtrip
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 37))
    auto = roundtrip(x, 64)                      # interpret=None -> auto
    forced = roundtrip(x, 64, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(forced))


# ------------------------------------------------- kernel vs jnp oracle
@pytest.mark.parametrize("shape,qb", [((6, 64), 16), ((2, 5, 48), 16),
                                      ((128, 128), 64)])
def test_fused_qdq_matches_ref(shape, qb):
    from repro.kernels.boundary import kernel as K, ref as R
    x = jax.random.normal(jax.random.PRNGKey(1), shape) * 3.0
    np.testing.assert_allclose(np.asarray(K.qdq(x, qb)),
                               np.asarray(R.qdq_ref(x, qb)), atol=1e-6)


@pytest.mark.parametrize("n", [64, 100, 4096, 37])
def test_fused_flat_qdq_matches_quant8(n):
    """The single-launch flat round trip reproduces quant8's two-pass
    quantize/dequantize bit-for-bit geometry (incl. the padded tail
    block, whose zeros never raise an absmax)."""
    from repro.compression.quant8 import _roundtrip
    from repro.kernels.boundary.kernel import qdq_flat
    x = jax.random.normal(jax.random.PRNGKey(2), (n,)) * 2.0
    np.testing.assert_allclose(np.asarray(qdq_flat(x, 64)),
                               np.asarray(_roundtrip(x, 64)), atol=1e-6)


@pytest.mark.parametrize("mode,k", [("bottleneck", 1), ("maxout", 4)])
@pytest.mark.parametrize("quantize", [False, True])
def test_fused_codec_kernels_match_ref(mode, k, quantize):
    from repro.kernels.boundary import kernel as K, ref as R
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64)) * 3.0
    w_c = (jax.random.normal(jax.random.PRNGKey(4), (64, 16)) * 0.2
           if mode == "bottleneck" else None)
    c = 16
    w_d = jax.random.normal(jax.random.PRNGKey(5), (c, 64)) * 0.2
    qb = R.wire_qblock(c)
    ze = R.encode_ref(x, w_c, mode, k)
    ref = R.qdq_ref(ze, qb) if quantize else ze
    np.testing.assert_allclose(
        np.asarray(K.encode(x, w_c, mode, k, qb, quantize)),
        np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(K.decode(ze, w_d, mode)),
        np.asarray(R.decode_ref(ze, w_d, mode)), atol=1e-5)
    # the true wire payload: int8 codes identical, scales/decode close
    q_r, s_r = R.encode_quantize_ref(x, w_c, mode, k, qb)
    q_k, s_k = K.encode_quantize(x, w_c, mode, k, qb)
    np.testing.assert_array_equal(np.asarray(q_r), np.asarray(q_k))
    np.testing.assert_allclose(np.asarray(s_r), np.asarray(s_k),
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(K.dequantize_decode(q_k, s_k, w_d, mode, qb)),
        np.asarray(R.dequantize_decode_ref(q_r, s_r, w_d, mode, qb)),
        atol=1e-5)


@pytest.mark.parametrize("mode,k", [("bottleneck", 1), ("maxout", 4)])
@pytest.mark.parametrize("quantized", [False, True])
def test_fused_codec_grads_match_backends_and_flow(mode, k, quantized):
    """Backends share one jnp backward: (dx, dw_c, dw_d) agree to f32
    rounding, the STE rides the wire QDQ, and both codec matrices keep
    training (nonzero grads)."""
    from repro.kernels.boundary import ops as O
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64)) * 2.0
    w_c = jax.random.normal(jax.random.PRNGKey(7), (64, 16)) * 0.2
    w_d = jax.random.normal(jax.random.PRNGKey(8), (16, 64)) * 0.2

    def loss(x, wc, wd, use_kernel):
        w = wc if mode == "bottleneck" else None
        z = O.encode_wire(x, w, mode, k, 16, quantized, use_kernel)
        return jnp.sum(O.decode_wire(z, wd, mode, use_kernel) ** 2)

    gp = jax.grad(loss, argnums=(0, 1, 2))(x, w_c, w_d, True)
    gj = jax.grad(loss, argnums=(0, 1, 2))(x, w_c, w_d, False)
    for a, b in zip(gp, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4)
    if mode == "bottleneck":
        assert float(jnp.max(jnp.abs(gp[1]))) > 0      # w_c trains
    assert float(jnp.max(jnp.abs(gp[2]))) > 0          # w_d trains


def test_flash_pallas_impl_matches_jnp_vjp():
    """flash_attention(impl="pallas"): fused forward kernel + the
    chunked jnp backward — out and (dq, dk, dv) equal the jnp path
    (GQA, causal)."""
    from repro.models.flash import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (2, 24, 4, 16))
    k = jax.random.normal(ks[1], (2, 24, 2, 16))
    v = jax.random.normal(ks[2], (2, 24, 2, 16))

    def loss(q, k, v, impl):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       impl=impl) ** 2)

    np.testing.assert_allclose(
        float(loss(q, k, v, "pallas")), float(loss(q, k, v, "jnp")),
        rtol=1e-6)
    gp = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, "pallas")
    gj = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, "jnp")
    for a, b in zip(gp, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)


def test_rmsnorm_train_matches_autodiff():
    from repro.kernels.rmsnorm.ops import rmsnorm_train
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    x = jax.random.normal(jax.random.PRNGKey(10), (6, 33, 64)) * 2.0
    s = jax.random.normal(jax.random.PRNGKey(11), (64,)) * 0.5 + 1.0
    f_k = lambda x, s: jnp.sum(jnp.sin(rmsnorm_train(x, s)))
    f_r = lambda x, s: jnp.sum(jnp.sin(rmsnorm_ref(x, s)))
    np.testing.assert_allclose(float(f_k(x, s)), float(f_r(x, s)),
                               rtol=1e-6)
    gk, gr = jax.grad(f_k, (0, 1))(x, s), jax.grad(f_r, (0, 1))(x, s)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)


# ------------------------------------------- full train-step equivalence
@pytest.mark.parametrize("wire_quant", [False, True])
def test_pipeline_train_step_pallas_matches_jnp(wire_quant):
    """One GSPMD pipelined train step, kernels="pallas" vs "jnp" at
    identical config/init/batch: loss within 1e-5, every gradient leaf
    within 1e-5 of the jnp path's (scale-normalized), boundary codec
    grads nonzero (the fused crossing ships on this path).  The
    grad-capturing optimizer keeps the accumulated gradient itself in
    its state: a param delta ``(p + g) - p`` would round ``g`` to one
    ULP of ``p`` (2**-23 for the O(1) embedding rows, 1.6e-5 of their
    gradient's scale), which is coarser than the bound under test."""
    from repro.data import make_batch
    from repro.dist.pipeline import make_pipeline_train_step
    from repro.optim.adamw import Optimizer
    from repro.train.steps import make_state
    cfg_j, cfg_p = _cfg_pair(wire_quant=wire_quant, **CODEC_KW)
    grad_opt = Optimizer(
        init=lambda p: {"g": jax.tree.map(jnp.zeros_like, p)},
        update=lambda g, s, p: (jax.tree.map(jnp.zeros_like, g),
                                {"g": g}))
    batch = make_batch(cfg_j.vocab_size, SEQ, GB)
    outs = {}
    for name, cfg in (("jnp", cfg_j), ("pallas", cfg_p)):
        state = make_state(cfg, grad_opt, jax.random.PRNGKey(0))
        assert "boundary" in state["params"]
        step = jax.jit(make_pipeline_train_step(cfg, grad_opt,
                                                n_stages=2,
                                                n_microbatches=4,
                                                remat=False))
        new_state, m = step(state, batch)
        grads = new_state["opt"]["g"]
        outs[name] = (float(m["loss"]), grads)
        for kk, g in grads["boundary"].items():
            assert float(jnp.max(jnp.abs(g))) > 0, kk
    assert abs(outs["pallas"][0] - outs["jnp"][0]) < 1e-5
    # wire_quant: a 1-ULP pre-rounding diff can flip an int8 code at an
    # exact tie, moving that element by scale/127 — so the quantized
    # variant gets a slightly looser (still tight) gradient bound
    tol = 1e-4 if wire_quant else 1e-5
    for a, b in zip(jax.tree.leaves(outs["pallas"][1]),
                    jax.tree.leaves(outs["jnp"][1])):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=tol)


@pytest.mark.parametrize("wire_quant", [False, True])
def test_elastic_run_pallas_matches_jnp(wire_quant):
    """The elastic path (numeric SwarmRunner, learned codec): the
    pallas-backed swarm reproduces the jnp swarm's loss trajectory at
    identical seed and sample order."""
    losses = {}
    for name, cfg in zip(("jnp", "pallas"),
                         _cfg_pair(wire_quant=wire_quant,
                                   boundary_compression="bottleneck",
                                   bottleneck_dim=16)):
        scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                           global_batch=GB, n_trainers=2,
                           rebalance_period=0.0, codec="bottleneck",
                           max_steps=STEPS)
        r = SwarmRunner(cfg, scfg, adamw(lr=1e-2), numeric=True, seed=0)
        r.build(peers_per_stage=2)
        m = r.run(until=1e6)
        assert r.step == STEPS
        losses[name] = m["loss"]
    # step 1 runs at identical params (matches to f32 rounding); step 2
    # adds an adamw update that amplifies ULP-level grad diffs, so the
    # bound is relative (tie-flipped int8 codes widen it under
    # wire_quant — see the pipeline test)
    np.testing.assert_allclose(losses["pallas"], losses["jnp"],
                               rtol=1e-4 if wire_quant else 1e-5)


def test_churn_exactly_once_pallas_wire_quant():
    """Exactly-once accounting survives churn with the fused
    wire-quantized pallas crossing on: failures + a warm join reproduce
    the fault-free reference trajectory (same fused codec in the
    sequential oracle), and no (stage, microbatch) pair is ever
    double-counted."""
    from test_churn import _assert_exactly_once
    cfg = tiny_dense_config(kernels="pallas", wire_quant=True,
                            boundary_compression="bottleneck",
                            bottleneck_dim=16)
    programs = build_stage_programs(cfg, 2, SEQ)
    opt = adamw(lr=1e-2, grad_clip=0.0)
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=3,
                       rebalance_period=0.0, codec="bottleneck",
                       max_steps=STEPS)
    runner = SwarmRunner(cfg, scfg, opt, numeric=True, seed=0,
                         programs=programs, record_accumulation=True)
    runner.build(peers_per_stage=3)
    runner.apply_trace([TraceEvent(0.05, -1), TraceEvent(0.22, +1)])
    m = runner.run(until=1e6)
    assert runner.step == STEPS
    assert m["failures"] == 1 and m["joins"] == 1
    ref = reference_losses(cfg, programs, opt, 0, STEPS, SEQ, MB, GB)
    np.testing.assert_allclose(m["loss"], ref, atol=2e-4)
    _assert_exactly_once(runner, 2, GB // MB)
