"""Per-assigned-architecture smoke tests: REDUCED same-family config, one
train step + one prefill + one decode step on CPU; asserts shapes + no
NaNs (the FULL configs are exercised only via the dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, ASSIGNED, get_reduced, ShapeSpec
from repro.data import make_batch
from repro.optim import adamw
from repro.train.steps import (make_train_step, make_serve_step,
                               make_prefill_step, make_state,
                               decode_cache_specs)

SEQ, BATCH = 32, 2


def _batch_for(cfg, key):
    batch = make_batch(cfg.vocab_size, SEQ, BATCH)
    if cfg.rope == "mrope":
        batch["positions"] = jnp.broadcast_to(jnp.arange(SEQ),
                                              (3, BATCH, SEQ))
    if cfg.family == "audio":
        batch["audio_embed"] = jax.random.normal(
            key, (BATCH, cfg.encoder_max_len, cfg.d_model),
            cfg.compute_jdtype)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED + ["swarm-1b"])
def test_arch_train_step(arch):
    cfg = get_reduced(arch)
    opt = adamw(lr=1e-3)
    key = jax.random.PRNGKey(0)
    state = make_state(cfg, opt, key)
    batch = _batch_for(cfg, key)
    step = jax.jit(make_train_step(cfg, opt))
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2["step"]) == 1
    # params actually moved
    moved = any(
        float(jnp.max(jnp.abs(a - b))) > 0
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(state2["params"])))
    assert moved


@pytest.mark.parametrize("arch", ASSIGNED)
def test_arch_decode_step(arch):
    cfg = get_reduced(arch)
    opt = adamw()
    state = make_state(cfg, opt, jax.random.PRNGKey(0))
    shape = ShapeSpec("d", 48, BATCH, "decode")
    cs = decode_cache_specs(cfg, shape)
    caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cs)
    step = jax.jit(make_serve_step(cfg))
    tok = jnp.zeros((BATCH, 1), jnp.int32)
    for pos in range(3):
        tok, caches = step(state["params"], caches, tok, jnp.int32(pos))
    assert tok.shape == (BATCH, 1)
    assert int(tok.max()) < cfg.vocab_size


@pytest.mark.parametrize("arch", ASSIGNED)
def test_arch_prefill_step(arch):
    cfg = get_reduced(arch)
    opt = adamw()
    key = jax.random.PRNGKey(1)
    state = make_state(cfg, opt, key)
    batch = _batch_for(cfg, key)
    batch.pop("labels")
    step = jax.jit(make_prefill_step(cfg))
    nxt, caches = step(state["params"], batch)
    assert nxt.shape == (BATCH, 1)
    for leaf in jax.tree.leaves(caches):
        assert np.all(np.isfinite(np.asarray(leaf, np.float32)))


def test_prefill_decode_consistency():
    """prefill(S tokens) then decode == full forward over S+1 tokens."""
    cfg = get_reduced("yi-6b")
    from repro.models import model as M
    from repro.models import params as P
    params = P.init(jax.random.PRNGKey(3),
                    __import__("repro.train.steps",
                               fromlist=["model_specs"]).model_specs(cfg))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, SEQ + 1), 0,
                              cfg.vocab_size)
    full_logits, _ = M.lm_apply(cfg, params, toks, remat=False)
    logits_p, caches = M.lm_prefill(cfg, params, toks[:, :SEQ],
                                    cache_len=SEQ + 1, remat=False,
                                    last_only=False)
    np.testing.assert_allclose(
        np.asarray(full_logits[:, :SEQ], np.float32),
        np.asarray(logits_p, np.float32), atol=2e-4)
    logits_d, _ = M.lm_decode_step(cfg, params, toks[:, SEQ:SEQ + 1],
                                   caches, jnp.int32(SEQ))
    np.testing.assert_allclose(np.asarray(full_logits[:, -1], np.float32),
                               np.asarray(logits_d[:, 0], np.float32),
                               atol=2e-3)


def test_ring_cache_matches_full_cache_for_swa():
    """Sliding-window decode with a ring buffer == with a full cache."""
    cfg = get_reduced("h2o-danube-3-4b")      # sliding_window = 8
    from repro.models import model as M
    from repro.train.steps import model_specs
    from repro.models import params as P
    params = P.init(jax.random.PRNGKey(5), model_specs(cfg))
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, 24), 0,
                              cfg.vocab_size)
    # reference: full forward logits for last position
    full_logits, _ = M.lm_apply(cfg, params, toks, remat=False)
    # decode token-by-token with the ring cache (size == window == 8)
    caches = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        __import__("repro.train.steps", fromlist=["decode_cache_specs"]
                   ).decode_cache_specs(cfg, ShapeSpec("d", 24, 1,
                                                       "decode")))
    logits = None
    for pos in range(24):
        logits, caches = M.lm_decode_step(cfg, params, toks[:, pos:pos + 1],
                                          caches, jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(full_logits[:, -1], np.float32),
                               np.asarray(logits[:, 0], np.float32),
                               atol=2e-3)


def test_full_configs_param_counts():
    """Full configs instantiate abstractly with plausible param counts."""
    from repro.models import flops as F
    from repro.configs import get_config
    expected = {
        "yi-6b": (5.5e9, 7.5e9),
        "gemma-2b": (2.0e9, 3.2e9),
        "qwen1.5-4b": (3.0e9, 5.0e9),
        "deepseek-v2-236b": (2.0e11, 2.6e11),
        "llama4-scout-17b-a16e": (4.0e10, 1.4e11),
        "hymba-1.5b": (1.2e9, 2.2e9),
        # our xLSTM blocks omit the 2x pre-up-projection (DESIGN.md §5):
        # ~75M for the "125m" geometry
        "xlstm-125m": (6.0e7, 2.2e8),
        "whisper-large-v3": (1.4e9, 2.0e9),
    }
    for arch, (lo, hi) in expected.items():
        n = F.total_params(get_config(arch))
        assert lo < n < hi, (arch, n)


@pytest.mark.parametrize("forget_logit", [0.0, -1.0])
def test_mlstm_full_chunk_stays_finite(forget_logit):
    """A 128-step mLSTM chunk with forget gates at or below 1/2: the
    intra-chunk decay spans e**-88 (e**-168 at logit -1), so it must be
    formed as one exponent, not exp(csum_i) * exp(-csum_j) — those
    factors flush to zero and overflow, and 0 * inf poisons the forward
    and its gradient with nans."""
    from repro.models.config import ArchConfig, SSMConfig
    from repro.models.params import init
    from repro.models.ssm import apply_mlstm, mlstm_specs
    cfg = ArchConfig(name="mlstm-chunk", family="ssm", n_layers=1,
                     d_model=64, n_heads=4, n_kv_heads=4, d_ff=0,
                     vocab_size=256, head_dim=16, rope="none",
                     ssm=SSMConfig(chunk=128), compute_dtype="float32",
                     param_dtype="float32")
    p = init(jax.random.PRNGKey(0), mlstm_specs(cfg))
    p["b_if"] = p["b_if"].at[:, 1].set(forget_logit)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64))
    y, grads = jax.value_and_grad(
        lambda p, x: jnp.sum(apply_mlstm(cfg, p, x) ** 2))(p, x)
    assert np.isfinite(float(y)) and float(y) > 0
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
