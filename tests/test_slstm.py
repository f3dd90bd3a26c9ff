"""The sLSTM time recurrence (``repro.models.ssm``): its written-out
backward against autodiff, the float32 sums of its recurrent weights'
gradients in a bfloat16 model, decode through the cache against the full
forward, and the named scopes the recurrent layers' loops carry."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ssm
from repro.models.config import ArchConfig, SSMConfig
from repro.models.params import init


def _cfg(compute_dtype="float32"):
    return ArchConfig(name="slstm", family="ssm", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=256,
                      head_dim=16, rope="none", ssm=SSMConfig(chunk=32),
                      compute_dtype=compute_dtype, param_dtype="float32")


def _setup(T, seed=0):
    cfg = _cfg()
    p = init(jax.random.PRNGKey(seed), ssm.slstm_specs(cfg))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, T, 64))
    tgt = jax.random.normal(jax.random.PRNGKey(seed + 2), (2, T, 64))
    return cfg, p, x, tgt


def _autodiff_slstm(cfg, p, x):
    """The same recurrence as a plain scan over ``_slstm_cell``,
    differentiated by JAX."""
    B, T, d = x.shape
    cd = x.dtype
    wx = jnp.einsum("btd,dhg->bthg", x, p["w"].astype(cd))

    def step(state, wx_t):
        new = ssm._slstm_cell(p["r"], p["b"], wx_t, state)
        return new, new[2]

    z = jnp.zeros((B, cfg.n_heads, d // cfg.n_heads), jnp.float32)
    final, hs = jax.lax.scan(step, (z, z, z.astype(cd), z),
                             wx.swapaxes(0, 1))
    out = hs.swapaxes(0, 1).reshape(B, T, d) @ p["wo"].astype(cd)
    return out, dict(zip("cnhm", final))


def test_written_backward_matches_autodiff():
    """float32 throughout, with cotangents on the output and on every
    part of the final state (a prefill's cache): the two backwards sum
    the same terms in other orders, so they agree to float32 round-off."""
    cfg, p, x, tgt = _setup(T=40)
    w = jax.random.normal(jax.random.PRNGKey(9), (4,))

    def loss(fn, p, x):
        out, st = fn(cfg, p, x)
        return jnp.sum(out * tgt) + sum(
            w[i] * jnp.sum(st[k].astype(jnp.float32))
            for i, k in enumerate("cnhm"))

    ours = jax.jit(jax.value_and_grad(
        lambda p, x: loss(lambda c, p, x: ssm.apply_slstm(
            c, p, x, return_state=True), p, x), argnums=(0, 1)))(p, x)
    auto = jax.jit(jax.value_and_grad(
        lambda p, x: loss(_autodiff_slstm, p, x), argnums=(0, 1)))(p, x)
    np.testing.assert_allclose(ours[0], auto[0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(ours[1]), jax.tree.leaves(auto[1])):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


def test_recurrent_weight_grads_sum_in_float32():
    """A bfloat16 layer over 1,024 steps against its float32 self.
    bfloat16 operands round each product to 2**-9, and the gradients of
    ``w`` and ``wo``, always summed in float32 outside the loop, differ
    from float32's by about 0.5% (relative norm of the difference).  The
    recurrent weight ``r`` and bias ``b`` must agree as well: their
    gradients summed in bfloat16 across the steps (an accumulator carried
    through the loop in the dtype ``r`` was cast to) differ by 3-4%.
    1.5% lies between, with room on both sides."""
    cfg, p, x, tgt = _setup(T=1024)

    def grads(compute_dtype):
        c = _cfg(compute_dtype)
        return jax.jit(jax.grad(lambda p: jnp.sum(ssm.apply_slstm(
            c, p, x.astype(c.compute_jdtype)).astype(jnp.float32) * tgt)))(p)

    with jax.default_matmul_precision("highest"):
        exact = grads("float32")
    low = grads("bfloat16")
    for k in ("r", "b", "w", "wo"):
        gap = float(jnp.linalg.norm((low[k] - exact[k]).ravel())
                    / jnp.linalg.norm(exact[k].ravel()))
        assert gap < 0.015, (k, gap)


@pytest.mark.parametrize("compute_dtype,tol", [
    ("float32", 1e-5),
    # the same cell runs both ways; bfloat16 rounds h again per step
    ("bfloat16", 2e-2)])
def test_decode_matches_the_full_forward(compute_dtype, tol):
    """Stepping ``apply_slstm_decode`` through the cache from zeros gives
    the full forward's outputs and its final state (the prefill cache)."""
    cfg = _cfg(compute_dtype)
    cd = cfg.compute_jdtype
    _, p, x, _ = _setup(T=24)
    x = x.astype(cd)
    full, state = jax.jit(lambda p, x: ssm.apply_slstm(
        cfg, p, x, return_state=True))(p, x)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         ssm.slstm_cache_specs(cfg, 2))
    step = jax.jit(lambda p, x, c: ssm.apply_slstm_decode(cfg, p, x, c))
    outs = []
    for t in range(x.shape[1]):
        y, cache = step(p, x[:, t:t + 1], cache)
        outs.append(y)
    got = jnp.concatenate(outs, axis=1).astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(full.astype(jnp.float32))))
    np.testing.assert_allclose(got, full.astype(jnp.float32),
                               atol=tol * scale)
    for k in "cnhm":
        assert cache[k].dtype == state[k].dtype
        np.testing.assert_allclose(cache[k].astype(jnp.float32),
                                   state[k].astype(jnp.float32),
                                   atol=tol * max(1.0, float(jnp.max(
                                       jnp.abs(state[k].astype(
                                           jnp.float32))))))


def test_recurrences_carry_their_named_scopes():
    """Every loop of the recurrent layers, forward and backward, carries
    ``repro.ssm.mlstm`` or ``repro.ssm.slstm`` in its ops' metadata, and
    the sLSTM backward contracts its recurrent weight gradient over time
    outside its loop."""
    cfg, ps, x, _ = _setup(T=16)
    pm = init(jax.random.PRNGKey(5), ssm.mlstm_specs(_cfg()))

    def f(ps, pm, x):
        y = ssm.apply_mlstm(cfg, pm, x)
        y = jax.checkpoint(lambda p, y: ssm.apply_slstm(cfg, p, y))(ps, y)
        return jnp.sum(y * y)

    text = jax.jit(jax.grad(f, argnums=(0, 1))).lower(ps, pm, x) \
        .compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("repro.ssm.mlstm", "repro.ssm.slstm"):
        loops = {n for n in names if scope in n and "/while" in n}
        assert any("transpose(" in n for n in loops), scope
        assert any("transpose(" not in n for n in loops), scope
    assert any("repro.ssm.slstm/tbhk,tbhg->hkg" in n and "/while" not in n
               for n in names)
