"""The two host paths that run once per hop or per step as one jitted
program each: the Pallas int8 wire round trip and the barrier's optimizer
step.  Each must give what the eager math gives and compile once per
shape, not once per call."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adamw, delayed_parameter_updates, mean_step
from repro.runtime import base


def _tree(key):
    k = jax.random.split(key, 3)
    return {"a": jax.random.normal(k[0], (4, 96), jnp.float32),
            "b": jax.random.normal(k[1], (7, 33)).astype(jnp.bfloat16),
            "ids": jnp.arange(5, dtype=jnp.int32)}


def test_kernel_wire_round_trip_is_one_program_per_shape():
    from repro.kernels.boundary.ops import int8_roundtrip
    fn = base._kernel_roundtrip_tree
    before = fn._cache_size()
    for i in range(3):
        tree = _tree(jax.random.PRNGKey(i))
        out = base._int8_roundtrip_tree(tree, 64, True)
        for name in ("a", "b"):
            want = int8_roundtrip(tree[name], 64, 64, True)
            assert out[name].dtype == tree[name].dtype
            np.testing.assert_array_equal(np.asarray(out[name]),
                                          np.asarray(want))
        np.testing.assert_array_equal(np.asarray(out["ids"]),
                                      np.asarray(tree["ids"]))
    assert fn._cache_size() == before + 1
    base._int8_roundtrip_tree({"a": jnp.ones((2, 64))}, 64, True)
    assert fn._cache_size() == before + 2


@pytest.mark.parametrize("clip", [1.0, 0.0])
@pytest.mark.parametrize("delayed", [False, True])
def test_mean_step_is_the_eager_step_in_one_program(clip, delayed):
    opt = adamw(lr=1e-2, grad_clip=clip)
    if delayed:
        opt = delayed_parameter_updates(opt)
    params = {k: v for k, v in _tree(jax.random.PRNGKey(7)).items()
              if k != "ids"}
    step = mean_step(opt)
    p_eager, s_eager = params, opt.init(params)
    p_jit, s_jit = params, opt.init(params)
    for i, tokens in enumerate((96, 128, 100)):
        grads = [jax.tree.map(
            lambda p, j=j: jax.random.normal(
                jax.random.PRNGKey(10 * i + j), p.shape, jnp.float32),
            params) for j in range(2)]
        g = jax.tree.map(lambda a, b: (a + b) / tokens, *grads)
        upd, s_eager = opt.update(g, s_eager, p_eager)
        p_eager = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                               p_eager, upd)
        p_jit, s_jit = step(grads, float(tokens), p_jit, s_jit)
    for name in params:
        assert p_jit[name].dtype == params[name].dtype
        # fused and op-by-op float32 math differ in the last bit only
        np.testing.assert_allclose(
            np.asarray(p_jit[name], np.float32),
            np.asarray(p_eager[name], np.float32), rtol=1e-6, atol=1e-7)
    # the token count is traced, so three counts share one program
    assert step._cache_size() == 1
