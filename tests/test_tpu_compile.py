"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at swarm-1b widths for one chip
of a described ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what the chip would refuse (unaligned blocks,
too much VMEM) and which interpret mode never checks.  Each compiled
program must contain the native kernel (``tpu_custom_call``).  The
kernel wrappers are called with ``interpret=False``: on a CPU host the
default would pick the interpreter.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports this file.
"""
import pytest

import jax
import jax.numpy as jnp

B, S, H, HD, D = 4, 1024, 32, 128, 4096       # swarm-1b, 4 x 1024 tokens
C, QB = 1024, 64                               # bottleneck wire, int8 block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def sds(topo):
    """``sds(shape, dtype)``: an argument placed on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_forward_with_lse(sds):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    q = sds((B, S, H, HD), jnp.bfloat16)
    # the block sizes models.layers.apply_attn trains with (512, 1024)
    text = _compile_text(
        lambda q, k, v: flash_attention_fwd(q, k, v, True, 0, None, 512,
                                            1024, False, True), q, q, q)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode,k", [("bottleneck", 1), ("maxout", 2)])
def test_boundary_encode_quantize(sds, mode, k):
    from repro.kernels.boundary import kernel as K
    x = sds((B, S, D), jnp.bfloat16)
    if mode == "bottleneck":
        w = sds((D, C), jnp.float32)
        fn = lambda x, w: K.encode_quantize(x, w, mode, k, QB,
                                            interpret=False)
        text = _compile_text(fn, x, w)
    else:
        fn = lambda x: K.encode_quantize(x, None, mode, k, QB,
                                         interpret=False)
        text = _compile_text(fn, x)
    assert "tpu_custom_call" in text


def test_boundary_dequantize_decode(sds):
    from repro.kernels.boundary import kernel as K
    q = sds((B, S, C), jnp.int8)
    s = sds((B, S, C // QB), jnp.float32)
    w = sds((C, D), jnp.float32)
    text = _compile_text(
        lambda q, s, w: K.dequantize_decode(q, s, w, "bottleneck", QB,
                                            jnp.bfloat16, interpret=False),
        q, s, w)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_int8_boundary_qdq(sds, dtype):
    """The int8 wire round trip every hop of an int8-boundary swarm
    applies (``runtime.base.wire_fwd_codec`` under kernels="pallas")."""
    from repro.kernels.boundary import kernel as K
    x = sds((B, S, D), dtype)
    text = _compile_text(lambda x: K.qdq_flat(x, QB, interpret=False), x)
    assert "tpu_custom_call" in text


def test_wire_quant_row_qdq(sds):
    """Row-blocked QDQ over the trailing dim (the learned codec's
    ``wire_quant`` path) at d 4096."""
    from repro.kernels.boundary import kernel as K
    x = sds((B, S, D), jnp.bfloat16)
    text = _compile_text(lambda x: K.qdq(x, QB, interpret=False), x)
    assert "tpu_custom_call" in text


def test_quant8_quantize_dequantize(sds):
    from repro.kernels.quant8 import kernel as K
    n = B * S * D
    flat = sds((n,), jnp.bfloat16)
    text = _compile_text(lambda x: K.quantize(x, QB, False), flat)
    assert "tpu_custom_call" in text
    q = sds((n // QB, QB), jnp.int8)
    s = sds((n // QB, 1), jnp.float32)
    text = _compile_text(
        lambda q, s: K.dequantize(q, s, jnp.bfloat16, False), q, s)
    assert "tpu_custom_call" in text


def test_rmsnorm(sds):
    from repro.kernels.rmsnorm.kernel import rmsnorm
    x = sds((B, S, D), jnp.bfloat16)
    scale = sds((D,), jnp.float32)
    text = _compile_text(lambda x, s: rmsnorm(x, s, 1e-6, False), x, scale)
    assert "tpu_custom_call" in text
