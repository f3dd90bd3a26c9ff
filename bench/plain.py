"""Plain numerics for the references: float32 at matmul precision
"highest", or the control's per-tensor-scaled float8 (e4m3) operands.

Nothing here imports the program.  A reference routes every matmul
through :meth:`Numerics.einsum`; everything else (norms, gates, softmax,
the loss, the optimizer) stays float32 in both modes, so the control
differs from the reference only in the precision of its matmuls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _fp8_round(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under one absmax scale per tensor (the way
    fp8 training scales its operands), and back to float32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def fp8(x: jax.Array) -> jax.Array:
    """float8 operand: rounded forward, its cotangent rounded backward."""
    return _fp8_round(x)


fp8.defvjp(lambda x: (_fp8_round(x), None), lambda _, g: (_fp8_round(g),))


class Numerics:
    """``mode`` is ``"f32"`` (the reference) or ``"fp8"`` (the control)."""

    def __init__(self, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown numerics {mode!r}")
        self.mode = mode

    def einsum(self, spec: str, *ops: jax.Array) -> jax.Array:
        ops = [o.astype(jnp.float32) for o in ops]
        if self.mode == "fp8":
            ops = [fp8(o) for o in ops]
        return jnp.einsum(spec, *ops, precision=HIGHEST,
                          preferred_element_type=jnp.float32)


def layernorm(p: dict, x: jax.Array) -> jax.Array:
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]


def token_sum_ce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Cross-entropy summed over tokens (so microbatch sums add up)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def int8_qdq(x: jax.Array, block: int = 64) -> jax.Array:
    """Blockwise absmax int8 round trip over the flattened tensor: what
    crosses a stage boundary (quantize on send, dequantize on receipt)."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    q = jnp.clip(jnp.round(blocks / jnp.maximum(amax, 1e-12) * 127.0),
                 -127, 127)
    out = (q * amax / 127.0).reshape(-1)
    return out[:out.shape[0] - pad].reshape(x.shape)


def adamw_update(g, m, v, count, p, hp: dict):
    """One AdamW step on float32 leaves (no clipping); returns the new
    (param, m, v).  ``count`` is the step number after this one."""
    b1, b2 = hp["b1"], hp["b2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** count)
    vh = v / (1 - b2 ** count)
    upd = -hp["lr"] * (mh / (jnp.sqrt(vh) + hp["eps"])
                       + hp["weight_decay"] * p)
    return p + upd, m, v
