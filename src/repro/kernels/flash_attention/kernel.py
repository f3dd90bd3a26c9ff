"""Pallas TPU kernel: flash attention forward (FlashAttention-2 schedule).

TPU mapping (vs the CUDA original — see DESIGN.md §3 hardware adaptation):
  * grid = (B*KV*G, nq, nk); the innermost ``nk`` axis iterates key blocks
    for a fixed query block, so the (m, l, acc) online-softmax state lives
    in VMEM scratch that persists across ``nk`` steps — the TPU analogue of
    FA2's per-CTA registers.
  * BlockSpec tiles: q [1, BQ, D], k/v [1, BK, D] with BQ/BK multiples of
    the (8,128) VPU layout and D = head_dim (128-aligned in every assigned
    arch); the two matmuls per tile hit the MXU at [BQ,D]x[D,BK] and
    [BQ,BK]x[BK,Dv].
  * causal masking via block-level position arithmetic (fully-masked key
    blocks still execute — Pallas grids are static; the Splash-style
    skip is a further optimization, noted in EXPERIMENTS.md §Perf).

GQA is handled by flattening (B, KV, G) into the leading grid axis and
indexing k/v with ``h // G``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 256
DEFAULT_BK = 512


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                      scale, causal, window, nk, bq, bk, sq, sk,
                      lse_ref=None):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0]                                   # [bq, D]
    k = k_ref[0]                                   # [bk, D]
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]

    qpos = (sk - sq) + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = kpos < sk
    if causal:
        ok &= qpos >= kpos
    if window > 0:
        ok &= (qpos - kpos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_sc[...]
                    / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:     # log-sum-exp residual for the backward
            lse_ref[0] = m_sc[...] + jnp.log(jnp.maximum(l_sc[...], 1e-30))


def _flash_fwd_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref,
                          m_sc, l_sc, acc_sc, **kw):
    _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
                      lse_ref=lse_ref, **kw)


@functools.partial(jax.jit,
                   static_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_fwd(q, k, v, causal=True, window=0, scale=None,
                        block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                        interpret=None, with_lse: bool = False):
    """q [B,Sq,H,D], k/v [B,Sk,KV,Dv] -> [B,Sq,H,Dv].

    ``interpret=None`` auto-detects the backend (interpret mode only off
    TPU/GPU).  ``with_lse=True`` additionally returns the per-query
    log-sum-exp ``[B, KV, G, Sq]`` — the residual the FlashAttention-2
    backward (``repro.models.flash._flash_bwd``) recomputes tiles from.
    """
    from repro.kernels.backend import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    scale = D ** -0.5 if scale is None else scale

    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (Sq + pq) // bq, (Sk + pk) // bk

    # [B*H, S, D] views; kv indexed by h // G
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq + pq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk + pk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk + pk, Dv)

    body = _flash_fwd_kernel_lse if with_lse else _flash_fwd_kernel
    kernel = functools.partial(
        body, scale=scale, causal=causal, window=window,
        nk=nk, bq=bq, bk=bk, sq=Sq, sk=Sk)

    out_specs = pl.BlockSpec((1, bq, Dv), lambda h, qi, ki: (h, qi, 0))
    out_shape = jax.ShapeDtypeStruct((B * H, Sq + pq, Dv), v.dtype)
    if with_lse:
        # lse rides as [B*H, Sq, 1]: a (bq, 1) block keeps the TPU's
        # (8, 128) tiling rule (a trailing dim equal to the array's own)
        out_specs = [out_specs,
                     pl.BlockSpec((1, bq, 1), lambda h, qi, ki: (h, qi, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B * H, Sq + pq, 1), jnp.float32)]

    res = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda h, qi, ki, _G=G: (h // _G, ki, 0)),
            pl.BlockSpec((1, bk, Dv),
                         lambda h, qi, ki, _G=G: (h // _G, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max m
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom l
            pltpu.VMEM((bq, Dv), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = res[0] if with_lse else res
    out = out.reshape(B, H, Sq + pq, Dv).transpose(0, 2, 1, 3)[:, :Sq]
    if not with_lse:
        return out
    # [B*H, Sq] -> [B, KV, G, Sq]: H splits as (KV, G) with h = kv*G + g,
    # matching the jnp oracle's lse layout (models.flash._flash_fwd_impl)
    lse = res[1].reshape(B, KV, G, Sq + pq)[..., :Sq]
    return out, lse
