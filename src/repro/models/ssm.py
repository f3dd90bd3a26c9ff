"""Recurrent sequence mixers: Mamba selective SSM, xLSTM (mLSTM + sLSTM).

Training paths are chunkwise-parallel (memory O(chunk), FLOPs linear in T);
decode paths are O(1)-state single-step recurrences — this is what makes the
``long_500k`` shape servable for the ssm/hybrid architectures.  The mLSTM
chunk scan and the sLSTM time recurrence (forward and backward) run inside
the named scopes ``repro.ssm.mlstm`` and ``repro.ssm.slstm``, which the
compiled ops carry in their metadata (``repro.obs``).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.params import ParamSpec
from repro.models.probe import probe_enabled

Tree = Any


# ================================================================ Mamba
def mamba_specs(cfg: ArchConfig, d: int | None = None) -> Tree:
    s = cfg.ssm
    d = d or cfg.d_model
    di = s.expand * d
    dtr = s.dt_rank or -(-d // 16)
    pd = cfg.param_jdtype
    return {
        "w_in": ParamSpec((d, 2 * di), pd, axes=("embed", "mlp")),
        "conv_w": ParamSpec((s.conv_kernel, di), pd, axes=("conv", "mlp")),
        "conv_b": ParamSpec((di,), pd, "zeros", ("mlp",)),
        "w_x": ParamSpec((di, dtr + 2 * s.state_dim), pd, axes=("mlp", "state")),
        "w_dt": ParamSpec((dtr, di), pd, axes=("state", "mlp")),
        "b_dt": ParamSpec((di,), pd, "zeros", ("mlp",)),
        "a_log": ParamSpec((di, s.state_dim), jnp.float32, "zeros",
                           ("mlp", "state")),
        "d_skip": ParamSpec((di,), jnp.float32, "ones", ("mlp",)),
        "w_out": ParamSpec((di, d), pd, axes=("mlp", "embed")),
    }


def _causal_conv(u: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. u [B, T, C], w [K, C]."""
    K = w.shape[0]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(u)
    for k in range(K):
        out = out + up[:, k:k + u.shape[1]] * w[k]
    return out + b


def _mamba_inner(cfg, p, x):
    """Shared pre-processing: returns (u, z, dt, Bm, Cm, A)."""
    s, cd = cfg.ssm, x.dtype
    d = x.shape[-1]
    di = s.expand * d
    dtr = s.dt_rank or -(-d // 16)
    uz = x @ p["w_in"].astype(cd)
    u, z = uz[..., :di], uz[..., di:]
    return u, z, dtr, di


def apply_mamba(cfg: ArchConfig, p: Tree, x: jax.Array,
                return_state: bool = False):
    """Training path. x [B, T, d] -> [B, T, d] (opt. final decode state)."""
    s, cd = cfg.ssm, x.dtype
    B, T, d = x.shape
    u, z, dtr, di = _mamba_inner(cfg, p, x)
    u_raw = u
    u = jax.nn.silu(_causal_conv(u, p["conv_w"].astype(cd),
                                 p["conv_b"].astype(cd)))
    xp = u @ p["w_x"].astype(cd)
    dt_lr, Bm, Cm = (xp[..., :dtr], xp[..., dtr:dtr + s.state_dim],
                     xp[..., dtr + s.state_dim:])
    dt = jax.nn.softplus(dt_lr @ p["w_dt"].astype(cd)
                         + p["b_dt"].astype(cd)).astype(jnp.float32)
    A = -jnp.exp(p["a_log"])                                  # [di, N]

    c = T if probe_enabled() else min(s.chunk, T)
    nc = T // c
    assert nc * c == T, (T, c)

    def chunk_step(h, args):
        uc, dtc, Bc, Cc = args   # [B, c, ...]
        # decay factors a [B, c, di, N], inputs bx [B, c, di, N]
        a = jnp.exp(dt[..., None][:, 0:0] if False else
                    (dtc[..., None] * A))                     # [B,c,di,N]
        bx = (dtc * uc.astype(jnp.float32))[..., None] * \
            Bc.astype(jnp.float32)[:, :, None, :]             # [B,c,di,N]

        def combine(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a2 * a1, a2 * b1 + b2
        a_acc, h_in = jax.lax.associative_scan(combine, (a, bx), axis=1)
        hs = a_acc * h[:, None] + h_in                        # [B,c,di,N]
        y = jnp.einsum("bcdn,bcn->bcd", hs, Cc.astype(jnp.float32))
        return hs[:, -1], y

    resh = lambda t: t.reshape(B, nc, c, *t.shape[2:]).swapaxes(0, 1)
    h0 = jnp.zeros((B, di, s.state_dim), jnp.float32)
    # nested remat: keep only the O(B*di*N) carry per chunk in backward —
    # without it the [B,c,di,N] discretized tensors of every chunk persist.
    h_last, ys = jax.lax.scan(jax.checkpoint(chunk_step), h0,
                              (resh(u), resh(dt), resh(Bm), resh(Cm)))
    y = ys.swapaxes(0, 1).reshape(B, T, di)
    y = (y + u.astype(jnp.float32) * p["d_skip"]).astype(cd)
    y = y * jax.nn.silu(z)
    out = y @ p["w_out"].astype(cd)
    if return_state:
        K = s.conv_kernel
        tail = jnp.pad(u_raw, ((0, 0), (max(0, K - 1 - T), 0), (0, 0))
                       )[:, -(K - 1):]
        return out, {"h": h_last,
                     "conv": tail.astype(cfg.compute_jdtype)}
    return out


def mamba_cache_specs(cfg: ArchConfig, batch: int, d: int | None = None) -> Tree:
    s = cfg.ssm
    d = d or cfg.d_model
    di = s.expand * d
    return {
        "h": ParamSpec((batch, di, s.state_dim), jnp.float32, "zeros",
                       ("batch", "mlp", "state")),
        "conv": ParamSpec((batch, s.conv_kernel - 1, di), cfg.compute_jdtype,
                          "zeros", ("batch", "conv", "mlp")),
    }


def apply_mamba_decode(cfg: ArchConfig, p: Tree, x: jax.Array, cache: Tree):
    """One-step decode. x [B, 1, d]."""
    s, cd = cfg.ssm, x.dtype
    B = x.shape[0]
    u, z, dtr, di = _mamba_inner(cfg, p, x)
    u, z = u[:, 0], z[:, 0]
    # conv over cached tail + current
    tail = cache["conv"].astype(cd)                           # [B, K-1, di]
    window = jnp.concatenate([tail, u[:, None]], axis=1)      # [B, K, di]
    uc = jax.nn.silu(jnp.einsum("bkc,kc->bc", window, p["conv_w"].astype(cd))
                     + p["conv_b"].astype(cd))
    xp = uc @ p["w_x"].astype(cd)
    dt_lr, Bm, Cm = (xp[..., :dtr], xp[..., dtr:dtr + s.state_dim],
                     xp[..., dtr + s.state_dim:])
    dt = jax.nn.softplus(dt_lr @ p["w_dt"].astype(cd)
                         + p["b_dt"].astype(cd)).astype(jnp.float32)
    A = -jnp.exp(p["a_log"])
    a = jnp.exp(dt[..., None] * A)                            # [B, di, N]
    h = a * cache["h"] + (dt * uc.astype(jnp.float32))[..., None] \
        * Bm.astype(jnp.float32)[:, None, :]
    y = jnp.einsum("bdn,bn->bd", h, Cm.astype(jnp.float32))
    y = (y + uc.astype(jnp.float32) * p["d_skip"]).astype(cd)
    y = y * jax.nn.silu(z)
    out = (y @ p["w_out"].astype(cd))[:, None]
    new_cache = {"h": h, "conv": window[:, 1:].astype(cache["conv"].dtype)}
    return out, new_cache


# ================================================================ mLSTM
# Matrix-memory LSTM == decay-gated linear attention; the normalizer n is
# folded in as an extra value column of ones.
def mlstm_specs(cfg: ArchConfig) -> Tree:
    d, H, pd = cfg.d_model, cfg.n_heads, cfg.param_jdtype
    hd = d // H
    return {
        "wq": ParamSpec((d, H, hd), pd, axes=("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, H, hd), pd, axes=("embed", "heads", "head_dim")),
        "wv": ParamSpec((d, H, hd), pd, axes=("embed", "heads", "head_dim")),
        "w_if": ParamSpec((d, H, 2), pd, "zeros", ("embed", "heads", "null")),
        "b_if": ParamSpec((H, 2), pd, "zeros", ("heads", "null")),
        "w_og": ParamSpec((d, d), pd, axes=("embed", "embed2")),
        "wo": ParamSpec((H, hd, d), pd, axes=("heads", "head_dim", "embed")),
    }


def apply_mlstm(cfg: ArchConfig, p: Tree, x: jax.Array,
                return_state: bool = False):
    """Chunkwise-parallel mLSTM. x [B, T, d]."""
    cd = x.dtype
    B, T, d = x.shape
    H = cfg.n_heads
    hd = d // H
    c = T if probe_enabled() else min(cfg.ssm.chunk if cfg.ssm else 128, T)
    nc = T // c
    assert nc * c == T

    q = jnp.einsum("btd,dhk->bthk", x, p["wq"].astype(cd)) * hd ** -0.5
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"].astype(cd)) * hd ** -0.5
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"].astype(cd))
    v = jnp.concatenate([v, jnp.ones((B, T, H, 1), cd)], -1)  # normalizer col
    gates = jnp.einsum("btd,dhg->bthg", x, p["w_if"].astype(cd)) \
        + p["b_if"].astype(cd)
    logi = gates[..., 0].astype(jnp.float32)                  # [B,T,H]
    logf = jax.nn.log_sigmoid(gates[..., 1].astype(jnp.float32))

    resh = lambda t: t.reshape(B, nc, c, *t.shape[2:]).swapaxes(0, 1)
    qc, kc, vc = resh(q), resh(k), resh(v)
    lic, lfc = resh(logi), resh(logf)

    def chunk_step(carry, args):
        C_in, m_in = carry          # [B,H,hd,hd+1], [B,H]
        qb, kb, vb, li, lf = args
        csum = jnp.cumsum(lf, axis=1)                         # [B,c,H]
        total = csum[:, -1]
        # stabilizer: running max of (csum_i + max future contribution)
        m_intra = jnp.max(li - csum, axis=1)                  # [B,H]
        m_new = jnp.maximum(m_in + total, m_intra + total)
        # inter-chunk: y_inter_i = (q_i * exp(csum_i + m_in - m_new')) C_in
        # use per-chunk stabilizer m_new for all positions (safe: exps <= 1)
        d_q = jnp.exp(csum + (m_in - m_new)[:, None])         # [B,c,H]
        y_inter = jnp.einsum("bihk,bhkv,bih->bihv", qb, C_in, d_q)
        # intra-chunk: score_ij = q_i k_j exp(csum_i - csum_j + li_j - m_new)
        s = jnp.einsum("bihk,bjhk->bhij", qb, kb)
        # the exponent is formed whole and masked before exp: factored as
        # exp(csum_i) * exp(li_j - csum_j - m_new), a 128-step chunk of
        # forget gates near 1/2 puts the two factors at e**-88 and e**88,
        # which flush to zero and overflow to inf (0 * inf = nan)
        cs = csum.transpose(0, 2, 1)                          # [B,H,c]
        lk = (li - csum).transpose(0, 2, 1) - m_new[:, :, None]
        mask = jnp.tril(jnp.ones((c, c), bool))
        dmat = jnp.exp(jnp.where(mask, cs[..., :, None] + lk[..., None, :],
                                 -jnp.inf))                   # [B,H,i,j]
        s = s * dmat
        y_intra = jnp.einsum("bhij,bjhv->bihv", s.astype(cd), vb)
        y = y_inter.astype(jnp.float32) + y_intra.astype(jnp.float32)
        # state update: C' = exp(total + m_in - m_new) C_in + sum_j gk'_j k_j v_j
        gk_state = jnp.exp(li + (total[:, None] - csum) - m_new[:, None])
        C_new = jnp.exp(m_in + total - m_new)[:, :, None, None] * C_in + \
            jnp.einsum("bjhk,bjhv,bjh->bhkv", kb, vb, gk_state.astype(cd))
        return (C_new, m_new), y

    C0 = jnp.zeros((B, H, hd, hd + 1), jnp.float32)
    m0 = jnp.zeros((B, H), jnp.float32)
    with jax.named_scope("repro.ssm.mlstm"):
        (C_f, m_f), ys = jax.lax.scan(jax.checkpoint(chunk_step), (C0, m0),
                                      (qc, kc, vc, lic, lfc))
    y = ys.swapaxes(0, 1).reshape(B, T, H, hd + 1)
    num, den = y[..., :hd], y[..., hd:]
    y = num / jnp.maximum(jnp.abs(den), 1.0)
    og = jax.nn.silu(x @ p["w_og"].astype(cd))
    out = jnp.einsum("bthk,hkd->btd", y.astype(cd), p["wo"].astype(cd)) * og
    if return_state:
        return out, {"C": C_f, "m": m_f}
    return out


def mlstm_cache_specs(cfg: ArchConfig, batch: int) -> Tree:
    H = cfg.n_heads
    hd = cfg.d_model // H
    return {
        "C": ParamSpec((batch, H, hd, hd + 1), jnp.float32, "zeros",
                       ("batch", "heads", "head_dim", "v_dim")),
        "m": ParamSpec((batch, H), jnp.float32, "zeros", ("batch", "heads")),
    }


def apply_mlstm_decode(cfg: ArchConfig, p: Tree, x: jax.Array, cache: Tree):
    cd = x.dtype
    B = x.shape[0]
    H = cfg.n_heads
    hd = cfg.d_model // H
    xt = x[:, 0]
    q = jnp.einsum("bd,dhk->bhk", xt, p["wq"].astype(cd)) * hd ** -0.5
    k = jnp.einsum("bd,dhk->bhk", xt, p["wk"].astype(cd)) * hd ** -0.5
    v = jnp.einsum("bd,dhk->bhk", xt, p["wv"].astype(cd))
    v = jnp.concatenate([v, jnp.ones((B, H, 1), cd)], -1)
    gates = jnp.einsum("bd,dhg->bhg", xt, p["w_if"].astype(cd)) \
        + p["b_if"].astype(cd)
    logi = gates[..., 0].astype(jnp.float32)
    logf = jax.nn.log_sigmoid(gates[..., 1].astype(jnp.float32))
    m_new = jnp.maximum(logf + cache["m"], logi)
    fp = jnp.exp(logf + cache["m"] - m_new)
    ip = jnp.exp(logi - m_new)
    C = fp[..., None, None] * cache["C"] + \
        ip[..., None, None] * jnp.einsum("bhk,bhv->bhkv", k, v
                                         ).astype(jnp.float32)
    y = jnp.einsum("bhk,bhkv->bhv", q.astype(jnp.float32), C)
    num, den = y[..., :hd], y[..., hd:]
    y = num / jnp.maximum(jnp.abs(den), 1.0)
    og = jax.nn.silu(xt @ p["w_og"].astype(cd))
    out = jnp.einsum("bhk,hkd->bd", y.astype(cd), p["wo"].astype(cd)) * og
    return out[:, None], {"C": C, "m": m_new}


# ================================================================ sLSTM
def slstm_specs(cfg: ArchConfig) -> Tree:
    d, H, pd = cfg.d_model, cfg.n_heads, cfg.param_jdtype
    hd = d // H
    return {
        "w": ParamSpec((d, H, 4 * hd), pd, axes=("embed", "heads", "head_dim")),
        "r": ParamSpec((H, hd, 4 * hd), pd, axes=("heads", "head_dim", "null")),
        "b": ParamSpec((H, 4 * hd), pd, "zeros", ("heads", "head_dim")),
        "wo": ParamSpec((d, d), pd, axes=("embed", "embed2")),
    }


def _slstm_gates(pre, c, n, m):
    """The sLSTM's pointwise step from its float32 pre-activations
    [B,H,4hd]; c, n, m [B,H,hd] float32.  Returns (c, n, h, m), h in
    float32."""
    zi, ii, fi, oi = jnp.split(pre, 4, axis=-1)
    z = jnp.tanh(zi)
    o = jax.nn.sigmoid(oi)
    logf = jax.nn.log_sigmoid(fi)
    m_new = jnp.maximum(logf + m, ii)
    ip = jnp.exp(ii - m_new)
    fp = jnp.exp(logf + m - m_new)
    c_new = fp * c + ip * z
    n_new = fp * n + ip
    h_new = o * c_new / jnp.maximum(n_new, 1.0)
    return c_new, n_new, h_new, m_new


def _slstm_pre(r, b, wx_t, h):
    """Float32 pre-activations of one step; the recurrent product takes
    ``h`` and ``r`` in the compute dtype."""
    rh = jnp.einsum("...hk,hkg->...hg", h, r.astype(h.dtype))
    return (wx_t.astype(jnp.float32) + rh.astype(jnp.float32)
            + b.astype(jnp.float32))


def _slstm_cell(r, b, wx_t, state):
    """One sLSTM step, shared by training and decode.  wx_t [B,H,4hd];
    state (c, n, h, m) each [B,H,hd], h in the compute dtype."""
    c, n, h, m = state
    c, n, h_new, m = _slstm_gates(_slstm_pre(r, b, wx_t, h), c, n, m)
    return c, n, h_new.astype(h.dtype), m


def _slstm_run(r, b, wx):
    """The forward recurrence over wx [T,B,H,4hd]: the final state and
    every step's state, each stacked [T,B,H,hd]."""
    _, B, H, g = wx.shape
    z = jnp.zeros((B, H, g // 4), jnp.float32)
    state0 = (z, z, z.astype(wx.dtype), z)
    r = r.astype(wx.dtype)              # once, not on every step

    def step(state, wx_t):
        new = _slstm_cell(r, b, wx_t, state)
        return new, new

    return jax.lax.scan(step, state0, wx)


@jax.custom_vjp
def _slstm_scan(r, b, wx):
    """(final state, hs [T,B,H,hd]) of the recurrence over wx [T,B,H,4hd].

    The backward is written out so that the gradients of the recurrent
    weights ``r`` and bias ``b`` are summed over time in float32 outside
    the loop, as one contraction of the step inputs with the
    pre-activation cotangents.  Left to autodiff, the loop carries their
    sum in the dtype ``r`` was cast to, so a bfloat16 model adds 2,048
    steps' terms in bfloat16."""
    return _slstm_scan_fwd(r, b, wx)[0]


def _slstm_scan_fwd(r, b, wx):
    with jax.named_scope("repro.ssm.slstm"):
        final, states = _slstm_run(r, b, wx)
    return (final, states[2]), (r, b, wx, states)


def _slstm_scan_bwd(res, cts):
    r, b, wx, states = res
    (dc, dn, dh, dm), dhs = cts
    f32, cd = jnp.float32, wx.dtype
    with jax.named_scope("repro.ssm.slstm"):
        # each step's input state: zeros, then the states the forward
        # left behind; the pre-activations again, all steps at once
        c, n, h, m = (jnp.concatenate([jnp.zeros_like(s[:1]), s[:-1]])
                      for s in states)
        pre = _slstm_pre(r, b, wx, h)
        rc = r.astype(cd)

        def step(carry, xs):
            dc, dn, dh, dm = carry
            pre_t, c_t, n_t, m_t, dh_out = xs
            _, pull = jax.vjp(_slstm_gates, pre_t, c_t, n_t, m_t)
            dpre, dc, dn, dm = pull((dc, dn, dh + dh_out, dm))
            dh = jnp.einsum("bhg,hkg->bhk", dpre.astype(cd), rc)
            return (dc, dn, dh.astype(f32), dm), dpre

        _, dpre = jax.lax.scan(
            step, (dc, dn, dh.astype(f32), dm),
            (pre, c, n, m, dhs.astype(f32)), reverse=True)
        dr = jnp.einsum("tbhk,tbhg->hkg", h.astype(f32), dpre)
        db = jnp.sum(dpre, axis=(0, 1))
    return dr.astype(r.dtype), db.astype(b.dtype), dpre.astype(cd)


_slstm_scan.defvjp(_slstm_scan_fwd, _slstm_scan_bwd)


def apply_slstm(cfg: ArchConfig, p: Tree, x: jax.Array,
                return_state: bool = False):
    """Sequential sLSTM (memory mixing forbids parallel scan). x [B,T,d]."""
    cd = x.dtype
    B, T, d = x.shape
    wx = jnp.einsum("btd,dhg->bthg", x, p["w"].astype(cd))
    (c, n, h, m), hs = _slstm_scan(p["r"], p["b"], wx.swapaxes(0, 1))
    y = hs.swapaxes(0, 1).reshape(B, T, d)
    out = y @ p["wo"].astype(cd)
    if return_state:
        return out, {"c": c, "n": n, "h": h, "m": m}
    return out


def slstm_cache_specs(cfg: ArchConfig, batch: int) -> Tree:
    H = cfg.n_heads
    hd = cfg.d_model // H
    f32 = jnp.float32
    mk = lambda dt: ParamSpec((batch, H, hd), dt, "zeros",
                              ("batch", "heads", "head_dim"))
    return {"c": mk(f32), "n": mk(f32), "h": mk(cfg.compute_jdtype),
            "m": mk(f32)}


def apply_slstm_decode(cfg: ArchConfig, p: Tree, x: jax.Array, cache: Tree):
    cd = x.dtype
    wx = jnp.einsum("bd,dhg->bhg", x[:, 0], p["w"].astype(cd))
    state = (cache["c"], cache["n"], cache["h"], cache["m"])
    c, n, h, m = _slstm_cell(p["r"], p["b"], wx, state)
    y = h.reshape(x.shape[0], -1) @ p["wo"].astype(cd)
    return y[:, None], {"c": c, "n": n, "h": h, "m": m}
