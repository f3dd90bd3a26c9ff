"""Plain float32 reference of xlstm-125m's pipeline stages.

Follows the xLSTM paper (arXiv:2405.04517) as the repository states it,
with these departures, all of them the program's own and noted here:

* each stage is 5 mLSTM blocks then 1 sLSTM block (the paper's mix is
  7:1); blocks are pre-layernorm residual, without the paper's
  up-projection and causal convolution;
* mLSTM: q, k scaled by head_dim**-0.5, exponential input gate, sigmoid
  forget gate, output gate silu(x W_og); the normalizer is
  max(|n.q|, exp(m)), where the stabilizer m is held per chunk of
  ``ssm.chunk`` positions: m_k = L_e + max(0, max_{j<=e}(i_j - L_j)), e the
  chunk's last position and L the running sum of log forget gates;
* sLSTM: exponential input gate, recurrent weights per head, no group
  norm, h = o * c / max(n, 1).

The mLSTM is written in its quadratic (parallel) form and the sLSTM as a
plain scan.  The parameter tree matches the program's layout leaf for
leaf, so gradients and steps compare by path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import plain
from bench.flops import stage_kinds
from bench.weights import Init, fan_in_std


def _runs(kinds: list) -> list:
    runs = []
    for k in kinds:
        if runs and runs[-1][0] == k:
            runs[-1][1] += 1
        else:
            runs.append([k, 1])
    return [tuple(r) for r in runs]


def _norm_specs(d: int, n: int) -> dict:
    return {"scale": Init((n, d), "ones"), "bias": Init((n, d), "zeros")}


def _layer_specs(c: dict, kind: str, n: int) -> dict:
    d, h = c["d_model"], c["n_heads"]
    hd = d // h
    if kind == "mlstm":
        cell = {
            "wq": Init((n, d, h, hd), std=fan_in_std(d)),
            "wk": Init((n, d, h, hd), std=fan_in_std(d)),
            "wv": Init((n, d, h, hd), std=fan_in_std(d)),
            "w_if": Init((n, d, h, 2), "zeros"),
            "b_if": Init((n, h, 2), "zeros"),
            "w_og": Init((n, d, d), std=fan_in_std(d)),
            "wo": Init((n, h, hd, d), std=fan_in_std(h * hd)),
        }
    elif kind == "slstm":
        cell = {
            "w": Init((n, d, h, 4 * hd), std=fan_in_std(d)),
            "r": Init((n, h, hd, 4 * hd), std=fan_in_std(hd)),
            "b": Init((n, h, 4 * hd), "zeros"),
            "wo": Init((n, d, d), std=fan_in_std(d)),
        }
    else:
        raise KeyError(kind)
    return {"ln1": _norm_specs(d, n), "cell": cell}


def param_specs(c: dict, n_stages: int, stage: int) -> dict:
    d, v = c["d_model"], c["vocab_size"]
    specs = {"blocks": [_layer_specs(c, k, n) for k, n in
                        _runs(stage_kinds(c, n_stages, stage))]}
    if stage == 0:
        specs["embed"] = Init((v, d), "embed", 1.0)
    if stage == n_stages - 1:
        specs["final_norm"] = {"scale": Init((d,), "ones"),
                               "bias": Init((d,), "zeros")}
        if not c.get("tie_embeddings") or stage != 0:
            specs["head"] = Init((d, v), std=fan_in_std(d))
    return specs


def _mlstm(num, c: dict, p: dict, x):
    b, t, d = x.shape
    h = c["n_heads"]
    hd = d // h
    chunk = min(c["ssm"]["chunk"], t)
    q = num.einsum("btd,dhk->bthk", x, p["wq"]) * hd ** -0.5
    k = num.einsum("btd,dhk->bthk", x, p["wk"]) * hd ** -0.5
    v = num.einsum("btd,dhk->bthk", x, p["wv"])
    g = num.einsum("btd,dhg->bthg", x, p["w_if"]) + p["b_if"]
    li = g[..., 0].transpose(0, 2, 1)                        # [B,H,T]
    lf = jax.nn.log_sigmoid(g[..., 1]).transpose(0, 2, 1)
    cum = jnp.cumsum(lf, axis=-1)                            # L_i
    causal = jnp.tril(jnp.ones((t, t), bool))
    dec = jnp.where(causal, cum[..., :, None] - cum[..., None, :]
                    + li[..., None, :], -jnp.inf)            # [B,H,i,j]
    a = jnp.max(dec, axis=-1)                                # per query
    w = jnp.exp(dec - a[..., None])
    s = num.einsum("bihk,bjhk->bhij", q, k) * w
    numer = num.einsum("bhij,bjhk->bihk", s, v)              # [B,T,H,hd]
    den = jnp.sum(s, axis=-1).transpose(0, 2, 1)             # [B,T,H]
    # the per-chunk stabilizer, read at each chunk's last position
    ends = (jnp.arange(t) // chunk + 1) * chunk - 1
    run = jax.lax.cummax(li - cum, axis=cum.ndim - 1)
    m = (cum + jnp.maximum(run, 0.0))[..., ends]             # [B,H,T]
    floor = jnp.exp(jnp.minimum(m - a, 80.0)).transpose(0, 2, 1)
    y = numer / jnp.maximum(jnp.abs(den), floor)[..., None]
    og = jax.nn.silu(num.einsum("btd,de->bte", x, p["w_og"]))
    return num.einsum("bthk,hkd->btd", y, p["wo"]) * og


def _slstm(num, c: dict, p: dict, x):
    b, t, d = x.shape
    h = c["n_heads"]
    hd = d // h
    wx = num.einsum("btd,dhg->bthg", x, p["w"])

    def step(state, wx_t):
        cc, n, hh, m = state
        pre = wx_t + num.einsum("bhk,hkg->bhg", hh, p["r"]) + p["b"]
        zi, ii, fi, oi = jnp.split(pre, 4, axis=-1)
        logf = jax.nn.log_sigmoid(fi)
        m_new = jnp.maximum(logf + m, ii)
        ip = jnp.exp(ii - m_new)
        fp = jnp.exp(logf + m - m_new)
        c_new = fp * cc + ip * jnp.tanh(zi)
        n_new = fp * n + ip
        h_new = jax.nn.sigmoid(oi) * c_new / jnp.maximum(n_new, 1.0)
        return (c_new, n_new, h_new, m_new), h_new

    z = jnp.zeros((b, h, hd), jnp.float32)
    _, hs = jax.lax.scan(step, (z, z, z, z), wx.swapaxes(0, 1))
    return num.einsum("btd,de->bte", hs.swapaxes(0, 1).reshape(b, t, d),
                      p["wo"])


_CELLS = {"mlstm": _mlstm, "slstm": _slstm}


def stage_forward(num, c: dict, n_stages: int, stage: int, params: dict,
                  x):
    """Stage input (token ids for stage 0) to its output hidden states."""
    if stage == 0:
        x = params["embed"][x]
    x = x.astype(jnp.float32)
    runs = _runs(stage_kinds(c, n_stages, stage))
    for (kind, n), blocks in zip(runs, params["blocks"]):
        def layer(x, p, _cell=_CELLS[kind]):
            return x + _cell(num, c, p["cell"], plain.layernorm(p["ln1"], x))
        layer = jax.checkpoint(layer)
        for i in range(n):
            x = layer(x, jax.tree.map(lambda a, _i=i: a[_i], blocks))
    return x


def stage_loss(num, c: dict, n_stages: int, stage: int, params: dict, x,
               labels):
    """Token-sum cross-entropy of the last stage."""
    y = plain.layernorm(params["final_norm"],
                        stage_forward(num, c, n_stages, stage, params, x))
    w = params["head"] if "head" in params else params["embed"].T
    return plain.token_sum_ce(num.einsum("btd,dv->btv", y, w), labels)
