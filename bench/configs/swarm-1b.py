"""Plain float32 reference of a swarm-1b pipeline stage.

The SWARM paper's own model (arXiv:2301.11913, section 4.3): a pre-norm
Transformer whose three pipeline stages each apply one shared layer
(ALBERT-style) n_layers / n_stages times.  A layer is layernorm, causal
multi-head attention with rotary embeddings (rotate-half convention,
theta ``rope_theta``), a residual, layernorm, a GeGLU feed-forward
(tanh-approximated gelu of the gate times the up projection) and a
residual.  The last stage adds a final layernorm and an untied head.
Attention is the plain softmax over the full score matrix; each layer
application is rematerialized so the backward fits one chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import plain
from bench.weights import Init, fan_in_std


def _reps(c: dict, n_stages: int) -> int:
    return c["n_layers"] // n_stages


def param_specs(c: dict, n_stages: int, stage: int) -> dict:
    d, h, kv, hd, f, v = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                          c["head_dim"], c["d_ff"], c["vocab_size"])
    norm = {"scale": Init((1, d), "ones"), "bias": Init((1, d), "zeros")}
    layer = {
        "ln1": dict(norm),
        "attn": {"wq": Init((1, d, h, hd), std=fan_in_std(d)),
                 "wk": Init((1, d, kv, hd), std=fan_in_std(d)),
                 "wv": Init((1, d, kv, hd), std=fan_in_std(d)),
                 "wo": Init((1, h, hd, d), std=fan_in_std(h * hd))},
        "ln2": dict(norm),
        "mlp": {"wi_gate": Init((1, d, f), std=fan_in_std(d)),
                "wi_up": Init((1, d, f), std=fan_in_std(d)),
                "wo": Init((1, f, d), std=fan_in_std(f))},
    }
    specs = {"blocks": [layer]}
    if stage == 0:
        specs["embed"] = Init((v, d), "embed", 1.0)
    if stage == n_stages - 1:
        specs["final_norm"] = {"scale": Init((d,), "ones"),
                               "bias": Init((d,), "zeros")}
        specs["head"] = Init((d, v), std=fan_in_std(d))
    return specs


def _rope(x, theta: float):
    t, dim = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(num, c: dict, p: dict, x):
    a = p["attn"]
    y = plain.layernorm(p["ln1"], x)
    q = _rope(num.einsum("bsd,dhk->bshk", y, a["wq"]), c["rope_theta"])
    k = _rope(num.einsum("bsd,dhk->bshk", y, a["wk"]), c["rope_theta"])
    v = num.einsum("bsd,dhk->bshk", y, a["wv"])
    rep = c["n_heads"] // c["n_kv_heads"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = num.einsum("bqhd,bkhd->bhqk", q, k) * c["head_dim"] ** -0.5
    t = x.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = num.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + num.einsum("bshk,hkd->bsd", o, a["wo"])
    m = p["mlp"]
    y = plain.layernorm(p["ln2"], x)
    gate = jax.nn.gelu(num.einsum("bsd,df->bsf", y, m["wi_gate"]))
    up = num.einsum("bsd,df->bsf", y, m["wi_up"])
    return x + num.einsum("bsf,fd->bsd", gate * up, m["wo"])


def stage_forward(num, c: dict, n_stages: int, stage: int, params: dict,
                  x):
    """Stage input (token ids for stage 0) to its output hidden states."""
    if stage == 0:
        x = params["embed"][x]
    x = x.astype(jnp.float32)
    p = jax.tree.map(lambda a: a[0], params["blocks"][0])
    layer = jax.checkpoint(lambda x: _layer(num, c, p, x))
    x, _ = jax.lax.scan(lambda x, _: (layer(x), None), x, None,
                        length=_reps(c, n_stages))
    return x


def stage_loss(num, c: dict, n_stages: int, stage: int, params: dict, x,
               labels):
    """Token-sum cross-entropy of the last stage."""
    y = plain.layernorm(params["final_norm"],
                        stage_forward(num, c, n_stages, stage, params, x))
    return plain.token_sum_ce(num.einsum("btd,dv->btv", y, params["head"]),
                              labels)
