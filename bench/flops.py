"""Operation and byte counts, from shapes alone, and the table of peaks.

The per-token forward counts copy the repository's shape arithmetic
(``repro.models.flops``): a matmul of [m, k] x [k, n] is 2mnk operations,
causal attention attends to half its context on average, and training
costs three forwards (forward plus a backward of twice its cost).  Remat
recompute, the last stage's separate loss forward, codecs and the
optimizer are not model work and are not counted, so a change that removes
redundant work raises utilization honestly and no reading can pass 100%.

Kernel counts give the least work a kernel's call needs: the operations
it must do and the bytes it must move to and from HBM once.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


# ------------------------------------------------------------ per layer
def _attn_flops(c: dict, ctx: float) -> float:
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    proj = 2 * d * hd * (2 * h + 2 * kv)          # q, o: H; k, v: KV
    scores = 2 * 2 * ctx * h * hd                 # q.k and p.v
    return proj + scores


def _ffn_flops(c: dict) -> float:
    mults = 3 if c["act"] in ("swiglu", "geglu") else 2
    return 2 * mults * c["d_model"] * c["d_ff"]


def _mlstm_flops(c: dict) -> float:
    d, h = c["d_model"], c["n_heads"]
    hd = d // h
    chunk = c["ssm"]["chunk"]
    proj = 2 * d * (3 * h * hd + 2 * h) + 2 * d * d + 2 * h * hd * d
    intra = 4 * chunk * h * hd
    state = 6 * h * hd * (hd + 1)
    return proj + intra + state


def _slstm_flops(c: dict) -> float:
    d, h = c["d_model"], c["n_heads"]
    hd = d // h
    return 2 * d * 4 * d + 2 * h * hd * 4 * hd + 2 * d * d + 20 * d


def layer_flops(c: dict, kind: str, seq: int) -> float:
    """Forward operations for one token through one layer of ``kind``."""
    ctx = seq / 2 if c.get("causal", True) else seq
    if kind == "attn":
        return _attn_flops(c, ctx) + _ffn_flops(c)
    if kind == "mlstm":
        return _mlstm_flops(c)
    if kind == "slstm":
        return _slstm_flops(c)
    raise KeyError(f"no operation count for layer kind {kind!r}")


def head_flops(c: dict) -> float:
    return 2.0 * c["d_model"] * c["vocab_size"]


def layer_kinds(c: dict) -> list:
    """Every layer application of the whole model, in order."""
    if c.get("block_pattern"):
        return list(c["block_pattern"])
    return ["attn"] * c["n_layers"]


def stage_kinds(c: dict, n_stages: int, stage: int) -> list:
    """The layer applications of one of ``n_stages`` even pipeline stages
    (an ALBERT-shared stage applies its one layer group n_layers/S times)."""
    kinds = layer_kinds(c)
    per = len(kinds) // n_stages
    return kinds[stage * per:(stage + 1) * per]


def stage_forward_flops(c: dict, n_stages: int, stage: int,
                        seq: int) -> float:
    """Forward operations per token of one stage; the last owns the head."""
    f = sum(layer_flops(c, k, seq) for k in stage_kinds(c, n_stages, stage))
    if stage == n_stages - 1:
        f += head_flops(c)
    return f


def train_flops_per_token(c: dict, n_stages: int, stages, seq: int) -> float:
    """Model operations per trained token over the ``stages`` a cell runs:
    three forwards each (no remat, no separate loss forward)."""
    return 3.0 * sum(stage_forward_flops(c, n_stages, s, seq)
                     for s in stages)


# ------------------------------------------------------------ kernels
def flash_fwd_cost(batch: int, heads: int, seq: int, head_dim: int,
                   causal: bool = True, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one flash-attention forward with its
    log-sum-exp: q.k and p.v over the unmasked pairs only, q, k, v read
    and o written once, and an f32 lse per query."""
    pairs = seq * (seq + 1) / 2 if causal else seq * seq
    ops = 2 * 2 * batch * heads * head_dim * pairs
    nbytes = 4 * batch * seq * heads * head_dim * itemsize \
        + 4 * batch * heads * seq
    return ops, nbytes


def qdq_cost(n_elements: int) -> tuple:
    """(operations, bytes) of one blockwise int8 round trip over
    ``n_elements`` f32 values: read once, written once."""
    return 6.0 * n_elements, 8.0 * n_elements


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """(share in %, bound) of the least time the chip needs for the work
    over the time it took; bound is "compute" or "bytes"."""
    t_ops = ops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_bytes else "bytes"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
