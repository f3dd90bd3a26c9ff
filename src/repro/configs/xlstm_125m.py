"""xlstm-125m — sLSTM + mLSTM blocks [arXiv:2405.04517; unverified].

The published d_model (768), head count (4), vocab (50304) and context
(2048) with simplified blocks, about 75M parameters against the paper's
125M; the same departures as the plain reference
``bench/configs/xlstm-125m.py``:

* the model is two identical halves of 5 mLSTM blocks then 1 sLSTM block
  (12 blocks, 10:2), so the 2-stage SWARM pipeline has structurally
  identical stages; the paper's 125M model is 24 blocks mixed 7:1;
* blocks are pre-layernorm residual cells, d_ff=0: no up-projection or
  causal convolution around the mLSTM (it runs d_model wide in heads of
  192; the paper's runs twice as wide in heads of 384), no
  block-diagonal q/k/v or group norm, no gated feed-forward after the
  sLSTM;
* mLSTM: exponential input gate, sigmoid forget gate, output gate
  silu(x W_og), the stabilizer held per chunk of ``ssm.chunk`` positions;
* sLSTM: exponential input gate, recurrent weights per head, no group
  norm, h = o * c / max(n, 1).
"""
from repro.models.config import ArchConfig, SSMConfig

_PATTERN = ("mlstm",) * 5 + ("slstm",) + ("mlstm",) * 5 + ("slstm",)

CONFIG = ArchConfig(
    name="xlstm-125m", family="ssm",
    n_layers=12, d_model=768, n_heads=4, n_kv_heads=4, d_ff=0,
    vocab_size=50304, head_dim=192,
    rope="none", act="gelu", norm="layernorm",
    block_pattern=_PATTERN,
    ssm=SSMConfig(state_dim=16, chunk=128),
    tie_embeddings=True,
)
