"""The program's two xlstm-125m stages against the plain float32
reference beside the configuration (``bench/configs/xlstm-125m.py``), on
seeded random weights at a small size on the CPU: bfloat16 compute,
d_model 64, 4 heads, 1,024 positions in mLSTM chunks of 32, each stage an
mLSTM block then an sLSTM block.  Stage 0's output feeds stage 1 on each
side (no wire codec), stage 1's input cotangent feeds stage 0's
backward; the loss and every leaf's gradient norm are compared."""
from __future__ import annotations

import os

import numpy as np
import pytest

from bench_tiny import ROOT

T = 1024
C = {
    "registry": "xlstm-125m", "n_stages": 2, "stages_run": [0, 1],
    "n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
    "head_dim": 16, "d_ff": 0, "vocab_size": 256,
    "block_pattern": ["mlstm", "slstm", "mlstm", "slstm"],
    "ssm": {"state_dim": 8, "chunk": 32}, "rope": "none", "act": "gelu",
    "norm": "layernorm", "causal": True, "tie_embeddings": True,
    "param_dtype": "float32", "compute_dtype": "bfloat16", "kernels": "jnp",
    "boundary_compression": "none",
}
# bfloat16 compute against float32: the token-mean loss reads ~1e-5
# relative (2 seeds); 1e-4 leaves room and still fails a model whose
# matmuls run a precision lower (float8 operands)
LOSS_TOL = 1e-4
# per leaf |norm - ref norm| over max(ref norm, median leaf norm), the
# benchmark's grad_norm_gap: bfloat16 operands read at most 2.6e-3 on
# every leaf; the sLSTM bias's gradient summed in bfloat16 over the
# 1,024 steps read 3.7e-2 to 5.2e-2
GRAD_TOL = 1e-2


@pytest.fixture(scope="module")
def compared():
    import jax
    import jax.numpy as jnp
    from bench import plain, ref_train, weights
    from bench.data import SyntheticLM
    from bench.run import arch_config, load_module
    from repro.runtime.stage_model import build_stage_programs

    model = load_module(os.path.join(ROOT, "bench", "configs",
                                     "xlstm-125m.py"), "ref_xlstm_stages")
    progs = build_stage_programs(arch_config(C), 2, T, compress="none")
    ps = [weights.make(model.param_specs(C, 2, s), 1500 + s)
          for s in range(2)]
    b = SyntheticLM(C["vocab_size"], T, 2, seed=15).batch(0)
    tok, lab = b["tokens"], b["labels"]

    h = progs[0].fwd(ps[0], tok)
    loss, gx, g1 = progs[1].bwd(ps[1], h, lab)
    _, g0 = progs[0].bwd(ps[0], tok, gx)

    num = plain.Numerics("f32")

    @jax.jit
    def reference(ps):
        x1, pull0 = jax.vjp(
            lambda p: model.stage_forward(num, C, 2, 0, p, tok), ps[0])
        rloss, (rg1, rgx) = jax.value_and_grad(
            lambda p, x: model.stage_loss(num, C, 2, 1, p, x, lab),
            argnums=(0, 1))(ps[1], x1)
        (rg0,) = pull0(rgx)
        return rloss, rg0, rg1

    with jax.default_matmul_precision("highest"):
        rloss, rg0, rg1 = reference(ps)
    prog = {**ref_train.leaf_norms(g0, "s0"),
            **ref_train.leaf_norms(g1, "s1")}
    ref = {**ref_train.leaf_norms(rg0, "s0"),
           **ref_train.leaf_norms(rg1, "s1")}
    return float(jnp.asarray(loss)), float(rloss), prog, ref


def test_loss_matches_the_reference(compared):
    loss, rloss, _, _ = compared
    assert abs(loss - rloss) / abs(rloss) < LOSS_TOL, (loss, rloss)


def test_every_leaf_gradient_norm_matches_the_reference(compared):
    _, _, prog, ref = compared
    assert set(prog) == set(ref)
    assert any("['r']" in k for k in ref) and any("['b']" in k for k in ref)
    med = float(np.median(list(ref.values())))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref}
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < GRAD_TOL, (worst, gaps[worst])
