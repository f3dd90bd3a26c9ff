"""A run with the timed path broken underneath comes out not correct, once
for each fault a training cell can have on one chip: a step that leaves
the state unchanged, half of the batch left out (the mean taken over the
rest), and an answer (the last stage's gradient) altered where it is
produced."""
from __future__ import annotations

import pytest

from bench_tiny import tiny_root  # noqa: F401
from test_bench_harness import run_tiny


def _unchanged(monkeypatch):
    """The optimizer step hands back its params and state as they were."""
    import jax
    import jax.numpy as jnp
    import repro.optim
    from repro.optim.adamw import Optimizer
    orig = repro.optim.adamw

    def adamw(*a, **kw):
        opt = orig(*a, **kw)
        return Optimizer(init=opt.init, update=lambda g, s, p: (
            jax.tree.map(jnp.zeros_like, p), s))
    monkeypatch.setattr(repro.optim, "adamw", adamw)


def _half_batch(monkeypatch):
    from repro.runtime.numeric import NumericExecutor
    orig = NumericExecutor.accumulate
    calls = {}

    def accumulate(self, state, gp, loss, n_tokens, stage=None):
        calls[self.stage] = calls.get(self.stage, 0) + 1
        if calls[self.stage] % 2 == 0:
            return
        orig(self, state, gp, loss, n_tokens, stage=stage)
    monkeypatch.setattr(NumericExecutor, "accumulate", accumulate)


def _grad_doubled(monkeypatch):
    import jax
    from repro.runtime.numeric import NumericExecutor
    orig = NumericExecutor.run_bwd

    def run_bwd(self, state, inp, dy=None, labels=None):
        loss, gx, gp = orig(self, state, inp, dy, labels)
        if self.stage == self.n_stages - 1:
            leaves, tree = jax.tree.flatten(gp)
            big = max(range(len(leaves)), key=lambda i: leaves[i].size)
            leaves[big] = leaves[big] * 2
            gp = jax.tree.unflatten(tree, leaves)
        return loss, gx, gp
    monkeypatch.setattr(NumericExecutor, "run_bwd", run_bwd)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "grad_doubled": _grad_doubled}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["tiny-xlstm.elastic", "tiny-swarm.stage"])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                          fault):
    FAULTS[fault](monkeypatch)
    res = run_tiny(tiny_root, cell)
    assert res["correct"] is False, res["checks"]
