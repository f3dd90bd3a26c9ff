"""The fault-free staged reference a SwarmRunner is held to.

One sequential peer per stage, the same data order and the same
parameter init as the runner: every churn, runtime, span and chip-smoke
equivalence check compares ``SwarmRunner`` against
:func:`reference_losses`.  The accumulation and token-weighted averaging
here must stay in lockstep with ``SwarmRunner._ar_plan``.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.data.synthetic import SyntheticLM
from repro.models.config import ArchConfig
from repro.optim.adamw import Optimizer
from repro.runtime import StageExecutor, StageProgram, init_stage_params


def reference_losses(cfg: ArchConfig, programs: Sequence[StageProgram],
                     opt: Optimizer, seed: int, steps: int, seq: int,
                     mb: int, gb: int, data_seed: int = 17,
                     executors: Optional[Sequence[StageExecutor]] = None
                     ) -> list[float]:
    """Per-step mean token loss of ``steps`` fault-free optimizer steps.

    ``executors`` (the runner's, one per stage) put every boundary
    tensor through the wire codec the trainer's hops apply —
    ``wire_fwd`` on activations, ``wire_bwd`` on cotangents (the int8
    codec's quantize-on-send); without them boundaries cross raw, which
    is what the learned codecs and ``"none"`` do."""
    S = len(programs)
    assert S >= 2
    raw = [lambda t: t] * S
    fwd_wire = [e.wire_fwd for e in executors] if executors else raw
    bwd_wire = [e.wire_bwd for e in executors] if executors else raw
    params = init_stage_params(programs, jax.random.PRNGKey(seed))
    opt_states = [opt.init(p) for p in params]
    ds = SyntheticLM(cfg.vocab_size, seq, mb, seed=data_seed)
    idx, losses = 0, []
    for _ in range(steps):
        grads: list[Any] = [jax.tree.map(jnp.zeros_like, p) for p in params]
        loss_sum, tok = 0.0, 0
        for _ in range(gb // mb):
            b = ds.batch(idx)
            idx += 1
            xs = [b["tokens"]]              # per-stage boundary inputs
            for s in range(S - 1):
                xs.append(fwd_wire[s](programs[s].fwd(params[s], xs[-1])))
            last = programs[S - 1]
            if last.fwd_save is not None:
                # the last hop as a peer runs it: the loss forward keeps
                # its residuals and the backward consumes them
                loss, saved = last.fwd_save(params[S - 1], xs[-1],
                                            b["labels"])
                gx, gp = last.bwd_saved(params[S - 1], xs[-1], b["labels"],
                                        saved)
            else:
                loss, gx, gp = last.bwd(params[S - 1], xs[-1], b["labels"])
            grads[S - 1] = jax.tree.map(jnp.add, grads[S - 1], gp)
            for s in range(S - 2, 0, -1):
                gx, gp = programs[s].bwd(params[s], xs[s],
                                         bwd_wire[s + 1](gx))
                grads[s] = jax.tree.map(jnp.add, grads[s], gp)
            _, gp = programs[0].bwd(params[0], xs[0], bwd_wire[1](gx))
            grads[0] = jax.tree.map(jnp.add, grads[0], gp)
            loss_sum += float(loss)
            tok += mb * seq
        losses.append(loss_sum / tok)
        for s in range(S):
            gm = jax.tree.map(lambda g: g / tok, grads[s])
            upd, opt_states[s] = opt.update(gm, opt_states[s], params[s])
            params[s] = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                     params[s], upd)
    return losses
