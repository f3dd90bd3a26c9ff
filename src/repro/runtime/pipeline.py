"""PipelineExecutor — a SWARM peer serving a contiguous *span* of stages.

SWARM's square-cube argument (paper §3.1) says a well-provisioned peer
should hold *more of the model*, not more replicas of one slice; Varuna
reaches the same conclusion for preemptible fleets by fusing consecutive
pipeline stages on one worker and re-partitioning on membership change.
This backend is that lever: one peer serves stages ``[lo, hi)`` in a
SINGLE jitted step (:class:`repro.runtime.stage_model.SpanProgram`,
which reuses the ``repro.dist`` stage core and stack/stage-scan
machinery), so

* intra-span boundaries stay on-device — under a learned codec the
  in-program compress/decompress pair still runs (the math is identical
  to single-stage peers, which is what the span churn-equivalence test
  asserts), but zero bytes cross the host;
* the wire codec (``wire_fwd``/``wire_bwd``, e.g. SWARM's int8
  quantize-on-send) applies only at span *edges*, where the activation
  really crosses the network;
* one fwd + one bwd compile per (span, codec) process-wide — N span
  peers of one span share the jits, same discipline as the per-stage
  cache (``benchmarks/bench_swarm.py`` asserts it).

State is *per-stage-keyed* (``StageState.per_stage``): every covered
stage keeps its own params/opt/accumulator/version, so

* the All-Reduce groups per stage still work — a span peer joins one
  group per covered stage, exporting/adopting per-stage trees;
* checkpoint cuts write ordinary single-stage snapshots;
* a dying or shrinking span peer hands per-stage snapshots to
  single-stage peers, and a merge pulls them back — numeric ↔ mesh ↔
  pipeline state downloads all interoperate through the same
  single-stage host-tree wire format.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.compression import codecs
from repro.models.config import ArchConfig
from repro.models import params as P
from repro.runtime.base import StageState, exec_span, fold_into, \
    host_snapshot, install_snapshot, slot_export, slot_install, \
    wire_bwd_codec, wire_fwd_codec
from repro.runtime import numeric as numeric_rt

Tree = Any


class PipelineExecutor:
    """Run stages ``[lo, hi)`` fused in one jit on a single device."""

    device_count = 1

    def __init__(self, cfg: ArchConfig, n_stages: int, seq_len: int,
                 span: tuple[int, int], compress: Optional[str] = None,
                 quant_block: int = 64):
        lo, hi = span
        if not (0 <= lo < hi <= n_stages):
            raise ValueError(f"span [{lo}, {hi}) outside [0, {n_stages})")
        self.cfg = cfg
        self.n_stages = n_stages
        self.seq_len = seq_len
        self.span = (lo, hi)
        self.stage = lo                       # entry stage
        from repro.models.stage_plan import get_stage_plan
        self.plan = get_stage_plan(cfg, n_stages)
        self.compress_mode = codecs.resolve_mode(cfg, compress)
        self.quant_block = quant_block
        self.prog = numeric_rt.get_span_program(
            cfg, n_stages, seq_len, (lo, hi), self.compress_mode)
        self.fwd_flops_per_token = self.prog.fwd_flops_per_token
        self.bwd_flops_per_token = self.prog.bwd_flops_per_token

    @property
    def stages(self) -> range:
        return range(*self.span)

    # ---------------------------------------------------------- lifecycle
    def init_state(self, key: jax.Array) -> StageState:
        state = StageState(per_stage={})
        keys = jax.random.split(key, len(self.stages))
        for k, s in zip(keys, self.stages):
            sub = StageState(params=P.init(k, self.prog.specs[s]))
            sub.reset_progress()
            state.per_stage[s] = sub
        return state

    def for_span(self, span: range) -> "StageExecutor":
        if (span.start, span.stop) == self.span:
            return self
        if len(span) == 1:
            from repro.runtime.numeric import build_numeric_executors
            return build_numeric_executors(
                self.cfg, self.n_stages, self.seq_len,
                compress=self.compress_mode,
                quant_block=self.quant_block)[span.start]
        return PipelineExecutor(self.cfg, self.n_stages, self.seq_len,
                                (span.start, span.stop),
                                compress=self.compress_mode,
                                quant_block=self.quant_block)

    def for_stage(self, stage: int) -> "StageExecutor":
        return self.for_span(range(stage, stage + 1))

    def dp_shards(self, batch: int) -> int:
        del batch
        return 1

    def session_program(self, total_len: int):
        from repro.serve.programs import get_session_program
        return get_session_program(self.cfg, self.n_stages, self.span,
                                   total_len, compress=self.compress_mode)

    # ------------------------------------------------------------ helpers
    def _params_tuple(self, state: StageState) -> tuple:
        return tuple(state.per_stage[s].params for s in self.stages)

    def _covers_last(self) -> bool:
        return self.span[1] == self.n_stages

    def _require(self, stage: Optional[int]) -> int:
        if stage is None:
            raise ValueError(
                f"span executor [{self.span[0]}, {self.span[1]}) needs an "
                "explicit covered stage for per-stage state operations")
        if stage not in self.stages:
            raise ValueError(f"stage {stage} outside span {self.span}")
        return stage

    # ---------------------------------------------------------- execution
    @exec_span
    def run_fwd(self, state: StageState, inp: Tree,
                labels: Optional[jax.Array] = None) -> Tree:
        ps = self._params_tuple(state)
        if self._covers_last():
            return self.prog.fwd(ps, inp, labels)
        return self.prog.fwd(ps, inp)

    @exec_span
    def run_bwd(self, state: StageState, inp: Tree,
                dy: Optional[Tree] = None,
                labels: Optional[jax.Array] = None):
        ps = self._params_tuple(state)
        if self._covers_last():
            loss, gx, gp = self.prog.bwd(ps, inp, labels)
        else:
            loss = None
            gx, gp = self.prog.bwd(ps, inp, dy)
        # per-stage grads keyed by GLOBAL stage id: the scheduler folds
        # each covered stage independently (the ledger may admit a
        # subset of them on a re-issued attempt)
        gp = {s: g for s, g in zip(self.stages, gp)}
        return loss, gx, gp

    # ------------------------------------------------- dispatch / collect
    def dispatch_fwd(self, state: StageState, inp: Tree,
                     labels: Optional[jax.Array] = None):
        # the fused span jit dispatches asynchronously; collect hands
        # over the in-flight futures
        y = self.run_fwd(state, inp, labels)
        return lambda: y

    def dispatch_bwd(self, state: StageState, inp: Tree,
                     dy: Optional[Tree] = None,
                     labels: Optional[jax.Array] = None):
        out = self.run_bwd(state, inp, dy, labels)
        return lambda: out

    # --------------------------------------------------------- wire codec
    def wire_fwd(self, y: Tree) -> Tree:
        return wire_fwd_codec(self, y)          # span-edge only

    def wire_bwd(self, gx: Tree) -> Tree:
        return wire_bwd_codec(self, gx)

    # -------------------------------------------------------- accumulation
    def accumulate(self, state: StageState, gp: Optional[Tree],
                   loss: Optional[float], n_tokens: int,
                   stage: Optional[int] = None) -> None:
        s = self._require(stage)
        fold_into(state.per_stage[s], gp, loss, n_tokens, s)

    def export_grads(self, state: StageState,
                     stage: Optional[int] = None) -> Tree:
        return state.per_stage[self._require(stage)].grad_acc

    def export_state(self, state: StageState,
                     stage: Optional[int] = None):
        sub = state.per_stage[self._require(stage)]
        return sub.params, sub.opt

    @exec_span
    def adopt_step(self, state: StageState, new_params: Tree,
                   new_opt: Tree, stage: Optional[int] = None) -> None:
        sub = state.per_stage[self._require(stage)]
        sub.params = new_params
        sub.opt = new_opt
        sub.version += 1
        sub.reset_progress()

    # ---------------------------------------------------- state transfer
    def snapshot(self, state: StageState, stage: Optional[int] = None,
                 slots=()) -> Tree:
        """Single-stage-format snapshot of one covered stage, or (with
        ``stage=None``) the whole span as ``{"per_stage": {s: snap}}`` —
        the former is the interop format every hand-off uses."""
        if stage is None:
            return {"per_stage": {
                s: host_snapshot(state.per_stage[s], slots=slots)
                for s in self.stages}}
        return host_snapshot(state.per_stage[self._require(stage)],
                             slots=slots)

    def restore(self, state: StageState, snap: Tree,
                stage: Optional[int] = None, slots=()) -> None:
        if state.per_stage is None:
            state.per_stage = {}
        if stage is None:
            for s, sub_snap in snap["per_stage"].items():
                self.restore(state, sub_snap, stage=int(s), slots=slots)
            return
        s = self._require(stage)
        sub = state.per_stage.setdefault(s, StageState())
        install_snapshot(sub, snap, slots=slots)

    # ------------------------------------------------------ keyed slots
    def export_slot(self, state: StageState, name: str, key,
                    stage: Optional[int] = None) -> Tree:
        return slot_export(state.per_stage[self._require(stage)], name, key)

    def install_slot(self, state: StageState, name: str, key, value: Tree,
                     stage: Optional[int] = None) -> None:
        slot_install(state.per_stage[self._require(stage)], name, key,
                     value)

    def drop_slot(self, state: StageState, name: str, key=None,
                  stage: Optional[int] = None) -> None:
        if stage is None:
            for sub in state.views():
                sub.drop_slot(name, key)
            return
        state.per_stage[self._require(stage)].drop_slot(name, key)
