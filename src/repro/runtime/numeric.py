"""NumericExecutor — single-device stage math behind a shared jit cache.

Absorbs the ``StageProgram`` machinery (``repro.runtime.stage_model``)
behind a *process-wide* compile cache keyed on ``(arch config, stage
count, sequence length, codec mode)``: every peer of a stage — across
runners, across the churn tests' seed matrix, across benchmark repeats —
shares one jitted ``fwd``/``bwd`` per stage instead of re-tracing its
own.  A retrace counter (a trace-time side effect inside the jitted
body) records every actual XLA trace per ``(stage, kind, argument
shapes)`` in the process-wide counter store of :mod:`repro.obs`;
``compile_stats()`` is what the fairness/retrace tests and
``benchmarks/bench_swarm.py`` read.  The executors' calls open the
``repro.exec.*`` and ``repro.wire.*`` profiler spans (see
:mod:`repro.obs`).

Gradient accumulation donates the accumulator buffer (``grad_acc`` is
exclusively owned by its :class:`StageState`), so the fold is in-place
at the XLA level — no second gradient-sized live buffer per microbatch.
"""
from __future__ import annotations

import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.compression import codecs
from repro.models.config import ArchConfig
from repro.runtime.base import SavedForward, StageState, exec_span, \
    fold_into, host_snapshot, install_snapshot, single_stage, \
    slot_export, slot_install, wire_bwd_codec, wire_fwd_codec
from repro.models.stage_plan import get_stage_plan
from repro.runtime.stage_model import (SpanProgram, StageProgram,
                                       build_span_program,
                                       build_stage_programs,
                                       init_stage_params)

Tree = Any

# ---------------------------------------------------------------- caches
# (cfg, n_stages, seq_len, comp) -> list[StageProgram]; ArchConfig is a
# frozen dataclass, hence hashable — identical configs share programs.
_PROGRAMS: dict[tuple, list[StageProgram]] = {}
# (cfg, n_stages, seq_len, comp, (lo, hi)) -> SpanProgram: one fused jit
# per (span, codec), shared by every peer serving that span
_SPANS: dict[tuple, SpanProgram] = {}
_LOCK = threading.Lock()
# counter-store keys of the XLA trace counts: (_TRACE, key)
_TRACE = "xla_trace"


def record_trace(key: tuple) -> None:
    """Count one XLA trace under ``key`` in the :mod:`repro.obs` store —
    every backend (numeric programs, mesh jits, serving sessions)
    reports here."""
    obs.count((_TRACE, key))


def reset_compile_stats() -> None:
    """Clear retrace counters AND every jit cache — numeric programs,
    mesh jits, and serving session programs alike — so tests/benchmarks
    that assert compile counts start from a genuinely cold cache."""
    import sys
    from repro.runtime import mesh as mesh_rt   # lazy: mesh imports us
    obs.reset()
    with _LOCK:
        _PROGRAMS.clear()
        _SPANS.clear()
    with mesh_rt._LOCK:
        mesh_rt._MESH_JITS.clear()
    serve_progs = sys.modules.get("repro.serve.programs")
    if serve_progs is not None:
        serve_progs.reset_session_cache()


def compile_stats() -> dict:
    """``{"traces", "per_key"}`` — ``traces`` is the total number of XLA
    traces since the last reset; ``per_key`` maps ``(cfg_name, n_stages,
    seq, comp, stage, kind, shapes)`` -> count."""
    per_key = {k[1]: v for k, v in obs.counters().items()
               if isinstance(k, tuple) and k[0] == _TRACE}
    return {"traces": sum(per_key.values()), "per_key": per_key}


def get_stage_programs(cfg: ArchConfig, n_stages: int, seq_len: int,
                       compress: Optional[str] = None
                       ) -> list[StageProgram]:
    """The shared, counted stage programs for this configuration."""
    comp = codecs.resolve_mode(cfg, compress)
    key = (cfg, n_stages, seq_len, comp)
    with _LOCK:
        progs = _PROGRAMS.get(key)
    if progs is not None:
        return progs
    tag = (cfg.name, n_stages, seq_len, comp)

    def hook(stage: int, kind: str, shapes: tuple):
        record_trace(tag + (stage, kind, shapes))

    progs = build_stage_programs(cfg, n_stages, seq_len, compress=comp,
                                 trace_hook=hook)
    with _LOCK:
        # first build wins if two threads raced; both lists are equivalent
        progs = _PROGRAMS.setdefault(key, progs)
    return progs


def get_span_program(cfg: ArchConfig, n_stages: int, seq_len: int,
                     span: tuple[int, int],
                     compress: Optional[str] = None) -> SpanProgram:
    """The shared, counted fused program for a ``[lo, hi)`` span: one
    fwd/bwd jit per (configuration, span, codec) process-wide, so N span
    peers of one span compile once and a second same-shape runner
    re-traces nothing (same discipline as the per-stage cache)."""
    comp = codecs.resolve_mode(cfg, compress)
    key = (cfg, n_stages, seq_len, comp, tuple(span))
    with _LOCK:
        prog = _SPANS.get(key)
    if prog is not None:
        return prog
    tag = (cfg.name, n_stages, seq_len, comp)

    def hook(span_id, kind: str, shapes: tuple):
        record_trace(tag + (span_id, kind, shapes))

    prog = build_span_program(cfg, n_stages, seq_len, tuple(span),
                              compress=comp, trace_hook=hook)
    with _LOCK:
        prog = _SPANS.setdefault(key, prog)
    return prog


class NumericExecutor:
    """Single-device stage execution (today's eager-ish SWARM peer)."""

    device_count = 1

    def __init__(self, cfg: ArchConfig, prog: StageProgram,
                 compress_mode: str, quant_block: int = 64,
                 family: Optional[list["NumericExecutor"]] = None,
                 seq_len: Optional[int] = None):
        self.cfg = cfg
        self.prog = prog
        self.stage = prog.stage
        self.n_stages = prog.n_stages
        self.plan = get_stage_plan(cfg, prog.n_stages)
        self.seq_len = seq_len              # lets for_span build fused kin
        self.compress_mode = compress_mode
        self.quant_block = quant_block
        self.fwd_flops_per_token = prog.fwd_flops_per_token
        self.bwd_flops_per_token = prog.bwd_flops_per_token
        # all executors of one pipeline, so migrations can swap stages
        self._family = family if family is not None else [self]

    @property
    def stages(self) -> range:
        return range(self.stage, self.stage + 1)

    # ---------------------------------------------------------- lifecycle
    def init_state(self, key: jax.Array) -> StageState:
        state = StageState(params=init_stage_params([self.prog], key)[0])
        state.reset_progress()
        return state

    def for_stage(self, stage: int) -> "NumericExecutor":
        return self._family[stage]

    def for_span(self, span: range) -> "StageExecutor":
        """Width-1 spans stay in the numeric family; wider spans swap the
        peer onto the fused :class:`~repro.runtime.pipeline
        .PipelineExecutor` backend (how a merge turns a single-stage
        peer into a span peer)."""
        if len(span) == 1:
            return self._family[span.start]
        if self.seq_len is None:
            raise ValueError("NumericExecutor built without seq_len "
                             "cannot widen to a span")
        from repro.runtime.pipeline import PipelineExecutor
        return PipelineExecutor(self.cfg, self.n_stages, self.seq_len,
                                (span.start, span.stop),
                                compress=self.compress_mode,
                                quant_block=self.quant_block)

    def dp_shards(self, batch: int) -> int:
        del batch
        return 1

    def session_program(self, total_len: int):
        from repro.serve.programs import get_session_program
        return get_session_program(
            self.cfg, self.n_stages, (self.stage, self.stage + 1),
            total_len, compress=self.compress_mode)

    # ---------------------------------------------------------- execution
    @exec_span
    def run_fwd(self, state: StageState, inp: Tree,
                labels: Optional[jax.Array] = None) -> Tree:
        if self.stage != self.n_stages - 1:
            return self.prog.fwd(state.params, inp)
        if self.prog.fwd_save is None:
            return self.prog.fwd(state.params, inp, labels)
        state.saved_fwd = None          # one pending forward per state
        loss, saved = self.prog.fwd_save(state.params, inp, labels)
        state.saved_fwd = SavedForward(inp, labels, state.params, loss,
                                       saved)
        return loss

    @exec_span
    def run_bwd(self, state: StageState, inp: Tree,
                dy: Optional[Tree] = None,
                labels: Optional[jax.Array] = None):
        if self.stage != self.n_stages - 1:
            gx, gp = self.prog.bwd(state.params, inp, dy)
            return None, gx, gp
        hit = state.saved_fwd
        if hit is not None and hit.inp is inp and hit.labels is labels \
                and hit.params is state.params:
            state.saved_fwd = None
            with obs.span("exec.bwd_saved", stage=self.stage):
                gx, gp = self.prog.bwd_saved(state.params, inp, labels,
                                             hit.saved)
            obs.count(("exec.bwd_saved", self.stage))
            return hit.loss, gx, gp
        # a miss: the recompute program, whose loss and gradients a mesh
        # or pipeline executor's backward of this stage gives bit for bit
        # (the pair's gradients are the same; its loss is the forward's)
        obs.count(("exec.bwd_recomputed", self.stage))
        return self.prog.bwd(state.params, inp, labels)

    # ------------------------------------------------- dispatch / collect
    def dispatch_fwd(self, state: StageState, inp: Tree,
                     labels: Optional[jax.Array] = None):
        # jax dispatches asynchronously: run_fwd returns device futures
        # with the work already in flight, so issuing now and collecting
        # later is a genuine overlap on real hardware
        y = self.run_fwd(state, inp, labels)
        return lambda: y

    def dispatch_bwd(self, state: StageState, inp: Tree,
                     dy: Optional[Tree] = None,
                     labels: Optional[jax.Array] = None):
        out = self.run_bwd(state, inp, dy, labels)
        return lambda: out

    # --------------------------------------------------------- wire codec
    def wire_fwd(self, y: Tree) -> Tree:
        return wire_fwd_codec(self, y)

    def wire_bwd(self, gx: Tree) -> Tree:
        return wire_bwd_codec(self, gx)

    # -------------------------------------------------------- accumulation
    def accumulate(self, state: StageState, gp: Optional[Tree],
                   loss: Optional[float], n_tokens: int,
                   stage: Optional[int] = None) -> None:
        single_stage(self, stage)
        fold_into(state, gp, loss, n_tokens, self.stage)

    def export_grads(self, state: StageState,
                     stage: Optional[int] = None) -> Tree:
        single_stage(self, stage)
        return state.grad_acc                   # already scheduler-local

    def export_state(self, state: StageState,
                     stage: Optional[int] = None):
        single_stage(self, stage)
        return state.params, state.opt

    @exec_span
    def adopt_step(self, state: StageState, new_params: Tree,
                   new_opt: Tree, stage: Optional[int] = None) -> None:
        single_stage(self, stage)
        state.params = new_params
        state.opt = new_opt
        state.version += 1
        state.reset_progress()

    # ---------------------------------------------------- state transfer
    def snapshot(self, state: StageState, stage: Optional[int] = None,
                 slots=()) -> Tree:
        single_stage(self, stage)
        return host_snapshot(state, slots=slots)

    def restore(self, state: StageState, snap: Tree,
                stage: Optional[int] = None, slots=()) -> None:
        single_stage(self, stage)
        install_snapshot(state, snap, slots=slots)

    # ------------------------------------------------------ keyed slots
    def export_slot(self, state: StageState, name: str, key,
                    stage: Optional[int] = None) -> Tree:
        single_stage(self, stage)
        return slot_export(state, name, key)

    def install_slot(self, state: StageState, name: str, key, value: Tree,
                     stage: Optional[int] = None) -> None:
        single_stage(self, stage)
        slot_install(state, name, key, value)

    def drop_slot(self, state: StageState, name: str, key=None,
                  stage: Optional[int] = None) -> None:
        single_stage(self, stage)
        state.drop_slot(name, key)


def build_numeric_executors(cfg: ArchConfig, n_stages: int, seq_len: int,
                            compress: Optional[str] = None,
                            quant_block: int = 64,
                            programs: Optional[list[StageProgram]] = None
                            ) -> list[NumericExecutor]:
    """One executor per stage, all sharing the cached programs (or an
    injected pre-built list, e.g. the churn tests' shared seed matrix)."""
    comp = codecs.resolve_mode(cfg, compress)
    progs = programs if programs is not None else \
        get_stage_programs(cfg, n_stages, seq_len, comp)
    family: list[NumericExecutor] = []
    for p in progs:
        family.append(NumericExecutor(cfg, p, comp, quant_block,
                                      family=family, seq_len=seq_len))
    return family
