"""MeshExecutor — a SWARM peer backed by a device mesh.

The paper's swarms are heterogeneous (§3, and the pooled-hardware
setting of Diskin et al.): one "peer" may be a lone preemptible T4,
another an 8-device node.  This executor makes the latter a first-class
pipeline citizen: the peer's stage step runs *sharded* over its mesh via
the ``repro.dist`` sharding rules — parameters placed by their logical
axes (:class:`repro.dist.sharding.ShardingRules`), the microbatch split
over the mesh's ``data`` axis — while the elastic scheduler above
remains oblivious: routing, the microbatch ledger, warm joins and
migrations all speak the same :class:`~repro.runtime.base.StageExecutor`
protocol as single-device peers.

The wire is the host: ``wire_fwd``/``wire_bwd`` gather the boundary
tensor off the mesh (after the int8 round-trip, when active), exactly
modelling SWARM's network crossing — so a mesh-backed peer can hand
activations to a single-device peer and vice versa, and state downloads
(``snapshot``/``restore``) recommit the replicated stage state onto
whichever backend the receiving peer runs.

Jitted stage functions are cached process-wide per ``(program, mesh)``
with the same retrace counters as the numeric backend (tagged
``"mesh"``), so N mesh peers of a stage on equal meshes compile once.
"""
from __future__ import annotations

import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression import codecs
from repro.dist.constrain import resolve_spec
from repro.dist.sharding import ShardingRules, DEFAULT_RULES, \
    stage_param_shardings
from repro.models.config import ArchConfig
from repro.models.stage_plan import get_stage_plan
from repro.models import params as P
from repro.runtime.base import StageState, exec_span, fold_into, \
    host_snapshot, install_snapshot, single_stage, slot_export, \
    slot_install, wire_bwd_codec, wire_fwd_codec
from repro.runtime.stage_model import _traced, init_stage_params
from repro.runtime import numeric as numeric_rt

Tree = Any

# (program-cache key, stage, mesh fingerprint) -> (fwd_j, bwd_j)
_MESH_JITS: dict[tuple, tuple] = {}
_LOCK = threading.Lock()


def _mesh_fingerprint(mesh: jax.sharding.Mesh) -> tuple:
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))


class MeshExecutor:
    """Run one pipeline stage data-parallel over a device mesh."""

    def __init__(self, cfg: ArchConfig, n_stages: int, seq_len: int,
                 stage: int, mesh: jax.sharding.Mesh,
                 compress: Optional[str] = None, quant_block: int = 64,
                 rules: Optional[ShardingRules] = None,
                 batch_axis: str = "data"):
        self.cfg = cfg
        self.stage = stage
        self.n_stages = n_stages
        self.seq_len = seq_len
        self.plan = get_stage_plan(cfg, n_stages)
        self.mesh = mesh
        self.rules = rules or DEFAULT_RULES
        self.batch_axis = batch_axis
        self.compress_mode = codecs.resolve_mode(cfg, compress)
        self.quant_block = quant_block
        self.device_count = int(np.prod(
            [mesh.shape[a] for a in mesh.axis_names]))
        # shared program: same math object the numeric backend runs, so
        # numeric and mesh peers of one stage are bitwise siblings
        progs = numeric_rt.get_stage_programs(
            cfg, n_stages, seq_len, self.compress_mode)
        self.prog = progs[stage]
        self.fwd_flops_per_token = self.prog.fwd_flops_per_token
        self.bwd_flops_per_token = self.prog.bwd_flops_per_token
        self.param_shardings = stage_param_shardings(
            self.prog.specs, mesh, self.rules)
        self._repl = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        self._params_treedef = jax.tree.structure(self.param_shardings)
        self._fwd_j, self._bwd_j = self._get_jits()

    # ------------------------------------------------------------ helpers
    def _get_jits(self):
        key = ((self.cfg, self.n_stages, self.seq_len, self.compress_mode),
               self.stage, _mesh_fingerprint(self.mesh))
        with _LOCK:
            hit = _MESH_JITS.get(key)
        if hit is not None:
            return hit
        tag = (self.cfg.name, self.n_stages, self.seq_len,
               self.compress_mode)

        def hook(stage, kind, shapes):     # same wrapper as the numeric
            # backend (stage_model._traced); "mesh" tags the backend
            numeric_rt.record_trace(tag + (stage, "mesh", kind, shapes))

        jits = (_traced(self.prog.fwd_fn, hook, self.stage, "fwd"),
                _traced(self.prog.bwd_fn, hook, self.stage, "bwd"))
        with _LOCK:
            jits = _MESH_JITS.setdefault(key, jits)
        return jits

    def _batch_sharding(self, x) -> jax.sharding.NamedSharding:
        x = np.asarray(x) if not hasattr(x, "shape") else x
        axes = [self.batch_axis] + [None] * (x.ndim - 1)
        return jax.sharding.NamedSharding(
            self.mesh, resolve_spec(axes, x.shape, self.mesh))

    def _place_batch(self, x):
        if x is None:
            return None
        return jax.device_put(jnp.asarray(x), self._batch_sharding(x))

    def _place_params(self, params: Tree) -> Tree:
        return jax.tree.map(
            lambda x, sh: jax.device_put(jnp.asarray(x), sh),
            params, self.param_shardings)

    def _place_opt(self, opt: Tree) -> Tree:
        """Optimizer state placement: any subtree shaped exactly like the
        params tree (adam's m/v moments, DPU's banked grads) gets the
        params' shardings leaf-for-leaf; everything else (count flags,
        scalars) replicates."""
        if opt is None:
            return None

        def place(sub):
            if jax.tree.structure(sub) == self._params_treedef:
                return self._place_params(sub)
            if isinstance(sub, dict):
                return {k: place(v) for k, v in sub.items()}
            return jax.device_put(jnp.asarray(sub), self._repl)

        return place(opt)

    # ---------------------------------------------------------- lifecycle
    def init_state(self, key: jax.Array) -> StageState:
        state = StageState(params=self._place_params(
            init_stage_params([self.prog], key)[0]))
        state.reset_progress()
        return state

    @property
    def stages(self) -> range:
        return range(self.stage, self.stage + 1)

    def for_stage(self, stage: int) -> "MeshExecutor":
        if stage == self.stage:
            return self
        return MeshExecutor(self.cfg, self.n_stages, self.seq_len, stage,
                            self.mesh, self.compress_mode,
                            self.quant_block, self.rules, self.batch_axis)

    def for_span(self, span: range):
        if len(span) == 1:
            return self.for_stage(span.start)
        return MeshSpanExecutor(self.cfg, self.n_stages, self.seq_len,
                                (span.start, span.stop), self.mesh,
                                self.compress_mode, self.quant_block,
                                self.rules, self.batch_axis)

    def dp_shards(self, batch: int) -> int:
        """Actual data-parallel split of a ``batch``-sized microbatch —
        mirrors ``resolve_spec``'s divisibility fallback: a batch that
        does not divide the data axis replicates (no speedup)."""
        n = int(self.mesh.shape.get(self.batch_axis, 1))
        return n if n > 1 and batch % n == 0 else 1

    def session_program(self, total_len: int):
        raise NotImplementedError(
            "mesh-backed serving is pending the sharded-decode work "
            "(ROADMAP) — serve spans on the numeric/pipeline backends")

    # ---------------------------------------------------------- execution
    @exec_span
    def run_fwd(self, state: StageState, inp: Tree,
                labels: Optional[jax.Array] = None) -> Tree:
        inp = self._place_batch(inp)
        if self.stage == self.n_stages - 1:
            return self._fwd_j(state.params, inp, self._place_batch(labels))
        return self._fwd_j(state.params, inp)

    @exec_span
    def run_bwd(self, state: StageState, inp: Tree,
                dy: Optional[Tree] = None,
                labels: Optional[jax.Array] = None):
        inp = self._place_batch(inp)
        if self.stage == self.n_stages - 1:
            loss, gx, gp = self._bwd_j(state.params, inp,
                                       self._place_batch(labels))
            return loss, gx, gp
        gx, gp = self._bwd_j(state.params, inp, self._place_batch(dy))
        return None, gx, gp

    # ------------------------------------------------- dispatch / collect
    def dispatch_fwd(self, state: StageState, inp: Tree,
                     labels: Optional[jax.Array] = None):
        # the sharded jit dispatches asynchronously across the mesh;
        # collect hands over the in-flight futures
        y = self.run_fwd(state, inp, labels)
        return lambda: y

    def dispatch_bwd(self, state: StageState, inp: Tree,
                     dy: Optional[Tree] = None,
                     labels: Optional[jax.Array] = None):
        out = self.run_bwd(state, inp, dy, labels)
        return lambda: out

    # --------------------------------------------------------- wire codec
    def wire_fwd(self, y: Tree) -> Tree:
        # the wire IS the host: gather off the mesh so any backend (a
        # single-device peer, another mesh) can ingest the tensor
        return jax.device_get(wire_fwd_codec(self, y))

    def wire_bwd(self, gx: Tree) -> Tree:
        gx = wire_bwd_codec(self, gx)
        return None if gx is None else jax.device_get(gx)

    # -------------------------------------------------------- accumulation
    def accumulate(self, state: StageState, gp: Optional[Tree],
                   loss: Optional[float], n_tokens: int,
                   stage: Optional[int] = None) -> None:
        single_stage(self, stage)
        fold_into(state, gp, loss, n_tokens, self.stage)

    def export_grads(self, state: StageState,
                     stage: Optional[int] = None) -> Tree:
        # host-gathered: addable with any other backend's accumulator
        single_stage(self, stage)
        return jax.device_get(state.grad_acc)

    def export_state(self, state: StageState,
                     stage: Optional[int] = None):
        single_stage(self, stage)
        return jax.device_get(state.params), jax.device_get(state.opt)

    @exec_span
    def adopt_step(self, state: StageState, new_params: Tree,
                   new_opt: Tree, stage: Optional[int] = None) -> None:
        single_stage(self, stage)
        state.params = self._place_params(new_params)
        state.opt = self._place_opt(new_opt)
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
        # mesh placement for params; opt follows the params shardings
        # (install_snapshot's generic placement can't know them)
        placed = dict(snap)
        placed["params"] = self._place_params(snap["params"])
        placed["opt"] = self._place_opt(snap.get("opt"))
        install_snapshot(state, placed, slots=slots,
                         place=lambda t: t)

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


class MeshSpanExecutor:
    """Stages ``[lo, hi)`` fused in ONE jit, sharded over a device mesh.

    Combines :class:`~repro.runtime.pipeline.PipelineExecutor`'s span
    fusion with :class:`MeshExecutor`'s placement: intra-span boundaries
    stay device-to-device *inside* the sharded jit (no host round-trip
    between covered stages), while state remains per-stage-keyed — each
    covered stage keeps mesh-placed params/opt/accumulator of exactly
    the single-stage shape, so All-Reduce groups, checkpoint cuts, and
    span ↔ single hand-offs interoperate unchanged (the span
    snapshot-interop tests run against this backend too)."""

    def __init__(self, cfg: ArchConfig, n_stages: int, seq_len: int,
                 span: tuple[int, int], mesh: jax.sharding.Mesh,
                 compress: Optional[str] = None, quant_block: int = 64,
                 rules: Optional[ShardingRules] = None,
                 batch_axis: str = "data"):
        lo, hi = span
        if not (0 <= lo < hi <= n_stages):
            raise ValueError(f"span [{lo}, {hi}) outside [0, {n_stages})")
        self.cfg = cfg
        self.n_stages = n_stages
        self.seq_len = seq_len
        self.span = (lo, hi)
        self.stage = lo                       # entry stage
        self.plan = get_stage_plan(cfg, n_stages)
        self.mesh = mesh
        self.rules = rules or DEFAULT_RULES
        self.batch_axis = batch_axis
        self.compress_mode = codecs.resolve_mode(cfg, compress)
        self.quant_block = quant_block
        self.device_count = int(np.prod(
            [mesh.shape[a] for a in mesh.axis_names]))
        # the same fused program object PipelineExecutor runs — mesh
        # span peers are bitwise siblings of single-device span peers
        self.prog = numeric_rt.get_span_program(
            cfg, n_stages, seq_len, (lo, hi), self.compress_mode)
        self.fwd_flops_per_token = self.prog.fwd_flops_per_token
        self.bwd_flops_per_token = self.prog.bwd_flops_per_token
        self.param_shardings = {
            s: stage_param_shardings(self.prog.specs[s], mesh, self.rules)
            for s in self.stages}
        self._repl = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        self._treedefs = {s: jax.tree.structure(self.param_shardings[s])
                          for s in self.stages}
        self._fwd_j, self._bwd_j = self._get_jits()

    @property
    def stages(self) -> range:
        return range(*self.span)

    # ------------------------------------------------------------ helpers
    def _get_jits(self):
        key = ((self.cfg, self.n_stages, self.seq_len, self.compress_mode),
               self.span, _mesh_fingerprint(self.mesh))
        with _LOCK:
            hit = _MESH_JITS.get(key)
        if hit is not None:
            return hit
        tag = (self.cfg.name, self.n_stages, self.seq_len,
               self.compress_mode)

        def hook(span_id, kind, shapes):
            numeric_rt.record_trace(tag + (span_id, "mesh", kind, shapes))

        jits = (_traced(self.prog.fwd_fn, hook, self.span, "fwd"),
                _traced(self.prog.bwd_fn, hook, self.span, "bwd"))
        with _LOCK:
            jits = _MESH_JITS.setdefault(key, jits)
        return jits

    def _batch_sharding(self, x) -> jax.sharding.NamedSharding:
        x = np.asarray(x) if not hasattr(x, "shape") else x
        axes = [self.batch_axis] + [None] * (x.ndim - 1)
        return jax.sharding.NamedSharding(
            self.mesh, resolve_spec(axes, x.shape, self.mesh))

    def _place_batch(self, x):
        if x is None:
            return None
        return jax.device_put(jnp.asarray(x), self._batch_sharding(x))

    def _place_params(self, params: Tree, stage: int) -> Tree:
        return jax.tree.map(
            lambda x, sh: jax.device_put(jnp.asarray(x), sh),
            params, self.param_shardings[stage])

    def _place_opt(self, opt: Tree, stage: int) -> Tree:
        if opt is None:
            return None

        def place(sub):
            if jax.tree.structure(sub) == self._treedefs[stage]:
                return self._place_params(sub, stage)
            if isinstance(sub, dict):
                return {k: place(v) for k, v in sub.items()}
            return jax.device_put(jnp.asarray(sub), self._repl)

        return place(opt)

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

    # ---------------------------------------------------------- lifecycle
    def init_state(self, key: jax.Array) -> StageState:
        state = StageState(per_stage={})
        keys = jax.random.split(key, len(self.stages))
        for k, s in zip(keys, self.stages):
            sub = StageState(params=self._place_params(
                P.init(k, self.prog.specs[s]), s))
            sub.reset_progress()
            state.per_stage[s] = sub
        return state

    def for_span(self, span: range):
        if (span.start, span.stop) == self.span:
            return self
        if len(span) == 1:
            return MeshExecutor(self.cfg, self.n_stages, self.seq_len,
                                span.start, self.mesh, self.compress_mode,
                                self.quant_block, self.rules,
                                self.batch_axis)
        return MeshSpanExecutor(self.cfg, self.n_stages, self.seq_len,
                                (span.start, span.stop), self.mesh,
                                self.compress_mode, self.quant_block,
                                self.rules, self.batch_axis)

    def for_stage(self, stage: int):
        return self.for_span(range(stage, stage + 1))

    def dp_shards(self, batch: int) -> int:
        n = int(self.mesh.shape.get(self.batch_axis, 1))
        return n if n > 1 and batch % n == 0 else 1

    def session_program(self, total_len: int):
        raise NotImplementedError(
            "mesh-backed serving is pending the sharded-decode work "
            "(ROADMAP) — serve spans on the numeric/pipeline backends")

    # ---------------------------------------------------------- execution
    @exec_span
    def run_fwd(self, state: StageState, inp: Tree,
                labels: Optional[jax.Array] = None) -> Tree:
        ps = self._params_tuple(state)
        inp = self._place_batch(inp)
        if self._covers_last():
            return self._fwd_j(ps, inp, self._place_batch(labels))
        return self._fwd_j(ps, inp)

    @exec_span
    def run_bwd(self, state: StageState, inp: Tree,
                dy: Optional[Tree] = None,
                labels: Optional[jax.Array] = None):
        ps = self._params_tuple(state)
        inp = self._place_batch(inp)
        if self._covers_last():
            loss, gx, gp = self._bwd_j(ps, inp, self._place_batch(labels))
        else:
            loss = None
            gx, gp = self._bwd_j(ps, inp, self._place_batch(dy))
        gp = {s: g for s, g in zip(self.stages, gp)}
        return loss, gx, gp

    # ------------------------------------------------- dispatch / collect
    def dispatch_fwd(self, state: StageState, inp: Tree,
                     labels: Optional[jax.Array] = None):
        y = self.run_fwd(state, inp, labels)
        return lambda: y

    def dispatch_bwd(self, state: StageState, inp: Tree,
                     dy: Optional[Tree] = None,
                     labels: Optional[jax.Array] = None):
        out = self.run_bwd(state, inp, dy, labels)
        return lambda: out

    # --------------------------------------------------------- wire codec
    def wire_fwd(self, y: Tree) -> Tree:
        return jax.device_get(wire_fwd_codec(self, y))

    def wire_bwd(self, gx: Tree) -> Tree:
        gx = wire_bwd_codec(self, gx)
        return None if gx is None else jax.device_get(gx)

    # -------------------------------------------------------- accumulation
    def accumulate(self, state: StageState, gp: Optional[Tree],
                   loss: Optional[float], n_tokens: int,
                   stage: Optional[int] = None) -> None:
        s = self._require(stage)
        fold_into(state.per_stage[s], gp, loss, n_tokens, s)

    def export_grads(self, state: StageState,
                     stage: Optional[int] = None) -> Tree:
        return jax.device_get(
            state.per_stage[self._require(stage)].grad_acc)

    def export_state(self, state: StageState,
                     stage: Optional[int] = None):
        sub = state.per_stage[self._require(stage)]
        return jax.device_get(sub.params), jax.device_get(sub.opt)

    @exec_span
    def adopt_step(self, state: StageState, new_params: Tree,
                   new_opt: Tree, stage: Optional[int] = None) -> None:
        s = self._require(stage)
        sub = state.per_stage[s]
        sub.params = self._place_params(new_params, s)
        sub.opt = self._place_opt(new_opt, s)
        sub.version += 1
        sub.reset_progress()

    # ---------------------------------------------------- state transfer
    def snapshot(self, state: StageState, stage: Optional[int] = None,
                 slots=()) -> Tree:
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
        placed = dict(snap)
        placed["params"] = self._place_params(snap["params"], s)
        placed["opt"] = self._place_opt(snap.get("opt"), s)
        install_snapshot(sub, placed, slots=slots, place=lambda t: t)

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
