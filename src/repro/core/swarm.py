"""SwarmRunner — the full SWARM parallelism system on the virtual clock.

Composition (paper Fig. 2): consecutive swarms of peers serve pipeline
stages; trainer processes route microbatches via stochastic wiring; a DHT
carries liveness + load; adaptive rebalancing migrates peers between
stages; once the microbatch ledger (repro.core.ledger) shows the global
batch accumulated exactly once at every stage, each stage All-Reduces its
gradients and applies the (optionally delayed, DPU) optimizer step.
Gradients lost to dead or migrating peers are recomputed by survivors
under the same microbatch indices, so an optimizer step under churn
averages the identical sample set as fault-free training (App. A).

A peer's assignment is a contiguous *span* of stages (usually width 1).
Span peers (:class:`repro.runtime.PipelineExecutor`) occupy one DHT slot,
one All-Reduce group, and one ledger row per covered stage, but serve the
whole span in a single jitted step — only span-edge activations cross the
host (the square-cube lever, §3.1).  ``split_span``/``_resize_span``
re-partition spans on membership change, Varuna-style: a shrinking span
peer hands per-stage snapshots to single-stage peers, a merge pulls them
back.

Two modes:
  numeric=True   — real JAX math per stage (convergence experiments,
                   equivalence tests; Fig. 4 / App. E analogues).
  numeric=False  — timing only (Tables 2-5, Figs. 5-7 analogues: 400-peer,
                   32-hour traces run in seconds of wall time).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.compression import codecs
from repro.core.sim import Sim, Sleep, Spawn
from repro.core.dht import DHT
from repro.core.ledger import MicrobatchLedger
from repro.core.peer import Peer, DeviceProfile, PeerFailure, T4
from repro.core.wiring import StochasticWiring
from repro.core.trainer import Trainer, Microbatch
from repro.core import rebalance as rb
from repro.core.faults import TraceEvent
from repro.models.config import ArchConfig
from repro.models import flops as F
from repro.models.stage_plan import StagePlan, get_stage_plan
from repro.optim.adamw import Optimizer, mean_step
from repro.runtime import StageExecutor, StageProgram, \
    build_numeric_executors, init_stage_params

Tree = Any


def _as_span(stage: "int | range") -> range:
    return stage if isinstance(stage, range) else range(stage, stage + 1)


@dataclasses.dataclass
class SwarmConfig:
    """Swarm-level knobs (the architecture lives in ``ArchConfig``).

    The async tick is controlled by two fields:

    * ``overlap`` — boundary tensors ride the peers' NIC links as
      in-flight transfers (priced end-to-end at the sending/receiving
      pair's bottleneck) instead of two blocking serial sleeps, and
      stage math goes through the executors' dispatch/collect pair.
      Pure timing: the training trajectory is unchanged (bitwise under
      deterministic routing — the equivalence suite asserts it).
    * ``staleness`` — ATOM-style bounded staleness for the All-Reduce
      window: the optimizer step's numerics are applied at the barrier
      instant while the communication window runs *concurrently* with
      the next round's compute; at most ``staleness`` windows may be in
      flight before the next barrier blocks on the oldest.  Any value
      > 0 wraps the optimizer in ``delayed_parameter_updates`` (DPU,
      paper §3.2: fold step t's grads while t+1 computes), so the
      trajectory equals the sequential DPU(delay=1) reference; 0 keeps
      today's fully synchronous barrier bitwise.  ``dpu=True`` is the
      historical spelling of ``staleness=1``.

    Cost-model pricing is plan-driven: the runner computes a
    ``repro.models.stage_plan.StagePlan`` once from ``(ArchConfig,
    n_stages)`` and prices per-stage compute (``stage_flops`` — per
    kind, head on the owning stage) and per-boundary wire bytes
    (``boundary_bytes`` — whisper composite payloads, expert-sharded
    MoE top_k routing) from it; ``rebalance_period``-driven span merges
    rank candidate boundaries by those per-edge prices.

    Kernel backend: the hot path the peers execute is picked by the
    *architecture* config — ``ArchConfig.kernels`` (``"jnp"`` default,
    ``"pallas"`` for the fused flash/rmsnorm/boundary-codec kernels;
    pure backend switch, identical trajectories) and
    ``ArchConfig.wire_quant`` (blockwise-int8 QDQ of the learned
    codec's wire, priced by ``boundary_bytes``); the swarm itself needs
    no knob — runners with either backend share ledger, codec, and
    wire-byte accounting.
    """
    n_stages: int = 3
    microbatch_size: int = 1
    seq_len: int = 128
    global_batch: int = 8                # sequences per optimizer step
    n_trainers: int = 4
    rebalance_period: float = 300.0      # T (paper §4.3)
    announce_interval: float = 120.0
    announce_ttl: float = 300.0
    wiring_gamma: float = 0.1            # EMA alpha (paper §4.3)
    # boundary wire codec, canonical: "none" | "int8" | a learned mode
    # ("bottleneck" | "maxout", paper App. J) | "auto" (defer to
    # ``cfg.boundary_compression``).  Default "int8" is the historical
    # ``compress=True``.
    codec: Optional[str] = None
    # DEPRECATED spelling of ``codec`` (False -> "none", True -> "int8",
    # str passthrough); normalized away in ``__post_init__`` so
    # ``dataclasses.replace`` round-trips never re-warn
    compress: "bool | str | None" = None
    quant_block: int = 64
    dpu: bool = False
    # async tick (see class docstring): in-flight boundary transfers +
    # dispatch/collect execution, and the bounded-staleness All-Reduce
    overlap: bool = False
    staleness: int = 0
    max_steps: Optional[int] = None
    allreduce_bw: float = 50e6           # bytes/s effective per peer
    trainer_max_retries: int = 50        # per-attempt routing retries
    # elastic checkpointing (ROADMAP): persist a pipeline-consistent cut
    # of every stage's state each ``ckpt_period`` completed steps via
    # the executors' snapshot() — a stage that loses ALL its peers
    # resumes from the latest completed step instead of the step-0
    # reference params, and a runner constructed over a non-empty
    # ``ckpt_dir`` RESUMES that run (step counter + data cursor adopt
    # the latest cut)
    ckpt_dir: Optional[str] = None
    ckpt_period: int = 1
    # span rebalancing: let the Alg.-2 loop also propose span splits /
    # merges (repro.core.rebalance.plan_span_change) — a span peer
    # bottlenecked on one stage shrinks onto it, an underloaded peer
    # absorbs an adjacent well-covered stage (saving its host boundary)
    spans: bool = False
    # inter-region cost model (repro.core.square_cube.LinkTable): when
    # set, the rebalance loop prices each boundary over the link between
    # the regions serving its two stages (seconds, not bytes), so span
    # merges fuse across slow WAN pairs first.  Peers get regions from
    # the runner's ``region_fn`` and zone-tagged trace events.
    link_table: Optional[Any] = None

    def __post_init__(self):
        if self.compress is not None:
            resolved = ("int8" if self.compress is True else
                        "none" if self.compress is False else self.compress)
            warnings.warn(
                f"SwarmConfig(compress=...) is deprecated; use "
                f"codec={resolved!r}", DeprecationWarning, stacklevel=3)
            if self.codec is not None and self.codec != resolved:
                raise ValueError(
                    f"conflicting codecs: codec={self.codec!r} vs "
                    f"compress={self.compress!r}")
            self.codec = resolved
            self.compress = None
        if self.codec is None:
            self.codec = "int8"
        if self.codec != "auto" and self.codec not in codecs.MODES:
            raise ValueError(f"unknown codec {self.codec!r}; expected "
                             f"'auto' or one of {codecs.MODES}")
        if self.staleness < 0:
            raise ValueError(f"staleness must be >= 0, got "
                             f"{self.staleness}")
        if self.dpu:
            # historical spelling of the bounded-staleness knob
            self.staleness = max(self.staleness, 1)


class SwarmRunner:
    def __init__(self, cfg: ArchConfig, scfg: SwarmConfig,
                 optimizer: Optimizer, *, numeric: bool = True,
                 seed: int = 0,
                 profile_fn: Optional[Callable[[int], DeviceProfile]] = None,
                 data_fn: Optional[Callable[[int], dict]] = None,
                 programs: Optional[list[StageProgram]] = None,
                 record_accumulation: bool = False,
                 region_fn: Optional[Callable[[int], str]] = None):
        self.cfg = cfg
        self.scfg = scfg
        if scfg.staleness > 0:
            # bounded staleness implies DPU: the step applies the grads
            # banked one round ago while this round's fold rides the
            # concurrent All-Reduce window (paper §3.2; ATOM).  Wrapping
            # here keeps checkpoints, the reference init, and every
            # export/adopt consistent with the wrapped state shape.
            from repro.optim.dpu import delayed_parameter_updates
            optimizer = delayed_parameter_updates(optimizer, delay=1)
        self.optimizer = optimizer
        self._mean_step = mean_step(optimizer)
        self.overlap = bool(scfg.overlap)
        self.numeric = numeric
        self.sim = Sim()
        self.dht = DHT(lambda: self.sim.now)
        self.n_stages = scfg.n_stages
        # the canonical per-stage structure: kind runs, per-stage flops,
        # per-boundary wire pricing.  Timing-only runs over splits the
        # plan rejects (e.g. indivisible layer counts) fall back to the
        # legacy uniform pricing (plan=None); numeric construction below
        # would raise on such splits anyway.
        try:
            self.plan: Optional[StagePlan] = get_stage_plan(
                cfg, scfg.n_stages)
        except ValueError:
            self.plan = None
        self.compress_mode = codecs.resolve_mode(
            cfg, None if scfg.codec == "auto" else scfg.codec)
        self.quant_block = scfg.quant_block
        self.rng = np.random.default_rng(seed)
        self.profile_fn = profile_fn or (lambda i: T4)
        # zone placement, like profile_fn keyed by join index; only
        # meaningful with scfg.link_table (region-aware edge pricing)
        self.region_fn = region_fn or (lambda i: "local")
        self.data_fn = data_fn

        # stage execution goes through the runtime layer: one executor
        # per stage, shared by all that stage's peers (the process-wide
        # compile cache means the seed matrix of the churn tests and
        # repeated benchmark runs never re-trace either).  ``programs``
        # may still be injected (pre-jitted) for back-compat.  Span
        # executors are built on demand (``_span_executor``) and share
        # the process-wide span-program cache.
        if numeric:
            if programs is not None:
                assert len(programs) == scfg.n_stages
            self.executors: list[Optional[StageExecutor]] = \
                build_numeric_executors(
                    cfg, scfg.n_stages, scfg.seq_len,
                    compress=self.compress_mode,
                    quant_block=scfg.quant_block, programs=programs)
            self.programs: list[StageProgram] = \
                [e.prog for e in self.executors]
        else:
            self.executors = [None] * scfg.n_stages
            self.programs = [None] * scfg.n_stages
        self._ref_params: Optional[list[Tree]] = None
        if numeric:
            self._ref_params = init_stage_params(
                self.programs, jax.random.PRNGKey(seed))
            self._ref_opt = [optimizer.init(p) for p in self._ref_params]

        self.peers: dict[str, Peer] = {}
        self.wirings: list[StochasticWiring] = []
        self.trainers: list[Trainer] = []
        # (lo, hi) -> shared default PipelineExecutor for that span
        self._span_execs: dict[tuple[int, int], StageExecutor] = {}

        # training progress
        self.stopped = False
        self._t_stopped: Optional[float] = None   # virtual stop instant
        self._mb_counter = 0
        self._inflight = 0
        self._dispatch_paused = False
        self.step = 0
        # exactly-once accounting (App. A): which (stage, microbatch)
        # pairs of the current round are held, and by whom.  A span peer
        # holds one row per covered stage.
        self.ledger = MicrobatchLedger(scfg.n_stages)
        # optional audit trail, as (kind, step, stage, index, attempt,
        # peer_id) with kind in {"acc", "rel", "step"}: every applied
        # accumulation, every release (grads dying with a failed or
        # migrating peer), and an All-Reduce barrier marker — the churn
        # tests replay it to assert the exactly-once invariant
        self.record_accumulation = record_accumulation
        self.ledger_log: list[tuple[str, int, int, int, int, str]] = []
        self.metrics: dict[str, list] = {
            "loss": [], "step_time": [],
            "throughput_t": [], "throughput_v": [], "migrations": 0,
            "failures": 0, "joins": 0, "recomputed_microbatches": 0,
            "span_changes": 0,       # split/merge/resize events applied
            "wire_bytes": 0.0,       # activation/cotangent bytes that
                                     # actually crossed the host (span-
                                     # fused boundaries charge nothing)
            "ckpt_restores": [],     # (stage, restored-from step)
            "rollbacks": [],         # (step rolled back from, to)
            # async-tick accounting (overlap mode): what the same edges
            # would have cost serially vs what the in-flight transfers
            # actually took; run() derives overlap_fraction/peer_idle_s
            "wire_serial_s": 0.0,
            "wire_inflight_s": 0.0,
            "inflight_bytes": 0.0,
        }
        self._ar_pending: list = []  # unfinished All-Reduce windows
        self._samples_done_total = 0
        self._flops_per_sample_total = 0.0
        self._default_ds = None      # built once, on first use
        # cold-start resume: a non-empty ckpt_dir means this runner
        # CONTINUES that run — adopt the latest consistent cut's step
        # and data cursor, so peers restore step-k params AND training
        # replays the same sample indices fault-free training would use
        # from step k (otherwise later saves would also be pruned in
        # favor of the stale higher-numbered ones)
        self._resume_step = self._common_ckpt_step() if numeric else 0
        if self._resume_step:
            K = scfg.global_batch // max(scfg.microbatch_size, 1)
            self.step = self._resume_step
            self._mb_counter = self._resume_step * K
        self._open_round()

    # ================================================== setup
    def _span_executor(self, span: range) -> Optional[StageExecutor]:
        """The default executor for a span assignment (None in timing
        mode): the stage family for width 1, a runner-cached
        PipelineExecutor otherwise — so ALL default-backed peers of one
        span share one executor object (which is what keeps
        ``adopt_state_from``'s zero-copy alias path hot and avoids
        re-building executor families on every split/merge)."""
        if self.executors[span.start] is None:
            return None
        if len(span) == 1:
            return self.executors[span.start]
        key = (span.start, span.stop)
        ex = self._span_execs.get(key)
        if ex is None:
            ex = self._span_execs[key] = \
                self.executors[span.start].for_span(span)
        return ex

    def _rebacked_executor(self, peer: Peer,
                           span: range) -> Optional[StageExecutor]:
        """``peer``'s backend re-targeted at ``span``: custom backends
        (mesh slices) keep themselves via ``for_span``; default-backed
        peers go back through the runner's shared executors."""
        if peer.executor is None:
            return None
        from repro.runtime import MeshExecutor, MeshSpanExecutor
        if isinstance(peer.executor, (MeshExecutor, MeshSpanExecutor)):
            return peer.executor.for_span(span)
        return self._span_executor(span)

    def _routes_without(self, peer: Peer,
                        new_span: Optional[range]) -> bool:
        """Would the serving layout still tile [0, n_stages) if ``peer``
        served ``new_span`` (None = left entirely)?  Coverage alone is
        not enough — a hop enters a span only at its start (see
        ``rebalance.spans_route``)."""
        layout = [(q.stages.start, q.stages.stop)
                  for q in self.peers.values()
                  if q.alive and q.serving and q is not peer]
        if new_span is not None:
            layout.append((new_span.start, new_span.stop))
        return rb.spans_route(self.n_stages, layout)

    def add_peer(self, stage: "int | range",
                 profile: Optional[DeviceProfile] = None,
                 executor: Optional[StageExecutor] = None) -> Peer:
        """Cold-start a peer (initial ``build``): at step 0 the reference
        params ARE current, so announcing immediately is safe.  Mid-run
        joins go through ``_join_new_peer``, which downloads the stage
        state *before* announcing (warm join).

        ``stage`` may be a single stage or a contiguous ``range(lo, hi)``
        span; ``executor`` backs the peer with a custom runtime (e.g. a
        :class:`repro.runtime.MeshExecutor` over a device mesh, or a
        :class:`repro.runtime.PipelineExecutor` for a span); by default
        the peer shares the span's cached executor."""
        span = _as_span(stage)
        if executor is not None:
            assert (executor.stages.start, executor.stages.stop) == \
                (span.start, span.stop), (executor.stages, span)
        else:
            executor = self._span_executor(span)
        peer = Peer(self.sim, profile or self.profile_fn(len(self.peers)),
                    span, executor=executor,
                    region=self.region_fn(len(self.peers)))
        self.peers[peer.id] = peer
        if self.numeric:
            # _resume_step == 0 pins the step-0 reference: stale entries
            # in a torn/leftover ckpt_dir with no common step must not
            # leak differing per-stage "latest" params into a fresh run
            for s in peer.stages:
                self._restore_from_checkpoint(peer, s,
                                              step=self._resume_step)
        self._announce(peer)
        for w in self.wirings:
            w.add_server(peer.id, [peer.stages.start])
        self.sim.spawn(self._announcer(peer))
        return peer

    def build(self, peers_per_stage: int | list[int]):
        if isinstance(peers_per_stage, int):
            peers_per_stage = [peers_per_stage] * self.n_stages
        for s, n in enumerate(peers_per_stage):
            for _ in range(n):
                self.add_peer(s)
        for i in range(self.scfg.n_trainers):
            w = StochasticWiring(self.n_stages,
                                 gamma=self.scfg.wiring_gamma,
                                 seed=1000 + i)
            for pid, p in self.peers.items():
                if p.alive:
                    w.add_server(pid, [p.stages.start])
            self.wirings.append(w)
            t = Trainer(self.sim, self, w, f"trainer{i}",
                        max_retries=self.scfg.trainer_max_retries)
            self.trainers.append(t)
            self.sim.spawn(t.run())
        self.sim.spawn(self._sync_loop())
        if self.scfg.rebalance_period > 0:
            self.sim.spawn(self._rebalance_loop())

    # ================================================== DHT liveness
    def _announce(self, peer: Peer):
        # a span peer occupies EVERY covered stage slot: liveness and
        # coverage are per stage, even though routing only enters the
        # span at its start
        for s in peer.stages:
            self.dht.store(self.dht.stage_key(s), peer.id, s,
                           self.scfg.announce_ttl)

    def _dht_forget(self, peer: Peer, span: Optional[range] = None):
        for s in (span if span is not None else peer.stages):
            self.dht.delete(self.dht.stage_key(s), peer.id)
            self.dht.delete(self.dht.load_key(s), peer.id)

    def _announcer(self, peer: Peer):
        gen = peer._generation
        while peer.alive and peer._generation == gen and not self.stopped:
            if peer.serving:          # no announcements mid-download
                self._announce(peer)
            yield Sleep(self.scfg.announce_interval)

    def announced_stages(self) -> dict[str, int]:
        """Live serving peers by their ROUTING slot (span start) — what
        the wirings refresh from.  Coverage queries go per stage via
        ``_covering``."""
        out = {}
        for s in range(self.n_stages):
            for pid, rec in self.dht.get(self.dht.stage_key(s)).items():
                peer = self.peers.get(pid)
                if peer is not None and peer.alive and peer.serving \
                        and s in peer.stages:
                    out[pid] = peer.stages.start
        return out

    def _covering(self, stage: int, but: Optional[Peer] = None
                  ) -> list[Peer]:
        """Live serving peers whose span covers ``stage``."""
        return [p for p in self.peers.values()
                if p.alive and p.serving and stage in p.stages
                and p is not but]

    def _stage_regions(self) -> list[str]:
        """Dominant region per stage: the most common zone among the
        live serving peers covering it (alphabetical tie-break; "local"
        when nobody covers).  This is the per-stage region vector the
        link table prices boundary edges with."""
        regions = []
        for s in range(self.n_stages):
            counts: dict[str, int] = {}
            for p in self._covering(s):
                r = getattr(p, "region", "local")
                counts[r] = counts.get(r, 0) + 1
            regions.append(max(sorted(counts), key=counts.get)
                           if counts else "local")
        return regions

    # ================================================== data / dispatch
    def _open_round(self):
        """Fix the next round's sample set: exactly ``global_batch``
        samples (App. E synchronous semantics).  Lost samples re-issue
        under the *same* index, so the per-step sample set is identical
        to fault-free training."""
        K = self.scfg.global_batch // max(self.scfg.microbatch_size, 1)
        self.ledger.open_round(
            range(self._mb_counter, self._mb_counter + K))
        self._mb_counter += K

    def next_microbatch(self) -> Optional[Microbatch]:
        """Hand out work while some stage of the current round is short —
        the ledger re-issues exactly the indices whose gradients died
        with failed or migrated peers (App. A)."""
        if self.stopped or self._dispatch_paused:
            return None
        with obs.span("ctl.ledger"):
            nxt = self.ledger.next_index()
        if nxt is None:
            return None
        idx, attempt = nxt
        if attempt > 1:
            self.metrics["recomputed_microbatches"] += 1
        self._inflight += 1
        b, S = self.scfg.microbatch_size, self.scfg.seq_len
        mb = Microbatch(index=idx, size=b, n_tokens=b * S, attempt=attempt)
        if self.numeric:
            batch = (self.data_fn(idx) if self.data_fn else
                     self._default_data(idx))
            mb.tokens, mb.labels = batch["tokens"], batch["labels"]
        return mb

    def _default_data(self, idx: int) -> dict:
        if self._default_ds is None:    # one dataset per runner, reused
            from repro.data.synthetic import SyntheticLM
            self._default_ds = SyntheticLM(
                self.cfg.vocab_size, self.scfg.seq_len,
                self.scfg.microbatch_size, seed=17)
        return self._default_ds.batch(idx)

    def microbatch_done(self, mb: Microbatch, ok: bool):
        self._inflight -= 1
        # the ledger re-queues the index iff some stage still lacks it
        # (failed attempt, or a holder died mid-flight)
        with obs.span("ctl.ledger", mb=mb.index):
            self.ledger.settle(mb.index)
        if ok:
            self._samples_done_total += mb.size
            self.metrics["throughput_t"].append(self.sim.now)
            self.metrics["throughput_v"].append(self._samples_done_total)

    # ================================================== cost model
    def compute_time(self, peer: Peer, kind: str, stage: int,
                     mb: Microbatch) -> float:
        ex = (peer.executor if peer.executor is not None
              and stage in peer.executor.stages else self.executors[stage])
        if ex is not None:
            # span executors report whole-span totals: one hop runs the
            # entire fused span
            fpt = (ex.fwd_flops_per_token if kind == "fwd"
                   else ex.bwd_flops_per_token)
            # a mesh-backed peer splits the microbatch over its data
            # axis (data-parallel within the peer); dp_shards reports
            # the ACTUAL split — 1 when divisibility forces replication
            speedup = max(1, ex.dp_shards(mb.size))
            return peer.profile.compute_time(fpt * mb.n_tokens) / speedup
        # timing-only: analytic per-stage flops summed over the hop's
        # covered stages, priced per kind by the stage plan
        stages = peer.stages if stage in peer.stages \
            else range(stage, stage + 1)
        if self.plan is not None:
            fpt = sum(self.plan.stage_flops(s, self.scfg.seq_len)
                      for s in stages)
        else:                      # legacy fallback: uniform even split
            ctx = F._ctx_for(self.cfg, self.scfg.seq_len, causal_avg=True)
            per = self.cfg.n_layers // self.n_stages
            fpt = 0.0
            for s in stages:
                kinds = self.cfg.block_kinds[s * per:(s + 1) * per]
                fpt += sum(F.per_token_layer_flops(self.cfg, k, ctx)
                           for k in kinds)
                if s == self.n_stages - 1:
                    fpt += 2 * self.cfg.d_model * self.cfg.vocab_size
        if kind == "bwd":
            fpt *= 3.0
        return peer.profile.compute_time(fpt * mb.n_tokens)

    def boundary_nbytes(self, mb: Microbatch,
                        boundary: Optional[int] = None) -> float:
        # one mode string end-to-end: the sim charges exactly the bytes the
        # active codec puts on the wire (flops.boundary_bytes is the same
        # formula bench_compression measures against the real tensors).
        # With a boundary index the plan prices THAT boundary: uniform
        # hidden-state pricing for dense LM stacks (identical to the
        # legacy formula), but whisper boundaries add the encoder-state
        # + token payload and expert-sharded MoE boundaries pay the
        # per-token-routed top_k factor.
        if (self.plan is not None and boundary is not None
                and 0 <= boundary < self.n_stages - 1):
            return self.plan.boundary_bytes(
                boundary, mb.size, self.scfg.seq_len, self.compress_mode)
        return F.boundary_bytes(
            self.cfg, mb.size, self.scfg.seq_len, self.compress_mode)

    def count_wire_bytes(self, nbytes: float):
        """One boundary tensor actually crossed the host (trainers call
        this per hop edge — span-fused boundaries never do)."""
        self.metrics["wire_bytes"] += nbytes

    def count_inflight_wire(self, serial_s: float, actual_s: float,
                            nbytes: float):
        """One in-flight edge landed (overlap mode): ``serial_s`` is what
        the blocking send+recv pair would have cost, ``actual_s`` what
        the trainer really waited.  Clamped per edge: a wait beyond the
        serial estimate is FIFO queueing on a contended link (the sync
        path priced NICs as infinitely parallel), not negative overlap,
        so it must not cancel savings other edges genuinely hid."""
        self.metrics["wire_serial_s"] += serial_s
        self.metrics["wire_inflight_s"] += min(actual_s, serial_s)
        self.metrics["inflight_bytes"] += nbytes

    # ================================================== gradient sync
    def accumulate(self, peer: Peer, gp: Optional[Tree], mb: Microbatch,
                   loss: Optional[float], stage: Optional[int] = None
                   ) -> bool:
        """Fold a microbatch gradient into ``peer``'s accumulator —
        exactly once per (stage, index) per round, for EVERY stage the
        peer's span covers.  A re-issued attempt falls through for the
        stages that already hold the gradient (re-running backward with
        unchanged params reproduces it bit-for-bit, so skipping is
        exact) — so a span peer may fold a strict subset of its covered
        stages.  ``gp`` is the stage's tree for single-stage peers, a
        ``{global stage id: tree}`` dict for span peers."""
        stages = [stage] if stage is not None else list(peer.stages)
        span_keyed = isinstance(gp, dict) and gp and \
            all(isinstance(k, int) for k in gp)
        last = self.n_stages - 1
        any_folded = False
        for s in stages:
            with obs.span("ctl.ledger", mb=mb.index, stage=s):
                fresh = self.ledger.record(s, mb.index, peer.id)
            if not fresh:
                continue
            if self.record_accumulation:
                self.ledger_log.append(
                    ("acc", self.step, s, mb.index, mb.attempt, peer.id))
            loss_s = loss if s == last else None
            if peer.executor is not None:
                # executor-owned fold (donated accumulator buffer)
                g_s = gp[s] if span_keyed else gp
                peer.executor.accumulate(peer.state, g_s, loss_s,
                                         mb.n_tokens, stage=s)
            else:                               # timing-only simulation
                view = peer.state.stage_view(s)
                view.token_count += mb.n_tokens
                if loss_s is not None:
                    view.loss_sum += loss_s
            any_folded = True
        return any_folded

    def _sync_loop(self):
        """Trigger All-Reduce + optimizer step when the ledger shows the
        full global batch accumulated at every stage.  Lost indices are
        re-issued by ``next_microbatch`` (via the ledger) concurrently —
        there is no separate recompute budget to over- or under-open."""
        if self.scfg.staleness > 0:
            yield from self._sync_loop_async()
            return
        while not self.stopped:
            # barrier: every stage holds every index AND nothing is in
            # flight (an in-flight re-issue may still run stale thunks
            # whose accumulations must land in *this* round)
            if not self.ledger.complete() or self._inflight > 0:
                yield Sleep(0.2)
                continue
            self._dispatch_paused = True
            t0 = self.sim.now
            yield from self._all_reduce_and_step()
            self.metrics["step_time"].append(self.sim.now - t0)
            self._open_round()
            self._dispatch_paused = False
            if (self.scfg.max_steps is not None
                    and self.step >= self.scfg.max_steps):
                self.stopped = True
                self._t_stopped = self.sim.now

    def _sync_loop_async(self):
        """Bounded-staleness barrier (ATOM-style; ``scfg.staleness`` > 0):
        the step's numerics apply ATOMICALLY at the barrier instant
        (identical gradients and install order to the sync path, so the
        trajectory equals the sequential DPU reference), while the
        All-Reduce *time* rides a concurrent window off the critical
        path — the next round's compute starts immediately.  At most
        ``staleness`` windows may be unfinished before the next barrier
        blocks on the oldest; dispatch never pauses (no yields between
        barrier detection and round reopen)."""
        last_barrier = 0.0
        while not self.stopped:
            if not self.ledger.complete() or self._inflight > 0:
                yield Sleep(0.2)
                continue
            self._ar_pending = [ev for ev in self._ar_pending
                                if not ev.fired]
            while len(self._ar_pending) >= self.scfg.staleness:
                yield self._ar_pending[0].wait()
                self._ar_pending = [ev for ev in self._ar_pending
                                    if not ev.fired]
            total = self._all_reduce_and_step_now()
            # step_time = inter-barrier interval: with the window off
            # the critical path this is the number to compare to sync
            self.metrics["step_time"].append(self.sim.now - last_barrier)
            last_barrier = self.sim.now
            ev = self.sim.event()
            self._ar_pending.append(ev)
            self.sim.spawn(self._ar_window(total, ev))
            self._open_round()
            if (self.scfg.max_steps is not None
                    and self.step >= self.scfg.max_steps):
                self.stopped = True
                self._t_stopped = self.sim.now

    def _ar_window(self, duration: float, ev):
        yield Sleep(duration)
        ev.fire()

    def _log_releases(self, lost: list[tuple[int, int]], peer_id: str):
        if self.record_accumulation:
            for s, i in lost:
                self.ledger_log.append(("rel", self.step, s, i, 0, peer_id))

    def _all_reduce_and_step(self):
        """Per-stage ring All-Reduce (time) + optimizer step (numerics).

        All numerics are computed at the barrier instant (no yields in
        the snapshot loop): failures landing inside the All-Reduce
        window cannot retroactively remove gradients from a step that
        already observed the complete global batch.  Migrations and
        state adoptions defer until the window closes (see ``_migrate``
        / ``_download_state``).  A span peer is a member of every
        covered stage's group, with per-stage grads/tokens/install."""
        with obs.span("swarm.barrier", step=self.step):
            plan = self._ar_plan()
        for s, group, ar_time, new_params, new_opt in plan:
            yield Sleep(ar_time)
            with obs.span("swarm.barrier", step=self.step):
                self._ar_install(s, group, new_params, new_opt)
        self.step += 1
        self._maybe_checkpoint()

    def _all_reduce_and_step_now(self) -> float:
        """Async-barrier variant: identical numerics, applied atomically
        at the barrier instant (no yields at all); returns the total
        All-Reduce time for the concurrent window."""
        total = 0.0
        with obs.span("swarm.barrier", step=self.step):
            for s, group, ar_time, new_params, new_opt in self._ar_plan():
                total += ar_time
                self._ar_install(s, group, new_params, new_opt)
        self.step += 1
        self._maybe_checkpoint()
        return total

    def _ar_install(self, s: int, group: list, new_params, new_opt):
        for p in group:
            if not p.alive:      # died inside the ring: state is dead
                continue
            if self.numeric:
                # install + re-place on the peer's backend, bump the
                # version, zero the accumulator — per covered stage
                p.executor.adopt_step(p.state, new_params, new_opt,
                                      stage=s)
            else:
                p.state.stage_view(s).zero_grads()

    def _ar_plan(self):
        """Gradient averaging + optimizer step per stage, computed with
        NO yields — shared by the sync and bounded-staleness barriers."""
        if self.record_accumulation:
            self.ledger_log.append(("step", self.step, -1, -1, 0, ""))
        plan = []
        for s in range(self.n_stages):
            # non-serving peers are mid-download: stale params, drained
            # grads — they adopt the stepped state when the download ends
            group = self._covering(s)
            if not group:
                continue
            k = len(group)
            nbytes = group[0].state_nbytes(stage=s) / 3.0   # grads only
            if nbytes == 0.0:                        # throughput mode
                nbytes = 2.0 * F.total_params(self.cfg) / self.n_stages
            ar_time = (2 * (k - 1) / max(k, 1)) * nbytes \
                / self.scfg.allreduce_bw + 0.01 * k
            new_params = new_opt = None
            if self.numeric:
                # average gradients over the stage (token-weighted);
                # export_grads yields scheduler-local trees, so the sum
                # mixes numeric, mesh-backed, and span peers freely
                total_tokens = sum(p.state.stage_view(s).token_count
                                   for p in group)
                grads = [p.executor.export_grads(p.state, stage=s)
                         for p in group]
                params, opt = group[0].executor.export_state(
                    group[0].state, stage=s)
                new_params, new_opt = self._mean_step(
                    grads, float(max(total_tokens, 1)), params, opt)
                loss_sum = sum(p.state.stage_view(s).loss_sum
                               for p in group)
                if s == self.n_stages - 1 and total_tokens:
                    self.metrics["loss"].append(loss_sum / total_tokens)
            plan.append((s, group, ar_time, new_params, new_opt))
        return plan

    # ================================================== rebalancing
    def _rebalance_loop(self):
        T = self.scfg.rebalance_period
        while not self.stopped:
            yield Sleep(T)
            # peers report queue sizes (Alg. 2 line 4) under EVERY stage
            # they cover; mid-download peers neither report nor qualify
            # as migration donors
            for p in self.peers.values():
                if p.alive and p.serving:
                    for s in p.stages:
                        self.dht.store(self.dht.load_key(s), p.id,
                                       p.queue_size() + 1e-3, T * 1.5)
            # single-stage moves consider only single-stage donors (a
            # span peer leaving would strand several stages at once);
            # span resizes go through plan_span_change
            pps = {s: [p.id for p in self.peers.values()
                       if p.alive and p.serving and p.stages ==
                       range(s, s + 1)]
                   for s in range(self.n_stages)}
            # ONE frozen control-plane view per round: every decision
            # below reads this capture (S DHT gets total), never the
            # live DHT per candidate — the O(P²·S) -> O(P·S + P log P)
            # restructure of ISSUE 10
            snap = rb.ControlSnapshot.capture(self.dht, self.n_stages)
            mig = rb.plan_migration(snap, self.n_stages, pps)
            if mig is not None:
                yield from self._migrate(self.peers[mig.peer],
                                         mig.dst_stage)
                continue
            if not self.scfg.spans:
                continue
            spans = {p.id: (p.stages.start, p.stages.stop)
                     for p in self.peers.values()
                     if p.alive and p.serving}
            # per-boundary wire prices from the stage plan: merges fuse
            # the most expensive edge first (routed-MoE / whisper
            # boundaries beat uniform hidden-state ones).  With a link
            # table the bytes become region-priced SECONDS — an edge
            # straddling a slow WAN pair ranks highest, so the swarm
            # fuses across slow links first.
            bcosts = (self.plan.boundary_costs(
                self.scfg.microbatch_size, self.scfg.seq_len,
                self.compress_mode) if self.plan is not None else None)
            if bcosts is not None and self.scfg.link_table is not None:
                bcosts = self.scfg.link_table.edge_costs(
                    list(bcosts), self._stage_regions())
            ch = rb.plan_span_change(snap, self.n_stages, spans,
                                     boundary_costs=bcosts)
            if ch is not None:
                yield from self._resize_span(self.peers[ch.peer],
                                             range(*ch.new_span))

    def _maybe_checkpoint(self):
        """Persist every stage's state (executor ``snapshot()`` →
        ``repro.ckpt``) after a completed optimizer step, so a stage that
        later loses ALL its peers resumes from here instead of step 0.

        A checkpoint is a *pipeline-consistent cut*: either every stage
        is saved at this step or none is (a stranded stage skips the
        whole save), so every stage directory always holds the same step
        numbers — which is what lets ``_rollback_to`` restore one
        uniform parameter version and ``prune_checkpoints`` keep only
        the latest cut.  Span peers serve as holders for each covered
        stage — the cut is single-stage snapshots regardless of spans."""
        if (not self.numeric or not self.scfg.ckpt_dir
                or self.step % max(self.scfg.ckpt_period, 1)):
            return
        holders = []
        for s in range(self.n_stages):
            holder = next(
                (p for p in self._covering(s)
                 if p.state.stage_view(s).params is not None), None)
            if holder is None:
                return                 # no consistent cut exists right now
            holders.append(holder)
        from repro.ckpt import prune_checkpoints, save_checkpoint, \
            stage_dir
        for s, holder in enumerate(holders):
            d = stage_dir(self.scfg.ckpt_dir, s)
            save_checkpoint(d, self.step,
                            holder.executor.snapshot(holder.state, stage=s))
            # keep 2 cuts: if a process dies between per-stage saves the
            # torn newest cut is excluded by _common_ckpt_step's
            # intersection and resume falls back to the previous one
            prune_checkpoints(d, keep=2)

    def _common_ckpt_step(self) -> int:
        """Newest checkpointed step EVERY stage can serve (0 if none).
        A torn cut — a process killed between per-stage saves leaves
        stage dirs at different steps — is excluded by the intersection,
        never resumed at mixed versions."""
        if not self.scfg.ckpt_dir:
            return 0
        from repro.ckpt import available_steps, stage_dir
        common = None
        for s in range(self.n_stages):
            steps = set(available_steps(
                stage_dir(self.scfg.ckpt_dir, s)))
            common = steps if common is None else common & steps
        return max(common) if common else 0

    def _rollback_to(self, step_k: int):
        """A stage must resume from checkpoint step ``step_k`` < the
        pipeline's current step: rewind EVERY stage to it (Varuna-style
        global rollback), so the pipeline trains one consistent version.
        Rewinds the step counter, the data cursor, and the loss
        trajectory — the replayed steps consume the same sample indices
        fault-free training used after ``step_k``, so the final
        trajectory still matches the reference."""
        self._dispatch_paused = True
        # drain in-flight microbatches: their accumulations belong to
        # the aborted round (attempts against the stranded stage fail
        # once trainer retries exhaust)
        while self._inflight > 0 and not self.stopped:
            yield Sleep(0.1)
        if self.stopped:
            return
        for s in range(self.n_stages):
            group = [p for p in self._covering(s)
                     if p.executor is not None]
            if not group:
                continue
            # one disk read per stage, fanned out to all its peers:
            # explicitly the target step (not "latest"), so every stage
            # rewinds to the SAME consistent cut (0 = step-0 reference)
            snap = self._ckpt_snapshot(s, step=step_k)
            for p in group:
                p.executor.restore(p.state, snap, stage=s)
        self.metrics["rollbacks"].append((self.step, step_k))
        K = self.scfg.global_batch // max(self.scfg.microbatch_size, 1)
        self.step = step_k
        self._mb_counter = step_k * K
        # the loss list is relative to the step this RUNNER started at
        # (a cold-resumed runner begins with an empty list at step
        # _resume_step), so truncate by offset, not absolute step
        del self.metrics["loss"][max(step_k - self._resume_step, 0):]
        self._open_round()
        self._dispatch_paused = False

    def _restore_from_checkpoint(self, peer: Peer, stage: int,
                                 step: Optional[int] = None):
        """Stage died entirely (or a cold start): restore the persisted
        checkpoint (``step``; None = the latest; 0 = explicitly the
        step-0 reference params, bypassing the directory) through the
        peer's executor, falling back to the reference when nothing is
        saved."""
        if self._ref_params is None:         # timing-only: no state
            return
        peer.executor.restore(peer.state,
                              self._ckpt_snapshot(stage, step=step),
                              stage=stage)

    def _ckpt_snapshot(self, stage: int, step: Optional[int] = None):
        """Host snapshot tree for ``stage`` (see
        ``_restore_from_checkpoint`` for the ``step`` semantics)."""
        snap = {"params": self._ref_params[stage],
                "opt": self._ref_opt[stage], "version": 0}
        if self.scfg.ckpt_dir and step != 0:
            from repro.ckpt import (available_steps, restore_checkpoint,
                                    stage_dir)
            d = stage_dir(self.scfg.ckpt_dir, stage)
            try:
                snap, got = restore_checkpoint(d, like=snap, step=step)
                self.metrics["ckpt_restores"].append((stage, got))
            except FileNotFoundError:
                # only an EMPTY stage dir may fall back to the step-0
                # reference; a present-but-missing explicitly requested
                # step means the directory is inconsistent with its
                # siblings — restoring anything else would silently mix
                # parameter versions across stages
                if step is not None and available_steps(d):
                    raise RuntimeError(
                        f"checkpoint dir {d} has steps "
                        f"{available_steps(d)} but not the requested "
                        f"step {step} — stage dirs are inconsistent")
        return snap

    def _download_stage_state(self, peer: Peer, s: int):
        """Warm-state download of ONE stage: copy stage ``s``'s
        replicated state from a live covering neighbor (retrying if the
        donor dies mid-transfer), falling back to the checkpoint when
        the stage has no survivors.  Cross-span by construction: a span
        donor emits the single-stage snapshot for ``s``, whatever the
        receiving peer's own span is.  Returns with the stage installed
        — or early if the peer itself dies."""
        if not self.numeric:           # timing-only state transfer
            yield Sleep(1.0)
            return

        while True:
            donors = self._covering(s, but=peer)
            if not donors:
                yield Sleep(1.0)
                # same discipline as the donor path below: never adopt
                # (or get snapshotted serving stale state) inside an
                # All-Reduce window — the stage would re-checkpoint the
                # pre-step params under the post-step number
                while self._dispatch_paused and not self.stopped:
                    yield Sleep(0.05)
                if not peer.alive or self.stopped:
                    return
                if self._covering(s, but=peer):
                    continue           # a peer recovered during the wait
                if self._ref_params is None:
                    return
                # truly stranded: resume from the latest persisted
                # checkpoint.  If that checkpoint is older than the
                # pipeline's current step (ckpt_period > 1, or no
                # ckpt_dir at all), first rewind the WHOLE pipeline to
                # it (Varuna-style global rollback) — a lone stage must
                # never serve params from an older step than its
                # neighbors.
                k = self._common_ckpt_step()
                if k < self.step:
                    yield from self._rollback_to(k)
                if peer.alive:
                    self._restore_from_checkpoint(peer, s, step=k)
                return
            donor = donors[0]
            yield Sleep(peer.profile.recv_time(donor.state_nbytes(stage=s)))
            # adopt outside the All-Reduce window, or the joiner would
            # capture pre-step params while the stage steps past it
            while self._dispatch_paused and not self.stopped:
                yield Sleep(0.05)
            if not peer.alive:
                return
            if donor.alive and donor.serving and s in donor.stages:
                if peer.stages == donor.stages:
                    # same span: whole-state adoption (zero-copy when
                    # the two share an executor)
                    peer.adopt_state_from(donor)
                else:
                    peer.executor.restore(
                        peer.state,
                        donor.executor.snapshot(donor.state, stage=s),
                        stage=s)
                return

    def _download_state(self, peer: Peer, span: range):
        """Download every stage of ``span`` (possibly from different
        donors — a merging peer pulls each stage from whoever covers
        it)."""
        for s in span:
            yield from self._download_stage_state(peer, s)
            if not peer.alive or self.stopped:
                return

    def _complete_warm_join(self, peer: Peer, span: range):
        """Warm-join tail shared by migrations, joins, and span resizes:
        the state download completes BEFORE the peer is announced or
        entered into any wiring — a (re)joining peer must never serve
        stale params.  Returns False if the peer died mid-download."""
        peer.serving = False
        yield from self._download_state(peer, span)
        if not peer.alive:                     # preempted mid-download
            return False
        peer.serving = True
        self._announce(peer)
        for w in self.wirings:
            w.move_server(peer.id, [span.start])
        return True

    def _retire_assignment(self, peer: Peer):
        """Stop serving the current span, in exactly-once order: drain
        queued thunks (they must never execute against newly adopted
        state), release the ledger entries the peer's gradients backed
        (survivors recompute those indices), leave the DHT slots and
        wirings."""
        peer.serving = False
        peer.drain()
        lost = []
        for s in peer.stages:
            lost += [(s, i) for i in self.ledger.release_peer(s, peer.id)]
        self._log_releases(lost, peer.id)
        peer.state.zero_grads()                # grads die with the move
        self._dht_forget(peer)
        for w in self.wirings:
            w.ban_server(peer.id)

    def _migrate(self, peer: Peer, dst: "int | range"):
        """Stage switch, in exactly-once order: stop serving, drain the
        queued src-stage thunks, release the ledger entries, download
        the dst state — and only then re-announce and re-enter
        wirings."""
        dst_span = _as_span(dst)
        # never yank accumulated grads out of an in-progress All-Reduce
        while self._dispatch_paused and not self.stopped:
            yield Sleep(0.05)
        if self.stopped or not peer.alive or not peer.serving:
            return
        # re-check after the deferral: the plan was made from an older
        # snapshot, and leaving must neither strand any source stage nor
        # break the span layout's routability
        if not all(self._covering(s, but=peer) for s in peer.stages) \
                or not self._routes_without(peer, dst_span):
            return
        self._retire_assignment(peer)
        peer.executor = self._rebacked_executor(peer, dst_span)
        peer.set_span(dst_span)
        peer.state = peer._fresh_state()
        ok = yield from self._complete_warm_join(peer, dst_span)
        if ok:
            self.metrics["migrations"] += 1

    def _resize_span(self, peer: Peer, new_span: range):
        """Shrink or grow a serving peer's span in place (Varuna-style
        re-partitioning; how spans split into single-stage peers and
        merge back).  Exactly-once order mirrors ``_migrate``: drain +
        release first, THEN swap the executor and state.  Stages kept
        across the resize keep their params locally (an on-device
        snapshot/restore, no transfer time); newly covered stages
        warm-download from whoever covers them.  Refuses when dropping
        a stage would strand it."""
        while self._dispatch_paused and not self.stopped:
            yield Sleep(0.05)
        if self.stopped or not peer.alive or not peer.serving:
            return False
        old_span = peer.stages
        if new_span == old_span:
            return False
        dropped = [s for s in old_span if s not in new_span]
        if not all(self._covering(s, but=peer) for s in dropped):
            return False                       # would strand a stage
        if not self._routes_without(peer, new_span):
            return False                       # coverage != routability
        kept = [s for s in new_span if s in old_span]
        keep_snaps = {}
        if peer.executor is not None:
            for s in kept:
                keep_snaps[s] = peer.executor.snapshot(peer.state, stage=s)
        self._retire_assignment(peer)
        peer.executor = self._rebacked_executor(peer, new_span)
        peer.set_span(new_span)
        peer.state = peer._fresh_state()
        for s, snap in keep_snaps.items():
            peer.executor.restore(peer.state, snap, stage=s)
        peer.serving = False
        for s in new_span:
            if s not in kept:
                yield from self._download_stage_state(peer, s)
                if not peer.alive or self.stopped:
                    return False
        peer.serving = True
        self._announce(peer)
        for w in self.wirings:
            w.move_server(peer.id, [new_span.start])
        self.metrics["span_changes"] += 1
        return True

    def split_span(self, peer: Peer, at: int):
        """Split ``peer``'s span ``[lo, hi)`` at ``at``: a fresh (or
        revived) peer warm-joins on ``[at, hi)`` — downloading those
        stages from the splitting peer, which still serves them — and
        only then does the donor shrink to ``[lo, at)``.  Coverage never
        gaps; the dying-span-peer path needs no choreography at all
        (per-stage snapshots already interoperate, see
        ``_download_stage_state``)."""
        lo, hi = peer.stages.start, peer.stages.stop
        if not (lo < at < hi):
            raise ValueError(f"split point {at} outside ({lo}, {hi})")
        yield from self._join_new_peer(span=range(at, hi))
        yield from self._resize_span(peer, range(lo, at))

    def merge_spans(self, peer: Peer, new_span: range):
        """Grow ``peer`` to ``new_span`` (absorbing adjacent stages it
        downloads from their current holders) — the inverse of
        ``split_span``."""
        yield from self._resize_span(peer, new_span)

    # ================================================== fault injection
    def apply_trace(self, trace: list[TraceEvent]):
        self.sim.spawn(self._trace_proc(trace))

    def _trace_proc(self, trace: list[TraceEvent]):
        for ev in trace:
            dt = ev.time - self.sim.now
            if dt > 0:
                yield Sleep(dt)
            if self.stopped:
                return
            if ev.delta < 0:
                for _ in range(-ev.delta):
                    self._fail_random_peer(region=ev.region)
            else:
                for _ in range(ev.delta):
                    yield from self._join_new_peer(region=ev.region)

    def _fail_random_peer(self, region: Optional[str] = None):
        live = [p for p in self.peers.values() if p.alive]

        def covered(p: Peer) -> bool:
            return all(any(q.serving and s in q.stages
                           for q in live if q is not p)
                       for s in p.stages)
        # never strand a stage: a serving peer may die only if every
        # stage it covers is served by someone else AND the remaining
        # span layout still routes (a span can be the only bridge at a
        # boundary even when all its stages stay covered); a
        # mid-download peer may die only if its target stages are still
        # served
        candidates = [p for p in live
                      if covered(p) and self._routes_without(p, None)]
        if region is not None:
            # zone-correlated reclaim: the event only takes capacity
            # from its zone — out-of-zone peers are never substituted
            candidates = [p for p in candidates
                          if getattr(p, "region", "local") == region]
        if not candidates:
            return
        self._fail_peer(candidates[self.rng.integers(len(candidates))])

    def _fail_peer(self, victim: Peer):
        """Preempt ``victim`` NOW (no stage-coverage guard — callers that
        must not strand a stage check first, e.g. ``_fail_random_peer``;
        stranding a stage is legal and exercises the checkpoint
        fallback)."""
        victim.fail()
        self.metrics["failures"] += 1
        # the victim's accumulated gradients die with it: survivors
        # recompute exactly the indices it held (App. A)
        self._log_releases(self.ledger.release_all(victim.id), victim.id)
        for w in self.wirings:
            w.ban_server(victim.id)
        self._dht_forget(victim)

    def _join_new_peer(self, span: Optional[range] = None,
                       region: Optional[str] = None):
        if span is None:
            # new peers join the most loaded stage (§3.2 "assigned to the
            # optimal pipeline stage by following the same protocol")
            loads = []
            for s in range(self.n_stages):
                group = self._covering(s)
                q = sum(p.queue_size() for p in group)
                loads.append((q + 1) / max(len(group), 1e-9))
            span = _as_span(int(np.argmax(loads)))
        # preemptible instances coming back reuse their peer object (a
        # revived mesh slice can now serve any span: MeshExecutor
        # .for_span(width > 1) builds a MeshSpanExecutor)
        dead = [p for p in self.peers.values() if not p.alive]
        if dead:
            peer = dead[0]
            # a revived peer keeps its backend (a mesh slice coming back
            # IS that mesh slice), re-targeted at the join span
            peer.executor = (self._rebacked_executor(peer, span)
                             if peer.executor is not None
                             else self._span_executor(span))
            if region is not None:
                peer.region = region      # fresh capacity in the
                # event's zone: the revived object is a new instance
            peer.revive(span)
        else:
            peer = Peer(self.sim, self.profile_fn(len(self.peers)), span,
                        executor=self._span_executor(span),
                        region=(region if region is not None
                                else self.region_fn(len(self.peers))))
            self.peers[peer.id] = peer
        self.metrics["joins"] += 1
        ok = yield from self._complete_warm_join(peer, span)
        if ok:
            self.sim.spawn(self._announcer(peer))

    # ================================================== run
    def run(self, until: Optional[float] = None,
            max_steps: Optional[int] = None):
        if max_steps is not None:
            self.scfg = dataclasses.replace(self.scfg, max_steps=max_steps)
            # _sync_loop reads scfg.max_steps each iteration via self.scfg
        self.sim.run(until=until)
        self.stopped = True
        # derived async-tick metrics (all-zero / empty-ratio in sync
        # runs): per-peer executor idle time and how much of the serial
        # wire cost the in-flight transfers hid.  Idle intervals close
        # at the instant training STOPPED, not at `until` — a max_steps
        # run drains the virtual clock to the horizon afterwards, and
        # that dead time is not executor idleness.
        t_end = min(self.sim.now, self._t_stopped
                    if self._t_stopped is not None else self.sim.now)
        m = self.metrics
        m["peer_idle_s"] = {pid: p.total_idle(t_end)
                            for pid, p in self.peers.items()}
        # clamp: an all-span swarm has no peer-to-peer edge to hide, so
        # inflight == serial up to float noise — report 0, not -1e-15
        m["overlap_fraction"] = max(0.0, (
            1.0 - m["wire_inflight_s"] / m["wire_serial_s"]
            if m["wire_serial_s"] > 0 else 0.0))
        return self.metrics

    def throughput(self, window: float = None) -> float:
        """Samples/s over the run (optionally trailing window)."""
        ts, vs = (self.metrics["throughput_t"],
                  self.metrics["throughput_v"])
        if len(ts) < 2:
            return 0.0
        if window:
            import bisect
            lo = bisect.bisect_left(ts, ts[-1] - window)
            lo = min(lo, len(ts) - 2)
            return (vs[-1] - vs[lo]) / max(ts[-1] - ts[lo], 1e-9)
        return vs[-1] / max(ts[-1], 1e-9)
