"""Trainer processes (paper §3.2 / App. C).

Trainers own no parameters and no GPU: they form microbatches and route
them through the pipeline as a chain of *hops* — one peer per contiguous
stage span — forward, then back, using stochastic wiring.  A hop may be a
single-stage peer or a span peer (``PipelineExecutor``) serving several
consecutive stages in one jitted step; either way the trainer only ever
enters a peer at its span START, and the activation bytes it moves are
charged per *hop edge* — fused intra-span boundaries cross nothing.  On a
peer failure anywhere along the path the trainer bans the peer and
re-routes — backward can go to a *different* peer than forward because
stages recompute activations from the boundary input (activation
checkpointing, App. A); a re-routed backward hop must cover the SAME span
(the cotangent in hand is pinned to that span's edges).

The trainer is backend- and codec-agnostic: stage execution and wire
handling (including the int8 round-trip that used to live here) go
through the peer's :class:`repro.runtime.StageExecutor`, so a path may
mix single-device, mesh-backed, and span peers freely.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.sim import Sim, Sleep
from repro.core.peer import Peer, PeerFailure
from repro.core.wiring import StochasticWiring

Tree = Any


@dataclasses.dataclass
class Microbatch:
    index: int
    tokens: Any = None          # numeric mode: jnp [b, S]
    labels: Any = None
    size: int = 1               # sequences
    n_tokens: int = 0
    attempt: int = 1            # provenance: ledger dispatch attempt


def _in_hop_span(kind: str, mb: Microbatch, stage: int, peer: Peer,
                 thunk: Callable[[], Any]) -> Callable[[], Any]:
    """``thunk`` run inside the ``repro.hop.<kind>`` span.  The span
    wraps the hop's numeric work alone: it opens and closes where the
    peer runs the thunk, never across a sim yield."""
    def hop():
        with obs.span("hop." + kind, mb=mb.index, stage=stage,
                      peer=peer.id):
            return thunk()
    return hop


@dataclasses.dataclass
class _Hop:
    """One completed forward hop: which peer ran which span on what."""
    peer: Peer
    span: range
    inp: Any                    # the hop's boundary input (for recompute)


class Trainer:
    def __init__(self, sim: Sim, swarm, wiring: StochasticWiring,
                 name: str, *, max_retries: int = 50,
                 refresh_interval: float = 30.0):
        self.sim = sim
        self.swarm = swarm
        self.wiring = wiring
        self.name = name
        self.max_retries = max_retries
        self.refresh_interval = refresh_interval
        self._last_refresh = -1e9

    # ------------------------------------------------------------ helpers
    def _maybe_refresh(self):
        if self.sim.now - self._last_refresh >= self.refresh_interval:
            self.wiring.refresh_from_dht(
                self.swarm.dht, self.swarm.announced_stages())
            self._last_refresh = self.sim.now

    def _pick(self, stage: int, span: Optional[range] = None):
        """Choose a live peer whose span STARTS at ``stage`` (optionally
        covering exactly ``span`` — the backward re-route constraint),
        or None when unavailable.  Runs in the ``repro.ctl.route`` span."""
        with obs.span("ctl.route", stage=stage):
            self._maybe_refresh()
            peer_id = self.wiring.choose_server(stage)
            if peer_id is None:
                return None
            peer = self.swarm.peers.get(peer_id)
            if peer is None or not peer.alive or not peer.serving \
                    or peer.stage != stage:
                self.wiring.ban_server(peer_id)
                return None
            if span is not None and peer.stages != span:
                # a healthy peer with a different span: not bannable,
                # just unusable for this cotangent — the caller
                # retries/fails
                return None
            return peer

    def _boundary_bytes(self, mb: Microbatch,
                        boundary: Optional[int] = None) -> float:
        """Wire bytes for one edge.  ``boundary`` indexes the pipeline
        boundary actually crossed (between stages b and b+1) so the
        swarm's stage plan can price it per kind — a whisper boundary
        carries encoder state + token ids besides the hidden states; an
        expert-sharded MoE boundary pays per routed token copy.  None
        (or an out-of-range index, e.g. the last hop's loss-side edge)
        falls back to the uniform hidden-state pricing."""
        return self.swarm.boundary_nbytes(mb, boundary)

    # ------------------------------------------------------------ core
    def run_microbatch(self, mb: Microbatch):
        """Generator process: one microbatch through fwd+bwd. Yields sim
        commands; returns (loss_sum, ok)."""
        swarm = self.swarm
        S = swarm.n_stages
        numeric = swarm.numeric
        # async tick: boundary tensors ride the peers' NIC links
        # (in-flight, priced end-to-end at the pair's bottleneck) instead
        # of two blocking Sleeps, and stage math goes through the
        # executors' dispatch/collect pair.  The sync path is untouched.
        overlap = bool(getattr(swarm, "overlap", False))
        hops: list[_Hop] = []

        # ---------------- forward (hop chain over spans)
        x = mb.tokens if numeric else None
        s = 0
        retries = 0
        while s < S:
            peer = self._pick(s)
            if peer is None:
                # dead end: NO live peer's span even starts at this
                # boundary (earlier hop choices walked into a gap of the
                # span layout, or a resize moved the entry away) — fail
                # the attempt NOW so the re-issue re-rolls the path,
                # instead of sleeping out max_retries seconds.  Only
                # past the first hop: its yields advanced the clock, so
                # the retry loop around re-issues cannot spin timeless
                # (at s == 0 the plain Sleep-retry path below waits for
                # a joiner the usual way).
                if s > 0 and not any(p.alive and p.stages.start == s
                                     for p in swarm.peers.values()):
                    return None, False
                retries += 1
                if retries > self.max_retries:
                    return None, False
                yield Sleep(1.0)
                continue
            span = peer.stages
            covers_last = span.stop == S
            nbytes = self._boundary_bytes(mb, s - 1) if s > 0 else \
                mb.n_tokens * 4.0
            t0 = self.sim.now
            try:
                if overlap:
                    # one in-flight transfer prices the whole edge at the
                    # pair's bottleneck (vs the serial send + recv pair);
                    # the sender's uplink is occupied, never its queue
                    prev = hops[-1].peer if hops else None
                    serial = peer.profile.recv_time(nbytes) + (
                        prev.profile.send_time(nbytes)
                        if prev is not None else 0.0)
                    tw = self.sim.now
                    yield peer.recv(nbytes, frm=prev).wait()
                    swarm.count_inflight_wire(
                        serial, self.sim.now - tw, nbytes)
                else:
                    yield Sleep(peer.profile.recv_time(nbytes))
                if s > 0:        # a real host boundary crossing
                    swarm.count_wire_bytes(nbytes)
                inp = x

                if numeric:
                    # the executor runs the whole span AND produces the
                    # wire tensor that crosses to the next hop (codec
                    # round trips, mesh host-gathers — all backend-owned;
                    # fused boundaries never surface here)
                    if overlap:
                        # dispatch/collect: the jit is issued the moment
                        # the thunk runs; collect() blocks on the futures
                        if covers_last:
                            thunk = (lambda _p=peer, _i=inp:
                                     _p.executor.dispatch_fwd(
                                         _p.state, _i, mb.labels)())
                        else:
                            thunk = (lambda _p=peer, _i=inp:
                                     _p.executor.wire_fwd(
                                         _p.executor.dispatch_fwd(
                                             _p.state, _i)()))
                    elif covers_last:
                        thunk = (lambda _p=peer, _i=inp:
                                 _p.executor.run_fwd(_p.state, _i,
                                                     mb.labels))
                    else:
                        thunk = (lambda _p=peer, _i=inp:
                                 _p.executor.wire_fwd(
                                     _p.executor.run_fwd(_p.state, _i)))
                    thunk = _in_hop_span("fwd", mb, s, peer, thunk)
                else:
                    thunk = lambda: None
                ct = swarm.compute_time(peer, "fwd", s, mb)
                y = yield peer.submit("fwd", ct, thunk).wait()
                # response travels back / onward
                if overlap:
                    if covers_last:     # the scalar loss back to us
                        yield peer.send(64.0).wait()
                    # else: the next hop's recv prices this edge once,
                    # end-to-end — nothing to wait on here
                else:
                    yield Sleep(peer.profile.send_time(
                        self._boundary_bytes(mb, span.stop - 1)
                        if not covers_last else 64.0))
                self.wiring.observe(peer.id, self.sim.now - t0)
                hops.append(_Hop(peer, span, inp))
                x = y
                s = span.stop
                retries = 0
            except PeerFailure:
                self.wiring.ban_server(peer.id)
                retries += 1
                if retries > self.max_retries:
                    return None, False

        # ---------------- backward (reverse hop chain, re-routable)
        loss_sum = float(x) if numeric else 0.0
        dy = None
        bwd_prev: Optional[Peer] = None   # who produced the dy in hand
        h = len(hops) - 1
        retries = 0
        while h >= 0:
            hop = hops[h]
            peer = hop.peer
            if peer is None or not peer.alive or not peer.serving \
                    or peer.stages != hop.span:
                peer = self._pick(hop.span.start, span=hop.span)
            if peer is None:
                # the cotangent in hand is pinned to this hop's span
                # edges: if NO live peer still has that exact span (a
                # resize re-partitioned the pipeline; a mid-download
                # peer that will serve it again counts), fail the
                # attempt NOW — the ledger re-issues and the fresh
                # forward follows the new span layout, instead of
                # sleeping out max_retries against an impossible route
                if not any(p.alive and p.stages == hop.span
                           for p in swarm.peers.values()):
                    return None, False
                retries += 1
                if retries > self.max_retries:
                    return None, False
                yield Sleep(1.0)
                continue
            covers_last = hop.span.stop == S
            # the cotangent in hand crossed the boundary at the hop's
            # top edge (out-of-range for the last hop: uniform fallback)
            nbytes = self._boundary_bytes(mb, hop.span.stop - 1)
            t0 = self.sim.now
            try:
                if overlap:
                    serial = peer.profile.recv_time(nbytes) + (
                        bwd_prev.profile.send_time(nbytes)
                        if bwd_prev is not None else 0.0)
                    tw = self.sim.now
                    yield peer.recv(nbytes, frm=bwd_prev).wait()
                    swarm.count_inflight_wire(
                        serial, self.sim.now - tw, nbytes)
                else:
                    yield Sleep(peer.profile.recv_time(nbytes))
                if not covers_last:      # a cotangent really crossed
                    swarm.count_wire_bytes(nbytes)
                if numeric:
                    if overlap:
                        if covers_last:
                            def thunk(_p=peer, _i=hop.inp):
                                collect = _p.executor.dispatch_bwd(
                                    _p.state, _i, labels=mb.labels)
                                loss, gx, gp = collect()
                                self.swarm.accumulate(_p, gp, mb,
                                                      float(loss))
                                return _p.executor.wire_bwd(gx)
                        else:
                            def thunk(_p=peer, _i=hop.inp, _dy=dy):
                                collect = _p.executor.dispatch_bwd(
                                    _p.state, _i, dy=_dy)
                                _, gx, gp = collect()
                                self.swarm.accumulate(_p, gp, mb, None)
                                return _p.executor.wire_bwd(gx)
                    elif covers_last:
                        def thunk(_p=peer, _i=hop.inp):
                            loss, gx, gp = _p.executor.run_bwd(
                                _p.state, _i, labels=mb.labels)
                            # the ledger admits each covered (stage,
                            # index) at most once per round — a re-issued
                            # attempt only folds the stages that lost it
                            self.swarm.accumulate(_p, gp, mb, float(loss))
                            # the cotangent crosses back as a wire tensor
                            # (int8 round-trip etc. — executor-owned)
                            return _p.executor.wire_bwd(gx)
                    else:
                        def thunk(_p=peer, _i=hop.inp, _dy=dy):
                            _, gx, gp = _p.executor.run_bwd(_p.state, _i,
                                                            dy=_dy)
                            self.swarm.accumulate(_p, gp, mb, None)
                            return _p.executor.wire_bwd(gx)
                    thunk = _in_hop_span("bwd", mb, hop.span.start, peer,
                                         thunk)
                else:
                    def thunk(_p=peer):
                        self.swarm.accumulate(_p, None, mb, None)
                        return None
                ct = swarm.compute_time(peer, "bwd", hop.span.start, mb)
                gx = yield peer.submit("bwd", ct, thunk).wait()
                if overlap:
                    if hop.span.start == 0:   # grads landed: tiny ack
                        yield peer.send(64.0).wait()
                    # else: the next hop's recv prices this edge
                else:
                    yield Sleep(peer.profile.send_time(
                        self._boundary_bytes(mb, hop.span.start - 1)
                        if hop.span.start > 0 else 64.0))
                self.wiring.observe(peer.id, self.sim.now - t0)
                dy = gx
                bwd_prev = peer
                h -= 1
                retries = 0
            except PeerFailure:
                self.wiring.ban_server(peer.id)
                retries += 1
                if retries > self.max_retries:
                    return None, False

        return loss_sum, True

    def run(self):
        """Main trainer loop: pull microbatch indices until stopped."""
        swarm = self.swarm
        while not swarm.stopped:
            mb = swarm.next_microbatch()
            if mb is None:
                yield Sleep(0.5)
                continue
            result = yield from self.run_microbatch(mb)
            loss_sum, ok = result if result is not None else (None, False)
            swarm.microbatch_done(mb, ok)
