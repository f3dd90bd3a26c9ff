"""SWARM parallelism — the paper's primary contribution.

sim/dht/wiring/rebalance/peer/trainer/swarm compose the decentralized
pipeline-parallel system of §3.2; square_cube is the §3.1 analysis;
faults supplies the preemption traces of §4.4/App. I.
"""
from repro.core.sim import Sim, Sleep, Event, Resource
from repro.core.dht import DHT
from repro.core.ledger import MicrobatchLedger
from repro.core.wiring import StochasticWiring
from repro.core.rebalance import plan_migration, optimal_assignment, \
    pipeline_throughput, Migration
from repro.core.peer import Peer, DeviceProfile, PeerFailure, StageState, \
    T4, V100, A100
from repro.core.swarm import SwarmRunner, SwarmConfig
from repro.core.faults import synth_preemptible_trace, TraceEvent
from repro.core.reference import reference_losses

__all__ = [
    "Sim", "Sleep", "Event", "Resource", "DHT", "MicrobatchLedger",
    "StochasticWiring",
    "plan_migration", "optimal_assignment", "pipeline_throughput",
    "Migration", "Peer", "DeviceProfile", "PeerFailure", "StageState",
    "T4", "V100", "A100", "SwarmRunner", "SwarmConfig",
    "synth_preemptible_trace", "TraceEvent", "reference_losses",
]
