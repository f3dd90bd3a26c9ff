"""The program's own spans and counters (``repro.obs``).

A tiny stage loop through ``NumericExecutor`` and a tiny numeric
``SwarmRunner`` run under ``jax.profiler.trace``; the trace is read back
with the benchmark's readers: the program's ``repro.*`` spans with
``bench/program_spans.py``, the benchmark's own ``bench.*`` spans with
``bench/trace_reduce.py``, which keeps only those."""
import collections
import os
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from conftest import tiny_dense_config
from repro import obs
from repro.core import SwarmConfig, SwarmRunner
from repro.optim import adamw
from repro.runtime import (PipelineExecutor, build_numeric_executors,
                           compile_stats, get_span_program,
                           get_stage_programs, reset_compile_stats)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEQ, MB, GB = 16, 2, 4


def _load(tmp_path):
    from bench import program_spans, trace_reduce
    return types.SimpleNamespace(
        spans=trace_reduce.load(str(tmp_path)).spans,
        program_spans=program_spans.load(str(tmp_path)))


def _by_name(trace) -> dict:
    out = collections.defaultdict(list)
    for s in trace.program_spans:
        out[s.name].append(s)
    return out


def _inside(inner, outer) -> bool:
    return (inner.line == outer.line and outer.start_ns <= inner.start_ns
            and inner.end_ns <= outer.end_ns)


@pytest.fixture(scope="module")
def stage_loop_trace(tmp_path_factory):
    """Two microbatches through a 2-stage int8 pipeline, by hand, then
    an optimizer-step install on each stage."""
    cfg = tiny_dense_config()
    exs = build_numeric_executors(cfg, 2, SEQ, compress="int8")
    states = [ex.init_state(jax.random.PRNGKey(s))
              for s, ex in enumerate(exs)]
    tokens = jnp.zeros((MB, SEQ), jnp.int32)
    labels = jnp.ones((MB, SEQ), jnp.int32)

    def microbatch():
        h = exs[0].wire_fwd(exs[0].run_fwd(states[0], tokens))
        loss, gx, gp1 = exs[1].run_bwd(states[1], h, labels=labels)
        exs[1].accumulate(states[1], gp1, float(loss), MB * SEQ)
        dy = exs[1].wire_bwd(gx)
        _, _, gp0 = exs[0].run_bwd(states[0], tokens, dy=dy)
        exs[0].accumulate(states[0], gp0, None, MB * SEQ)

    microbatch()                       # compiled outside the trace
    jax.block_until_ready(states[0].grad_acc)
    d = tmp_path_factory.mktemp("stage_loop")
    with jax.profiler.trace(str(d)):
        for _ in range(2):
            microbatch()
        for ex, st in zip(exs, states):
            ex.adopt_step(st, st.params, st.opt)
        jax.block_until_ready([st.grad_acc for st in states])
    return _load(d)


@pytest.fixture(scope="module")
def swarm_trace(tmp_path_factory):
    """One optimizer step of a numeric swarm (2 stages x 2 peers, 2
    trainers, int8 wire)."""
    cfg = tiny_dense_config()
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB * 2, n_trainers=2,
                       rebalance_period=0.0, codec="int8", max_steps=1)
    runner = SwarmRunner(cfg, scfg, adamw(lr=1e-2, grad_clip=0.0),
                         numeric=True, seed=0)
    runner.build(peers_per_stage=2)
    d = tmp_path_factory.mktemp("swarm")
    with jax.profiler.trace(str(d)):
        runner.run(until=1e6)
    assert runner.step == 1
    return _load(d)


def test_executor_and_wire_spans_carry_their_stage(stage_loop_trace):
    spans = _by_name(stage_loop_trace)
    stages = {name: sorted(s.stats["stage"] for s in ss)
              for name, ss in spans.items()}
    assert stages["repro.exec.run_fwd"] == [0, 0]
    assert stages["repro.exec.run_bwd"] == [0, 0, 1, 1]
    assert stages["repro.exec.accumulate"] == [0, 0, 1, 1]
    assert stages["repro.exec.adopt_step"] == [0, 1]
    # the sending stage: stage 0's output forward, stage 1's input back
    assert stages["repro.wire.fwd"] == [0, 0]
    assert stages["repro.wire.bwd"] == [1, 1]
    # the benchmark's own spans stay apart from the program's
    assert stage_loop_trace.spans == []


def test_exec_and_wire_spans_sit_on_the_calling_thread(stage_loop_trace):
    lines = {s.line for s in stage_loop_trace.program_spans}
    assert len(lines) == 1
    # the loop's calls follow one another: no two of its spans overlap
    ss = stage_loop_trace.program_spans
    assert all(a.end_ns <= b.start_ns for a, b in zip(ss, ss[1:]))


def test_hop_spans_hold_executor_and_wire_spans(swarm_trace):
    spans = _by_name(swarm_trace)
    hops = spans["repro.hop.fwd"] + spans["repro.hop.bwd"]
    # 4 microbatches, each one forward and one backward hop per stage
    assert len(spans["repro.hop.fwd"]) == len(spans["repro.hop.bwd"]) == 8
    mbs = collections.Counter(h.stats["mb"] for h in hops)
    assert sorted(mbs) == [0, 1, 2, 3] and set(mbs.values()) == {4}
    for h in hops:
        assert h.stats["stage"] in (0, 1)
        assert str(h.stats["peer"])
    inner = {"repro.hop.fwd": ("repro.exec.run_fwd", "repro.wire.fwd"),
             "repro.hop.bwd": ("repro.exec.run_bwd", "repro.wire.bwd",
                               "repro.exec.accumulate")}
    for name in ("repro.exec.run_fwd", "repro.exec.run_bwd",
                 "repro.exec.accumulate", "repro.wire.fwd",
                 "repro.wire.bwd"):
        for s in spans[name]:
            parents = [h for h in hops if _inside(s, h)]
            assert len(parents) == 1, name
            assert name in inner[parents[0].name]
            if "stage" in s.stats and name != "repro.wire.fwd":
                assert s.stats["stage"] == parents[0].stats["stage"]


def test_no_hop_span_stays_open_across_a_yield(swarm_trace):
    spans = _by_name(swarm_trace)
    by_line = collections.defaultdict(list)
    for h in spans["repro.hop.fwd"] + spans["repro.hop.bwd"]:
        by_line[h.line].append(h)
    for hs in by_line.values():
        hs.sort(key=lambda h: h.start_ns)
        for a, b in zip(hs, hs[1:]):
            assert a.end_ns <= b.start_ns, (a.stats, b.stats)


def test_barrier_span_holds_the_adopts(swarm_trace):
    spans = _by_name(swarm_trace)
    barriers = spans["repro.swarm.barrier"]
    assert barriers and {b.stats["step"] for b in barriers} == {0}
    adopts = spans["repro.exec.adopt_step"]
    assert len(adopts) == 4                 # 2 stages x 2 peers
    assert all(any(_inside(a, b) for b in barriers) for a in adopts)


def test_counters():
    obs.reset()
    obs.count("a")
    obs.count("a", 2)
    obs.count(("b", 1))
    assert obs.counters() == {"a": 3, ("b", 1): 1}
    got = obs.counters()
    got["a"] = 0                        # a copy
    assert obs.counters()["a"] == 3
    obs.reset()
    assert obs.counters() == {}


def test_span_is_a_profiler_annotation():
    s = obs.span("exec.run_fwd", stage=1)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass


def test_compile_stats_keep_their_counts_in_the_store():
    """The retrace scenario of the shared compile cache: 4 peers x 2
    stages trace one fwd and one bwd of stage 0 and one fwd_save and one
    bwd_saved of the last stage (every last-stage backward of these runs
    consumes its forward's residuals, so its recompute bwd is never
    traced), a second runner none."""
    reset_compile_stats()
    cfg = tiny_dense_config()
    scfg = SwarmConfig(n_stages=2, microbatch_size=MB, seq_len=SEQ,
                       global_batch=GB, n_trainers=3, rebalance_period=0.0,
                       codec="none", max_steps=1)
    opt = adamw(lr=1e-2, grad_clip=0.0)
    for seed in (0, 1):
        r = SwarmRunner(cfg, scfg, opt, numeric=True, seed=seed)
        r.build(peers_per_stage=4)
        r.run(until=1e6)
        st = compile_stats()
        assert set(st) == {"traces", "per_key"}
        assert st["traces"] == 4, st["per_key"]
        assert all(v == 1 for v in st["per_key"].values())
        assert sorted(k[-2] for k in st["per_key"]) == \
            ["bwd", "bwd_saved", "fwd", "fwd_save"]
    # the store holds the trace counts and the two runs' 2 + 2 saved
    # last-stage backwards, nothing else
    assert obs.counters() == {**{("xla_trace", k): 1 for k in st["per_key"]},
                              ("exec.bwd_saved", 1): 4}
    reset_compile_stats()
    assert compile_stats() == {"traces": 0, "per_key": {}}


def test_programs_have_distinct_names():
    from repro.runtime import base
    from repro.serve.programs import get_session_program
    cfg = tiny_dense_config()
    progs = get_stage_programs(cfg, 2, SEQ, "int8")
    assert [(p.fwd.__name__, p.bwd.__name__) for p in progs] == \
        [("stage_fwd", "stage_bwd")] * 2
    span = get_span_program(cfg, 2, SEQ, (0, 2), "int8")
    assert (span.fwd.__name__, span.bwd.__name__) == \
        ("span_fwd", "span_bwd")
    serve = get_session_program(cfg, 2, (0, 2), SEQ)
    assert (serve.prefill.__name__, serve.decode.__name__) == \
        ("prefill", "decode")
    assert base._accumulate.__name__ == "fold_grads"
    ex = PipelineExecutor(cfg, 2, SEQ, (0, 2), compress="int8")
    st = ex.init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((MB, SEQ), jnp.int32)
    text = ex.prog.bwd.lower(ex._params_tuple(st), tokens,
                             tokens).as_text()
    assert "jit_span_bwd" in text
