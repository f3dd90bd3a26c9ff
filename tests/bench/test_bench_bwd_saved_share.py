"""``executor.bwd_saved_share``: the share of the last stage's traced
``repro.exec.run_bwd`` spans that hold a ``repro.exec.bwd_saved`` span.

It reads nothing on the trace recorded on a TPU v5e before the program
had the saved backward (``data/program.xplane.pb``), reads hand-built
span lists, and reads a trace of the real executor that the test records
on the CPU, where two backwards consume their forward's residuals and one
runs the forward again."""
from __future__ import annotations

import os
import types

import pytest

from bench_tiny import ROOT

TRACE = os.path.join(ROOT, "tests", "bench", "data", "program.xplane.pb")
NAME = "executor.bwd_saved_share"


def _reader():
    from bench.run import load_module
    return load_module(os.path.join(ROOT, "bench", "layer_metrics",
                                    NAME + ".py"), "t_bwd_saved_share")


def _r(spans, n_stages=3):
    return types.SimpleNamespace(
        trace=types.SimpleNamespace(devices=[], spans=[], enqueues=[],
                                    calls=[], span_starts=None),
        microbatches=2, program_spans=spans,
        config={"causal": True, "n_stages": n_stages})


def _span(name, start, end, stage=2, line="main"):
    from bench.program_spans import HostSpan
    return HostSpan(name, start, end - start, line=line,
                    stats={"stage": stage})


def _bwd(start, saved, stage=2, line="main"):
    """A ``run_bwd`` span over [start, start + 10], holding a
    ``bwd_saved`` span where ``saved``."""
    out = [_span("repro.exec.run_bwd", start, start + 10, stage, line)]
    if saved:
        out.append(_span("repro.exec.bwd_saved", start + 1, start + 9,
                         stage, line))
    return out


def test_reads_nothing_on_a_program_without_the_saved_backward():
    from bench import program_spans
    spans = program_spans.load(TRACE)
    assert sum(s.name == "repro.exec.run_bwd" for s in spans) == 2
    assert not any(s.name == "repro.exec.bwd_saved" for s in spans)
    assert _reader().read(_r(spans)) is None
    assert _reader().read(_r([])) is None


@pytest.mark.parametrize("pattern,share", [
    ((True, True, True, True), 100.0),
    ((True, False, True, False), 50.0),
    ((False, False, False, True), 25.0),
])
def test_reads_the_share_of_saved_backwards(pattern, share):
    spans = [s for i, saved in enumerate(pattern)
             for s in _bwd(100 * i, saved)]
    assert _reader().read(_r(spans)) == share


def test_only_the_last_stage_and_its_own_thread_count():
    spans = (_bwd(0, True) + _bwd(100, False)
             # an earlier stage's backwards never hold a saved one
             + _bwd(200, False, stage=1) + _bwd(300, False, stage=0)
             # a saved span on another thread is not this backward's
             + _bwd(400, False)
             + [_span("repro.exec.bwd_saved", 401, 409, line="other")])
    assert _reader().read(_r(spans)) == pytest.approx(100.0 / 3)
    # read as a 2-stage pipeline, stage 1's backward held no saved one
    assert _reader().read(_r(spans, n_stages=2)) == 0.0


def test_reads_a_trace_of_the_executor(tmp_path):
    """A tiny 2-stage pipeline's last stage, driven as the stage loop
    drives it, twice with its forward first and once without."""
    import jax
    import jax.numpy as jnp
    from bench import program_spans
    from repro.models.config import ArchConfig
    from repro.runtime import build_numeric_executors

    cfg = ArchConfig(name="tiny", family="dense", n_layers=4, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                     head_dim=16, compute_dtype="float32",
                     param_dtype="float32")
    ex = build_numeric_executors(cfg, 2, 16, compress="int8")[-1]
    state = ex.init_state(jax.random.PRNGKey(0))
    inp = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    labels = jnp.ones((2, 16), jnp.int32)

    def microbatch(fwd_first):
        if fwd_first:
            float(ex.run_fwd(state, inp, labels))
        loss, gx, gp = ex.run_bwd(state, inp, labels=labels)
        ex.accumulate(state, gp, float(loss), 32)

    microbatch(True)                   # compiled outside the trace
    microbatch(False)
    jax.block_until_ready(state.grad_acc)
    with jax.profiler.trace(str(tmp_path)):
        microbatch(True)
        microbatch(False)
        microbatch(True)
        jax.block_until_ready(state.grad_acc)
    spans = program_spans.load(str(tmp_path))
    assert sum(s.name == "repro.exec.bwd_saved" for s in spans) == 2
    got = _reader().read(_r(spans, n_stages=2))
    assert got == pytest.approx(200.0 / 3)
