"""The readers of the xlstm cells' per-layer metrics.

``ssm.slstm_ms`` on a trace recorded on a TPU v5e
(``data/slstm.xplane.pb``): one microbatch of a reduced-width
xlstm-125m (``PROGRAM``) through the direct cell's chain of real
``NumericExecutor`` calls and int8 wire codec
(``bench/drivers/stage_chain.py``), each call in its ``bench.*`` span.
``control.hop_host_ms`` on synthetic program spans.  Each reader gives
nothing where its trace holds nothing for it.

Recorded with ``python tests/bench/test_bench_xlstm_readers.py [out]`` on
the chip (it refuses to run without a TPU), trimmed as
``test_bench_program_spans.trim`` trims, which keeps the ops' ``tf_op``
stats the reader reads."""
from __future__ import annotations

import os
import sys
import types

import pytest

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_tiny import ROOT  # noqa: E402

TRACE = os.path.join(ROOT, "tests", "bench", "data", "slstm.xplane.pb")
OTHER = os.path.join(ROOT, "tests", "bench", "data", "program.xplane.pb")
PROGRAM = {
    "registry": "xlstm-125m", "n_stages": 2, "n_layers": 4, "d_model": 256,
    "n_heads": 4, "n_kv_heads": 4, "head_dim": 64, "d_ff": 0,
    "vocab_size": 1024, "block_pattern": ["mlstm", "slstm"] * 2,
    "ssm": {"state_dim": 16, "chunk": 64}, "param_dtype": "float32",
    "compute_dtype": "bfloat16", "kernels": "pallas",
    "boundary_compression": "int8",
}
SEQ, MB, MICROBATCHES = 256, 2, 1
SCOPE = "repro.ssm.slstm"
# the reader's value on the recorded trace
SLSTM_MS = 0.771613672


def record(out_dir: str) -> None:
    """Warm every program with one microbatch and a step, then trace
    ``MICROBATCHES`` microbatches into ``out_dir``."""
    import jax
    from bench.run import arch_config
    from repro.runtime import build_numeric_executors

    cfg = arch_config(PROGRAM)
    exs = build_numeric_executors(cfg, 2, SEQ, compress="int8")
    states = [ex.init_state(jax.random.PRNGKey(s))
              for s, ex in enumerate(exs)]
    toks = jax.random.randint(jax.random.PRNGKey(2), (MB, SEQ), 0,
                              cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(3), (MB, SEQ), 0,
                                cfg.vocab_size)

    def call(s, name, *a, **kw):
        with jax.profiler.TraceAnnotation(f"bench.stage{s}.{name}"):
            return getattr(exs[s], name)(*a, **kw)

    def microbatch():
        x = call(0, "wire_fwd", call(0, "run_fwd", states[0], toks))
        float(call(1, "run_fwd", states[1], x, labels))
        loss, gx, gp = call(1, "run_bwd", states[1], x, labels=labels)
        call(1, "accumulate", states[1], gp, float(loss), MB * SEQ)
        dy = call(1, "wire_bwd", gx)
        _, _, gp = call(0, "run_bwd", states[0], toks, dy=dy)
        call(0, "accumulate", states[0], gp, None, MB * SEQ)

    def sync():
        jax.block_until_ready([st.grad_acc for st in states])

    microbatch()
    for ex, st in zip(exs, states):
        ex.adopt_step(st, st.params, st.opt)
    sync()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(MICROBATCHES):
        microbatch()
    sync()
    jax.profiler.stop_trace()


def _reader(name):
    from bench.run import load_module
    return load_module(os.path.join(ROOT, "bench", "layer_metrics",
                                    name + ".py"),
                       "t_" + name.replace(".", "_"))


def _r(microbatches=MICROBATCHES, **kw):
    """What ``bench/run.py`` hands a reader, with what the readers read
    put on it directly."""
    return types.SimpleNamespace(microbatches=microbatches, **kw)


@pytest.fixture(scope="module")
def devices():
    from bench import op_scopes
    return op_scopes.load(TRACE)


def test_the_recurrence_runs_three_times_a_stage(devices):
    """Per stage and microbatch the sLSTM loop runs forward, again in the
    backward's recompute, and backward: six runs of scoped ops, each a
    loop of at least ``SEQ`` trips.  Short runs beside them are the
    backward's ops outside its loop (the pre-activations again, the
    recurrent weight's gradient) that the compiler's schedule interleaves
    with the layer's other ops."""
    from bench import op_scopes
    (ops,) = devices
    scoped = [o for o in ops if SCOPE in o.scope]
    loops = []
    for s, e in op_scopes.scope_runs(ops, SCOPE):
        inside = [o for o in scoped if s <= o.start_ps < e]
        if len(inside) >= SEQ:
            loops.append(inside)
        else:
            assert len(inside) <= 2 and e - s < 5e6      # under 5 us
    assert len(loops) == 6 * MICROBATCHES
    backward = [lp for lp in loops if any("transpose(" in o.scope
                                          for o in lp)]
    assert len(backward) == 4 * MICROBATCHES     # recompute and backward


def test_slstm_ms_reads_the_recorded_trace(devices):
    from bench import op_scopes
    got = _reader("ssm.slstm_ms").read(_r(device_ops=devices))
    assert got == pytest.approx(SLSTM_MS, rel=1e-9)
    (ops,) = devices
    busy = (max(o.end_ps for o in ops) - min(o.start_ps for o in ops)) / 1e9
    assert 0 < got < busy
    mlstm = op_scopes.scope_ms(devices, "repro.ssm.mlstm")
    assert mlstm is not None and mlstm > 0


def test_slstm_ms_is_none_without_the_scope():
    """A trace of a program without the scope (the swarm-1b stage), and
    a run without a trace or without microbatches, read nothing."""
    from bench import op_scopes
    reader = _reader("ssm.slstm_ms")
    assert reader.read(_r(device_ops=op_scopes.load(OTHER))) is None
    assert reader.read(_r(device_ops=[])) is None
    assert reader.read(_r(microbatches=0, device_ops=[])) is None


def test_scope_runs_on_synthetic_ops():
    """Other scopes end a run, ops without a scope (copies the compiler
    adds) neither end nor extend one, and the gaps inside a run count."""
    from bench.op_scopes import Op, scope_runs
    a, b = "jit(f)/repro.ssm.slstm/while/body/dot", "jit(f)/add"
    ops = [Op(0, 10, a), Op(12, 20, ""), Op(25, 30, a), Op(31, 40, ""),
           Op(40, 50, b), Op(60, 70, a), Op(80, 90, "")]
    assert scope_runs(ops, "repro.ssm.slstm") == [[0, 30], [60, 70]]
    assert scope_runs(ops, "repro.ssm.mlstm") == []


def _span(name, start, end):
    from bench.program_spans import HostSpan
    return HostSpan(name, start, end - start)


def test_hop_host_ms_counts_control_outside_executor_work():
    """Two microbatches: a forward hop whose numeric work fills all but
    20 us of it and holds no ``repro.ctl`` span; a backward hop with a
    ledger commit inside it; a route between hops; a barrier around an
    adopt.  Only the time outside ``repro.exec``/``repro.wire`` counts,
    and each instant once."""
    spans = [
        _span("repro.ctl.route", 0, 5_000),
        _span("repro.hop.fwd", 10_000, 140_000),
        _span("repro.exec.run_fwd", 20_000, 100_000),
        _span("repro.wire.fwd", 100_000, 130_000),
        _span("repro.ctl.route", 150_000, 160_000),
        _span("repro.hop.bwd", 200_000, 400_000),
        _span("repro.exec.run_bwd", 210_000, 300_000),
        _span("repro.ctl.ledger", 300_000, 320_000),
        _span("repro.exec.accumulate", 320_000, 350_000),
        _span("repro.wire.bwd", 350_000, 390_000),
        _span("repro.swarm.barrier", 500_000, 700_000),
        _span("repro.exec.adopt_step", 600_000, 700_000),
        _span("repro.exec.run_fwd", 800_000, 900_000),   # outside control
    ]
    control_ns = 5_000 + 20_000 + 10_000 + 40_000 + 100_000
    got = _reader("control.hop_host_ms").read(
        _r(microbatches=2, program_spans=spans))
    assert got == pytest.approx(control_ns / 1e6 / 2)


def test_hop_host_ms_is_none_without_control_spans():
    reader = _reader("control.hop_host_ms")
    only_exec = [_span("repro.exec.run_fwd", 0, 10)]
    assert reader.read(_r(program_spans=only_exec)) is None
    assert reader.read(_r(program_spans=[])) is None
    assert reader.read(_r(microbatches=0, program_spans=only_exec)) is None


if __name__ == "__main__":
    import glob
    import tempfile

    from test_bench_program_spans import trim

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("the trace is recorded on a TPU")
    tmp = tempfile.mkdtemp(prefix="slstm-trace-")
    record(tmp)
    (found,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)
    out = sys.argv[1] if len(sys.argv) > 1 else TRACE
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(found, "rb") as f, open(out, "wb") as g:
        g.write(trim(f.read()))
    from bench import op_scopes
    devs = op_scopes.load(out)
    print(f"{out}: {os.path.getsize(out)} bytes; ssm.slstm_ms "
          f"{op_scopes.scope_ms(devs, SCOPE)!r} over {MICROBATCHES}; runs "
          f"{[len(op_scopes.scope_runs(d, SCOPE)) for d in devs]}")
