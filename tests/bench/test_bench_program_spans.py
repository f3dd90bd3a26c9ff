"""The readers of the program's own spans, on a trace recorded on a TPU
v5e (``data/program.xplane.pb``): two microbatches of a reduced-width
swarm-1b last stage (``PROGRAM``) through the real ``NumericExecutor``
and int8 wire codec, driven as the stage loop drives it (``bench.*``
spans around ``run_fwd`` with its loss read, ``run_bwd``,
``accumulate``, ``wire_bwd``), then one ``adopt_step``.  The program's
``repro.exec.*`` and ``repro.wire.*`` spans sit inside those; the
readers find them with ``bench/program_spans.py``.

Recorded with ``python tests/bench/test_bench_program_spans.py [out]``
on the chip (it refuses to run without a TPU), which keeps what the
reduction reads: ``trim`` drops the programs' HLO protos (the
``/host:metadata`` plane) and the ops' Python source stacks, three
quarters of the file."""
from __future__ import annotations

import collections
import os
import sys
import types

import pytest

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_tiny import ROOT  # noqa: E402

TRACE = os.path.join(ROOT, "tests", "bench", "data", "program.xplane.pb")
MICROBATCHES = 2
PROGRAM = {
    "registry": "swarm-1b", "n_stages": 3, "n_layers": 3, "d_model": 512,
    "n_heads": 4, "n_kv_heads": 4, "head_dim": 128, "d_ff": 2048,
    "vocab_size": 1024, "share_groups": 3, "param_dtype": "float32",
    "compute_dtype": "bfloat16", "kernels": "pallas",
    "boundary_compression": "int8",
}
SEQ, MB = 512, 2
STAGE_PROGRAMS = {"jit_stage_fwd", "jit_stage_bwd"}
# the eager int8 round trip: four reshapes around the Pallas call
WIRE_PROGRAMS = {"jit_reshape", "jit_wrapped"}
# the readers' values on the recorded trace
PROGRAMS_PER_MB = 22.0
CODEC_SHARE = 14.554872533771425
WIRE_HOST_MS = 118.2552995
IDLE_MS = 124.69242


def record(out_dir: str) -> None:
    """Warm every program, then trace ``MICROBATCHES`` microbatches and
    one ``adopt_step`` into ``out_dir``."""
    import jax
    import jax.numpy as jnp
    from bench.run import arch_config
    from repro.runtime import build_numeric_executors

    cfg = arch_config(PROGRAM)
    exs = build_numeric_executors(cfg, PROGRAM["n_stages"], SEQ,
                                  compress="int8")
    ex = exs[-1]
    state = ex.init_state(jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (MB, SEQ, cfg.d_model),
                          jnp.float32)
    inp = exs[-2].wire_fwd(h)
    labels = jax.random.randint(jax.random.PRNGKey(2), (MB, SEQ), 0,
                                cfg.vocab_size)

    def span(name):
        return jax.profiler.TraceAnnotation("bench." + name)

    def microbatch():
        with span("run_fwd"):
            float(ex.run_fwd(state, inp, labels))
        with span("run_bwd"):
            loss, gx, gp = ex.run_bwd(state, inp, labels=labels)
        with span("accumulate"):
            ex.accumulate(state, gp, float(loss), MB * SEQ)
        with span("wire_bwd"):
            return ex.wire_bwd(gx)

    def step():
        with span("adopt_step"):
            ex.adopt_step(state, state.params, state.opt)

    jax.block_until_ready((microbatch(), step(), state.grad_acc))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(MICROBATCHES):
        out = microbatch()
    step()
    jax.block_until_ready((out, state.grad_acc))
    jax.profiler.stop_trace()


def trim(raw: bytes) -> bytes:
    """A recorded ``XSpace`` without the ``/host:metadata`` plane and
    the ``source_stack`` stats, which no reader uses."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace.FromString(raw)
    planes = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(planes)
    for plane in space.planes:
        drop = {k for k, m in plane.stat_metadata.items()
                if m.name == "source_stack"}
        for meta in plane.event_metadata.values():
            keep = [st for st in meta.stats if st.metadata_id not in drop]
            del meta.stats[:]
            meta.stats.extend(keep)
    return space.SerializeToString()


@pytest.fixture(scope="module")
def trace():
    from bench import trace_reduce
    return trace_reduce.load(TRACE)


@pytest.fixture(scope="module")
def spans():
    from bench import program_spans
    return program_spans.load(TRACE)


def _reader(name):
    from bench.run import load_module
    return load_module(os.path.join(ROOT, "bench", "layer_metrics",
                                    name + ".py"),
                       "t_" + name.replace(".", "_"))


def _r(trace, spans=None):
    """What ``bench/run.py`` hands a reader, with the program spans
    given where ``spans`` is a list."""
    from bench import flops
    r = types.SimpleNamespace(
        trace=trace, window_s=1.0, microbatches=MICROBATCHES, tokens=1,
        counters={}, peak=flops.peaks("TPU v5 lite"), chips=1,
        flops_per_token=1.0, mb_tokens=MB * SEQ, memory_peak_bytes=1,
        traffic={}, config={"causal": True})
    if spans is not None:
        r.program_spans = spans
    return r


def _raw():
    """Host spans and device events straight from the file, without the
    reduction: {name: [(start, end)]} of the ``repro.*`` and ``bench.*``
    spans, the device's modules and its op intervals."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(TRACE)
    spans = collections.defaultdict(list)
    modules, ops = [], []
    for plane in data.planes:
        for ln in plane.lines:
            for e in ln.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if plane.name == "/host:CPU" and \
                        e.name.startswith(("repro.", "bench.")):
                    spans[e.name].append(iv)
                elif plane.name == "/device:TPU:0" and \
                        ln.name == "XLA Modules":
                    modules.append((e.name.split("(")[0],) + iv)
                elif plane.name == "/device:TPU:0" and ln.name == "XLA Ops":
                    ops.append(iv)
    return spans, modules, ops


def test_the_program_spans_are_kept_apart(trace, spans):
    names = collections.Counter(s.name for s in spans)
    assert names == {"repro.exec.run_fwd": 2, "repro.exec.run_bwd": 2,
                     "repro.exec.accumulate": 2, "repro.wire.bwd": 2,
                     "repro.exec.adopt_step": 1}
    assert all(s.stats == {"stage": 2} for s in spans)
    assert len({s.line for s in spans}) == 1
    assert {s.name for s in trace.spans} == {
        "bench.run_fwd", "bench.run_bwd", "bench.accumulate",
        "bench.wire_bwd", "bench.adopt_step"}
    from bench import trace_reduce
    gaps = dict(trace_reduce.idle_gaps(trace, n=100))
    assert all(k.startswith("bench.") or k == "host outside benchmark spans"
               for k in gaps)


def test_each_program_span_sits_in_its_benchmark_span(trace, spans):
    for s in spans:
        call = s.name.rsplit(".", 1)[-1]
        want = "bench.wire_bwd" if s.name == "repro.wire.bwd" \
            else "bench." + call
        assert any(b.name == want and b.start_ns <= s.start_ns
                   and s.end_ns <= b.end_ns for b in trace.spans), s.name


def test_programs_per_microbatch_is_a_hand_count(trace, spans):
    """Every program in the traced window was launched from inside a
    program span: per microbatch the stage forward, the stage backward,
    the fold and the wire's five, and the adopt's two a leaf (zeros in
    the accumulator's dtype) once for both microbatches."""
    _, modules, _ = _raw()
    names = collections.Counter(m[0] for m in modules)
    assert names["jit_stage_fwd"] == names["jit_stage_bwd"] == \
        names["jit_fold_grads"] == names["jit_wrapped"] == MICROBATCHES
    assert names["jit_reshape"] == 4 * MICROBATCHES
    leaves = names["jit_broadcast_in_dim"]
    assert names["jit_convert_element_type"] == leaves
    assert sum(names.values()) == 8 * MICROBATCHES + 2 * leaves
    got = _reader("executor.programs_per_mb").read(_r(trace, spans))
    assert got == len(modules) / MICROBATCHES == PROGRAMS_PER_MB


def test_codec_share_is_wire_device_time_over_stage_time(trace, spans):
    """By name: the wire round trip's programs over the stage
    programs, device time."""
    _, modules, _ = _raw()
    stage = sum(e - s for n, s, e in modules if n in STAGE_PROGRAMS)
    wire = sum(e - s for n, s, e in modules if n in WIRE_PROGRAMS)
    got = _reader("executor.codec_share").read(_r(trace, spans))
    assert got == pytest.approx(100.0 * wire / stage, rel=1e-12)
    assert 0 < got < 100
    assert got == pytest.approx(CODEC_SHARE, rel=1e-6)


def test_wire_host_ms_is_the_wire_spans_length(trace, spans):
    raw, _, _ = _raw()
    hand = sum(e - s for s, e in raw["repro.wire.bwd"]) / 1e6
    got = _reader("executor.wire_host_ms").read(_r(trace, spans))
    assert got == pytest.approx(hand / MICROBATCHES, rel=1e-12)
    assert got == pytest.approx(WIRE_HOST_MS, rel=1e-6)


def test_idle_ms_counts_gaps_inside_executor_and_wire_spans(trace, spans):
    raw, _, ops = _raw()
    host = [iv for name, ivs in raw.items()
            if name.startswith(("repro.exec.", "repro.wire."))
            for iv in ivs]
    merged = []
    for s, e in sorted(ops):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    idle = total = 0.0
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        total += s1 - e0
        if any(a <= (e0 + s1) / 2 <= b for a, b in host):
            idle += s1 - e0
    got = _reader("executor.idle_ms").read(_r(trace, spans))
    assert got == pytest.approx(idle / 1e6 / MICROBATCHES, rel=1e-12)
    assert 0 < got <= total / 1e6 / MICROBATCHES
    assert got == pytest.approx(IDLE_MS, rel=1e-6)


def test_the_accepted_readers_read_as_before(trace):
    """``stage.bwd_ms`` still finds the backward programs, now named
    ``jit_stage_bwd``, by the benchmark's ``run_bwd`` spans."""
    _, modules, _ = _raw()
    bwd = sum(e - s for n, s, e in modules if n == "jit_stage_bwd")
    got = _reader("stage.bwd_ms").read(_r(trace))
    assert got == pytest.approx(bwd / 1e6 / MICROBATCHES, rel=1e-12)
    assert _reader("kernel.wire_qdq_roofline").read(_r(trace)) > 0


READERS = ("executor.idle_ms", "executor.wire_host_ms",
           "executor.codec_share", "executor.programs_per_mb")


def test_a_trace_without_program_spans_reads_nothing(trace):
    """A program without spans (the parent of this reader) gives no
    value, and no reader raises; nor does a run whose trace file is not
    to be found."""
    empty = types.SimpleNamespace(devices=[], spans=[], enqueues=[],
                                  calls=[], span_starts=None)
    for name in READERS:
        assert _reader(name).read(_r(trace, [])) is None
        assert _reader(name).read(_r(empty, [])) is None
        assert _reader(name).read(_r(empty)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_reads_the_trace_file_of_the_run_calling_it(
        trace, spans, name):
    """``bench/run.py`` hands its readers the reduced trace alone; the
    program spans then come from the file under its ``trace_dir``."""
    trace_dir = TRACE  # noqa: F841 -- the name bench/run.py gives it
    r = _r(trace)
    got = _reader(name).read(r)
    assert [s.name for s in r.program_spans] == [s.name for s in spans]
    assert got == _reader(name).read(_r(trace, spans))
    assert got is not None


if __name__ == "__main__":
    import glob
    import tempfile

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("the trace is recorded on a TPU")
    tmp = tempfile.mkdtemp(prefix="program-trace-")
    record(tmp)
    (found,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)
    out = sys.argv[1] if len(sys.argv) > 1 else TRACE
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(found, "rb") as f, open(out, "wb") as g:
        g.write(trim(f.read()))
    print(f"{out}: {os.path.getsize(out)} bytes")
