"""The reduction from trace to metrics, on a small trace recorded on a TPU
v5e (``data/small.xplane.pb``): three rounds of a Pallas flash forward
(B*H 2, seq 512, head 128) in a ``bench.run_fwd`` span, an eager int8 wire
round trip of 4 x 256 x 256 values in ``bench.wire_bwd``, a 2048^2 bf16
matmul in ``bench.run_bwd``, and a 3 ms host sleep in ``bench.host_wait``."""
from __future__ import annotations

import os
import types

import pytest

from bench_tiny import ROOT

TRACE = os.path.join(ROOT, "tests", "bench", "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    from bench import trace_reduce
    return trace_reduce.load(TRACE)


def _reader(name):
    from bench.run import load_module
    return load_module(os.path.join(ROOT, "bench", "layer_metrics",
                                    name + ".py"), "t_" + name.replace(".", "_"))


def _r(trace, **kw):
    from bench import flops
    base = dict(trace=trace, window_s=0.4, microbatches=3, tokens=1,
                counters={}, peak=flops.peaks("TPU v5 lite"), chips=1,
                flops_per_token=1.0, mb_tokens=1, memory_peak_bytes=1,
                traffic={}, config={"causal": True})
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_union_merges_overlaps():
    from bench.trace_reduce import union
    assert union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == \
        [[0, 4], [5, 7], [8, 9]]


def test_busy_is_the_union_of_op_intervals(trace):
    from bench import trace_reduce
    (dev,) = trace.devices
    busy = trace_reduce.busy_s(dev)
    assert 0 < busy <= sum(o.dur_ns for o in dev.ops) / 1e9
    assert busy == pytest.approx(0.000449803, rel=1e-6)
    share = _reader("device.idle_share").read(_r(trace))
    assert share == pytest.approx(100 * (1 - busy / 0.4))


def test_busy_mfu_is_model_work_over_busy_device_time(trace):
    from bench import flops, trace_reduce
    busy = trace_reduce.busy_s(trace.devices[0])
    peak = flops.peaks("TPU v5 lite")["bf16_flops"]
    got = _reader("device.busy_mfu").read(_r(
        trace, microbatches=3, mb_tokens=4096, flops_per_token=1e6))
    assert got == pytest.approx(100 * 3 * 4096 * 1e6 / (busy * peak))
    assert 0 < got <= 100


def test_kernel_found_by_name(trace):
    from bench import trace_reduce
    (dev,) = trace.devices
    flash = trace_reduce.ops_named(dev, "flash_attention_fwd")
    assert len(flash) == 3
    assert trace_reduce.operand_shapes(flash[0].name)[0] == \
        ("bf16", (2, 512, 128))


def test_flash_roofline_and_its_bound(trace):
    from bench import flops
    ops, nbytes = flops.flash_fwd_cost(1, 2, 512, 128)
    share, bound = flops.roofline_share(3 * ops, 3 * nbytes,
                                        3 * 4910.67e-9,
                                        flops.peaks("TPU v5 lite"))
    assert bound == "bytes"           # this small call is bytes-bound
    got = _reader("kernel.flash_fwd_roofline").read(_r(trace))
    assert got == pytest.approx(share, rel=1e-3)
    assert 0 < got <= 100


def test_wire_qdq_found_inside_its_span(trace):
    from bench import flops
    ops, nbytes = flops.qdq_cost(4096 * 64)
    share, bound = flops.roofline_share(3 * ops, 3 * nbytes,
                                        3 * 12055.67e-9,
                                        flops.peaks("TPU v5 lite"))
    assert bound == "bytes"
    got = _reader("kernel.wire_qdq_roofline").read(_r(trace))
    assert got == pytest.approx(share, rel=1e-3)


def test_programs_found_by_the_span_that_called_them(trace):
    from bench import trace_reduce
    dev = trace.devices[0]

    def names(calls):
        return {trace_reduce.module_name(m.name) for m in
                trace_reduce.program_runs(dev, trace_reduce.span_programs(
                    trace, dev, calls))}
    assert names({"run_bwd"}) == {"jit_probe_matmul"}
    assert "jit_wrapped" in names({"wire_bwd"})
    assert len(trace_reduce.program_runs(
        dev, trace_reduce.span_programs(trace, dev, {"run_bwd"}))) == 3
    bwd = _reader("stage.bwd_ms").read(_r(trace))
    assert bwd == pytest.approx(0.1015, rel=1e-2)


def test_idle_gaps_charged_to_host_spans(trace):
    from bench import trace_reduce
    gaps = dict(trace_reduce.idle_gaps(trace))
    assert gaps["bench.host_wait"] > 0.009      # three 3 ms sleeps
    assert len(trace_reduce.top_ops(trace)) <= 10


def test_nothing_to_read_is_left_out(trace):
    empty = types.SimpleNamespace(devices=[], spans=[], enqueues=[],
                                  span_starts=None)
    r = _r(empty)
    for name in ("kernel.flash_fwd_roofline", "kernel.wire_qdq_roofline",
                 "stage.bwd_ms", "device.idle_share", "device.busy_mfu"):
        assert _reader(name).read(r) is None
