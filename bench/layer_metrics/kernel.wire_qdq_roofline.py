"""Share of its roofline the int8 wire round-trip kernel reaches: the
Pallas calls inside the programs that the benchmark's
``wire_fwd``/``wire_bwd`` spans put on the device, bytes-bound (f32 read
and written once)."""
from bench import flops, trace_reduce

KERNEL = "tpu_custom_call"


def read(r):
    nbytes = ops = secs = 0.0
    for dev in r.trace.devices:
        ids = trace_reduce.span_programs(r.trace, dev,
                                         {"wire_fwd", "wire_bwd"})
        wins = [(m.start_ns, m.end_ns)
                for m in trace_reduce.program_runs(dev, ids)]
        for o in trace_reduce.ops_named(dev, KERNEL):
            if not any(s <= o.start_ns <= e for s, e in wins):
                continue
            shapes = trace_reduce.result_shapes(o.name)
            if not shapes:
                return None
            n = 1
            for dim in shapes[0][1]:
                n *= dim
            f, b = flops.qdq_cost(n)
            ops, nbytes, secs = ops + f, nbytes + b, secs + o.dur_ns / 1e9
    if secs == 0.0:
        return None
    return flops.roofline_share(ops, nbytes, secs, r.peak)[0]
