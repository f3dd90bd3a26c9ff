"""Device milliseconds per microbatch of the stage backward programs,
summed over the cell's stages: every run of the programs that the
benchmark's ``run_bwd`` spans put on the device."""
from bench import trace_reduce


def read(r):
    if not r.microbatches:
        return None
    total, found = 0.0, False
    for dev in r.trace.devices:
        ids = trace_reduce.span_programs(r.trace, dev, {"run_bwd"})
        for m in trace_reduce.program_runs(dev, ids):
            total += m.dur_ns
            found = True
    if not found:
        return None
    return total / 1e6 / len(r.trace.devices) / r.microbatches
