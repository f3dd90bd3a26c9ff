"""Model FLOP utilization of the device while it is busy: the model
operations of the traced microbatches over the seconds in which an
operation ran on the device (the union of op intervals in the trace),
times the chips' bf16 peak.  End to end ``mfu`` is about this times the
busy share of the window.  Kernel rooflines stand under it; a kernel
taken off the path goes silent, this does not."""
from bench import trace_reduce


def read(r):
    if not r.microbatches or not r.trace.devices:
        return None
    busy = trace_reduce.mean_busy_s(r.trace)
    if busy <= 0:
        return None
    work = r.microbatches * r.mb_tokens * r.flops_per_token
    return 100.0 * work / (busy * r.chips * r.peak["bf16_flops"])
