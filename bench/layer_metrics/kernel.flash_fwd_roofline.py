"""Share of its roofline the Pallas flash-attention forward reaches:
least time for the calls' work (causal operations over the unmasked
pairs only, q, k, v and o moved once) over their summed device time."""
from bench import flops, trace_reduce

KERNEL = "flash_attention_fwd"


def read(r):
    ops = nbytes = secs = 0.0
    for dev in r.trace.devices:
        for o in trace_reduce.ops_named(dev, KERNEL):
            shapes = trace_reduce.operand_shapes(o.name)
            if not shapes:
                return None
            bh, seq, hd = shapes[0][1]
            f, b = flops.flash_fwd_cost(1, bh, seq, hd,
                                        causal=r.config.get("causal", True))
            ops, nbytes, secs = ops + f, nbytes + b, secs + o.dur_ns / 1e9
    if secs == 0.0:
        return None
    return flops.roofline_share(ops, nbytes, secs, r.peak)[0]
