"""Device milliseconds per microbatch of the sLSTM time recurrences,
summed over the cell's stages and layers: forward, the backward's
recompute and the backward, every op whose name stack holds the
program's ``repro.ssm.slstm`` scope (``bench/op_scopes.py``), with the
device's gaps between a loop's ops."""
from bench import op_scopes

SCOPE = "repro.ssm.slstm"


def read(r):
    if not r.microbatches:
        return None
    ms = op_scopes.scope_ms(op_scopes.of(r), SCOPE)
    return None if ms is None else ms / r.microbatches
