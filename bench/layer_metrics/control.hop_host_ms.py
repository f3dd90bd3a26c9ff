"""Host milliseconds per microbatch of the swarm's control plane: time
inside the program's trainer hop spans (``repro.hop.*``), its barrier
(``repro.swarm.barrier``) and its routing and ledger spans
(``repro.ctl.*``, which name the parts), outside the executor and wire
codec spans they hold (``repro.exec.*``, ``repro.wire.*``)."""
from bench import program_spans

CONTROL = ("repro.hop.", "repro.swarm.barrier", "repro.ctl.")
WORK = ("repro.exec.", "repro.wire.")


def _overlap_ns(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(r):
    if not r.microbatches:
        return None
    spans = program_spans.of(r)
    control = program_spans.intervals(spans, CONTROL)
    if not control:
        return None
    work = program_spans.intervals(spans, WORK)
    inside = sum(e - s for s, e in control) - _overlap_ns(control, work)
    return inside / 1e6 / r.microbatches
