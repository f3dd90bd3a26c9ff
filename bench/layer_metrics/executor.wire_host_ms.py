"""Host milliseconds per microbatch inside the program's wire codec
spans (``repro.wire.*``): the eager round trip, its lowering and its
dispatches."""
from bench import program_spans

SPANS = ("repro.wire.",)


def read(r):
    if not r.microbatches:
        return None
    host = program_spans.intervals(program_spans.of(r), SPANS)
    if not host:
        return None
    return sum(e - s for s, e in host) / 1e6 / r.microbatches
