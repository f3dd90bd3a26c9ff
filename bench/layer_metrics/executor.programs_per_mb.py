"""XLA program runs per microbatch launched from inside the program's
executor and wire codec spans (``repro.exec.*``, ``repro.wire.*``): the
dispatches a microbatch costs, eager codec programs included."""
from bench import program_spans

SPANS = ("repro.exec.", "repro.wire.")


def read(r):
    if not r.microbatches:
        return None
    spans = program_spans.of(r)
    runs = sum(len(program_spans.runs_inside(r.trace, dev, spans, SPANS))
               for dev in r.trace.devices)
    if not runs:
        return None
    return runs / len(r.trace.devices) / r.microbatches
