"""The wire codec's share of the stage's device time: device seconds of
the programs launched inside the program's ``repro.wire.*`` spans over
those of the programs launched inside its ``repro.exec.run_fwd`` and
``repro.exec.run_bwd`` spans, in percent (the paper's
compute-to-communication ratio, on the chip)."""
from bench import program_spans

WIRE = ("repro.wire.",)
STAGE = ("repro.exec.run_fwd", "repro.exec.run_bwd")


def read(r):
    spans = program_spans.of(r)
    wire = stage = 0.0
    for dev in r.trace.devices:
        wire += sum(m.dur_ns for m in
                    program_spans.runs_inside(r.trace, dev, spans, WIRE))
        stage += sum(m.dur_ns for m in
                     program_spans.runs_inside(r.trace, dev, spans, STAGE))
    if stage <= 0.0:
        return None
    return 100.0 * wire / stage
