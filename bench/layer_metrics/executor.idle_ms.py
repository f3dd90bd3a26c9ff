"""Device idle milliseconds per microbatch that the program's own host
work causes: the device's idle gaps (between merged op intervals, as
the breakdown's ``idle_gaps`` finds them) whose midpoint lies inside one
of the program's ``repro.exec.*`` or ``repro.wire.*`` spans."""
from bench import program_spans

SPANS = ("repro.exec.", "repro.wire.")


def read(r):
    if not r.microbatches or not r.trace.devices:
        return None
    host = program_spans.intervals(program_spans.of(r), SPANS)
    if not host:
        return None
    idle = sum(e - s for dev in r.trace.devices
               for s, e in program_spans.device_gaps(dev)
               if program_spans.covers(host, (s + e) / 2))
    return idle / 1e6 / len(r.trace.devices) / r.microbatches
