"""The program's own host spans in a profiler trace, for the readers of
``bench/layer_metrics/executor.*``.

The program opens its spans with ``repro.obs.span``: host events named
``repro.<layer>.<call>`` on the ``/host:CPU`` plane, on the device
trace's clock, with their args as stats.  ``trace_reduce.load`` keeps
only the benchmark's own ``bench.*`` spans, so this module reads the
program's from the same file, and ties device work to them with
``trace_reduce``'s own matching.

``bench/run.py`` hands its readers the reduced trace and not the file.
``of(r)`` takes the spans from ``r.program_spans`` where the caller put
them there (the tests do), and otherwise reads the file of the run that
is calling the reader: the trace directory is that run's local
``trace_dir``, which exists until the readers are done.  A trace
without program spans (a program that opens none) gives an empty list,
and the readers then report nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import os
import sys

from bench import trace_reduce

PREFIX = "repro."


@dataclasses.dataclass
class HostSpan(trace_reduce.Event):
    line: str = ""         # the host line (thread) that opened it
    stats: dict = dataclasses.field(default_factory=dict)


def load(path: str) -> list:
    """The ``repro.*`` host spans of the trace at ``path`` (a file, or a
    directory holding one), by start."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace_reduce.find_xplane(path))
    out = []
    for plane in data.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    out.append(HostSpan(e.name, e.start_ns, e.duration_ns,
                                        line=ln.name, stats=dict(e.stats)))
    return sorted(out, key=lambda s: s.start_ns)


def _run_trace_dir():
    """The ``trace_dir`` of the nearest caller that has one: the run
    whose trace the readers are reading."""
    f = sys._getframe(1)
    while f is not None:
        d = f.f_locals.get("trace_dir")
        if isinstance(d, str) and os.path.exists(d):
            return d
        f = f.f_back
    return None


def of(r) -> list:
    """The program spans of the run a reader is given, read once and
    kept on ``r``."""
    spans = getattr(r, "program_spans", None)
    if spans is None:
        path = _run_trace_dir()
        try:
            spans = load(path) if path else []
        except FileNotFoundError:
            spans = []
        r.program_spans = spans
    return spans


def intervals(spans, prefixes) -> list:
    """Host time inside the spans whose names start with one of
    ``prefixes`` (``"repro.wire."``...): merged [start, end] in ns."""
    return trace_reduce.union((s.start_ns, s.end_ns) for s in spans
                              if s.name.startswith(tuple(prefixes)))


def covers(merged, t_ns: float) -> bool:
    """Whether ``t_ns`` lies in one of the ``merged`` intervals."""
    i = bisect.bisect_right(merged, [t_ns, float("inf")]) - 1
    return i >= 0 and merged[i][0] <= t_ns <= merged[i][1]


def device_gaps(dev: trace_reduce.Device) -> list:
    """(start, end) in ns of the idle gaps between ``dev``'s merged op
    intervals, as ``trace_reduce.idle_gaps`` finds them."""
    busy = trace_reduce.union((o.start_ns, o.end_ns) for o in dev.ops)
    return [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]


def runs_inside(trace: trace_reduce.Trace, dev: trace_reduce.Device,
                spans, prefixes) -> list:
    """The program runs on ``dev`` launched from inside the spans named
    with one of ``prefixes``: ``trace_reduce.span_programs`` on a view
    of ``trace`` whose only spans are those merged intervals."""
    view = dataclasses.replace(
        trace, span_starts=None,
        spans=[trace_reduce.Event("program.inside", s, e - s)
               for s, e in intervals(spans, prefixes)])
    return trace_reduce.program_runs(
        dev, trace_reduce.span_programs(view, dev, ("inside",)))
