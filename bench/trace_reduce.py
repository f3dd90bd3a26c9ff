"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

Device planes are named ``/device:TPU:<n>``.  On each, the ``XLA Modules``
line holds one event per program run (``jit_<name>(<id>)``) and the
``XLA Ops`` line one event per HLO operation, named by the instruction's
text (``%<op>.<n> = <shape> <opcode>(...)``).  Host spans that the
benchmark opens with ``jax.profiler.TraceAnnotation`` land on the host
plane, on the same clock.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
ENQUEUE = "DoEnqueueProgram"
CALL = "PjitFunction("
LINK = "PJRT_LoadedExecutable_Execute linkage"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    run_id: int = -1

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Device:
    modules: list          # program runs, in launch (run_id) order
    ops: list


@dataclasses.dataclass
class Trace:
    devices: list          # one Device per chip
    spans: list            # the benchmark's host spans (name "bench.*")
    enqueues: list         # programs put on the device from the thread
                           # that called them: Event(run_id=...)
    calls: list = dataclasses.field(default_factory=list)
                           # host launches: Event(name=jitted function)
    span_starts: list = None


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str) -> Trace:
    """Read the trace at ``path`` (a file, or a directory holding one)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    devices, spans, enqueues, calls = [], [], [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            modules, ops = [], []
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    modules = [Event(e.name, e.start_ns, e.duration_ns,
                                     int(dict(e.stats).get("run_id", -1)))
                               for e in ln.events]
                elif ln.name == "XLA Ops":
                    ops = [Event(e.name, e.start_ns, e.duration_ns)
                           for e in ln.events]
            devices.append(Device(modules, ops))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                caller = ln.name.startswith("main")
                fn = None
                for e in sorted(ln.events, key=lambda e: e.start_ns):
                    if e.name.startswith(CALL):
                        fn = e.name[len(CALL):-1]
                    elif e.name == LINK and fn is not None:
                        calls.append(Event(fn, e.start_ns, e.duration_ns))
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns,
                                           e.duration_ns))
                    elif caller and e.name == ENQUEUE:
                        enqueues.append(Event(
                            e.name, e.start_ns, e.duration_ns,
                            int(dict(e.stats).get("run_id", -1))))
    return Trace(devices, sorted(spans, key=lambda e: e.start_ns),
                 sorted(enqueues, key=lambda e: e.start_ns),
                 sorted(calls, key=lambda e: e.start_ns))


def innermost_span(trace: Trace, t_ns: float, depth: int = 8):
    """Name of the shortest benchmark span open at ``t_ns`` among the
    ``depth`` that started last before it, or None."""
    if trace.span_starts is None:
        trace.span_starts = [s.start_ns for s in trace.spans]
    i = bisect.bisect_right(trace.span_starts, t_ns)
    open_ = [s for s in trace.spans[max(0, i - depth):i] if t_ns <= s.end_ns]
    return min(open_, key=lambda s: s.dur_ns).name if open_ else None


def _in(trace: Trace, t_ns: float, calls) -> bool:
    span = innermost_span(trace, t_ns)
    return bool(span) and span.rsplit(".", 1)[-1] in calls


def span_programs(trace: Trace, dev: Device, calls) -> set:
    """Ids of the programs put on ``dev`` from inside a benchmark span
    ``bench.[...].<call>`` with ``call`` in ``calls``.  Two ways find a
    run's caller: a program enqueued by the calling thread itself is
    tied to its run by ``run_id``; and the n-th host launch of a jitted
    function ``f`` is the n-th run of ``jit_f`` (runs are numbered in
    launch order), where the two counts agree."""
    runs = {m.run_id: m for m in dev.modules}
    ids = set()
    for e in trace.enqueues:
        if e.run_id in runs and _in(trace, e.start_ns, calls):
            ids.add(module_id(runs[e.run_id].name))
    by_fn = collections.defaultdict(list)
    for m in sorted(dev.modules, key=lambda m: m.run_id):
        by_fn[module_name(m.name)].append(m)
    launched = collections.defaultdict(list)
    for c in trace.calls:
        launched["jit_" + re.sub(r"[<>]", "", c.name.replace("<", "_"))
                 ].append(c)
    for fn, cs in launched.items():
        ms = by_fn.get(fn, [])
        if len(ms) != len(cs):
            continue
        for c, m in zip(cs, ms):
            if _in(trace, c.start_ns, calls):
                ids.add(module_id(m.name))
    return ids


def program_runs(dev: Device, ids: set) -> list:
    return [m for m in dev.modules if module_id(m.name) in ids]


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(dev: Device) -> float:
    """Seconds in which some operation ran on the device."""
    return sum(e - s for s, e in union((o.start_ns, o.end_ns)
                                       for o in dev.ops)) / 1e9


def mean_busy_s(trace: Trace) -> float:
    return sum(busy_s(d) for d in trace.devices) / max(len(trace.devices), 1)


def op_kind(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def ops_named(dev: Device, kind: str) -> list:
    return [o for o in dev.ops if op_kind(o.name) == kind]


def module_name(name: str) -> str:
    """``jit_counted(825261668654034349)`` -> ``jit_counted``."""
    return name.split("(", 1)[0]


def module_id(name: str) -> str:
    m = re.search(r"\((\d+)\)$", name)
    return m.group(1) if m else ""


_SHAPE = re.compile(r"(bf16|f32|f16|s8|s32)\[([\d,]*)\]")


def operand_shapes(name: str) -> list:
    """Shapes of an op's operands, from the text after its opcode."""
    body = name.split("custom-call(", 1)[-1] if "custom-call(" in name \
        else name.split("(", 1)[-1]
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(body.split(")")[0])]


def result_shapes(name: str) -> list:
    head = name.split(" = ", 1)[-1]
    head = head.split(" custom-call(", 1)[0].split(" fusion(", 1)[0]
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(head)]


def top_ops(trace: Trace, n: int = 10) -> list:
    """[kind, seconds] of the operations that took most device time,
    averaged over chips; loop and conditional ops, which contain
    others, are left out."""
    tot = collections.Counter()
    for d in trace.devices:
        for o in d.ops:
            k = op_kind(o.name)
            if k.startswith(("while", "conditional", "call")):
                continue
            tot[k] += o.dur_ns / 1e9
    nd = max(len(trace.devices), 1)
    return [[k, v / nd] for k, v in tot.most_common(n)]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[host activity, seconds]: the device's idle gaps, each charged to
    the innermost benchmark span open on the host at its midpoint."""
    tot = collections.Counter()
    for d in trace.devices:
        busy = union((o.start_ns, o.end_ns) for o in d.ops)
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            mid = (e0 + s1) / 2
            name = (innermost_span(trace, mid)
                    or "host outside benchmark spans")
            tot[name] += (s1 - e0) / 1e9
    nd = max(len(trace.devices), 1)
    return [[k, v / nd] for k, v in tot.most_common(n)]
