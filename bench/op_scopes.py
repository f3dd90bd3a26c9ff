"""Device ops of a profiler trace with the name scope each one carries,
for the readers of ``bench/layer_metrics/ssm.*``.

On a TPU trace's ``/device:TPU:<n>`` plane, the ``XLA Ops`` line holds
one event per op execution (every trip of a loop body included).  The
op's ``op_name`` metadata, the JAX name stack with each
``jax.named_scope`` it was traced in (``.../repro.ssm.slstm/while/...``),
is the ``tf_op`` stat of the event's metadata, which
``jax.profiler.ProfileData`` does not expose.  So this module reads the
``.xplane.pb`` itself, with message classes for the few ``XSpace`` fields
it needs, built here from the field numbers of the profiler's public
``xplane.proto`` (nothing beyond ``protobuf`` is imported).  Ops without
a ``tf_op`` (copies and other ops the compiler adds) keep an empty scope.

``of(r)`` reads the file of the run that is calling the reader, found as
``program_spans.of`` finds it, and keeps the ops on ``r``; the tests put
them on ``r.device_ops`` themselves.
"""
from __future__ import annotations

import dataclasses
import functools

from bench import program_spans, trace_reduce

OPS_LINE = "XLA Ops"
SCOPE_STAT = "tf_op"


@dataclasses.dataclass
class Op:
    start_ps: int
    end_ps: int
    scope: str              # the op's ``tf_op`` (its name stack), or ""


@functools.lru_cache(maxsize=None)
def _xspace():
    """The ``XSpace`` message class, decoding only what ``load`` reads."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    fd = descriptor_pb2.FieldDescriptorProto
    pkg = "bench_op_scopes"
    f = descriptor_pb2.FileDescriptorProto(
        name=pkg + "/xplane.proto", package=pkg, syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, kind in fields:
            repeated = kind.startswith("*")
            kind = kind.lstrip("*")
            field = m.field.add(
                name=fname, number=number,
                label=fd.LABEL_REPEATED if repeated else fd.LABEL_OPTIONAL)
            if kind in ("int64", "uint64", "string"):
                field.type = getattr(fd, "TYPE_" + kind.upper())
            else:
                field.type, field.type_name = fd.TYPE_MESSAGE, \
                    f".{pkg}.{kind}"

    message("XStat", ("metadata_id", 1, "int64"), ("str_value", 5, "string"),
            ("ref_value", 7, "uint64"))
    message("XEventMetadata", ("id", 1, "int64"), ("stats", 5, "*XStat"))
    message("XStatMetadata", ("id", 1, "int64"), ("name", 2, "string"))
    # map<int64, ...> fields are repeated (key = 1, value = 2) entries
    message("EventMetadataEntry", ("key", 1, "int64"),
            ("value", 2, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, "int64"),
            ("value", 2, "XStatMetadata"))
    message("XEvent", ("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
            ("duration_ps", 3, "int64"))
    message("XLine", ("name", 2, "string"), ("timestamp_ns", 3, "int64"),
            ("events", 4, "*XEvent"))
    message("XPlane", ("name", 2, "string"), ("lines", 3, "*XLine"),
            ("event_metadata", 4, "*EventMetadataEntry"),
            ("stat_metadata", 5, "*StatMetadataEntry"))
    message("XSpace", ("planes", 1, "*XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(pkg + ".XSpace"))


def load(path: str) -> list:
    """Per device plane of the trace at ``path`` (a file, or a directory
    holding one), its ops by start."""
    with open(trace_reduce.find_xplane(path), "rb") as fh:
        space = _xspace().FromString(fh.read())
    devices = []
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        scope_ids = {k for k, v in stat_names.items() if v == SCOPE_STAT}
        scopes = {}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if st.metadata_id in scope_ids:
                    scopes[e.key] = (st.str_value
                                     or stat_names.get(st.ref_value, ""))
        ops = []
        for ln in plane.lines:
            if ln.name != OPS_LINE:
                continue
            base = ln.timestamp_ns * 1000
            for ev in ln.events:
                start = base + ev.offset_ps
                ops.append(Op(start, start + ev.duration_ps,
                              scopes.get(ev.metadata_id, "")))
        devices.append(sorted(ops, key=lambda o: o.start_ps))
    return devices


def of(r) -> list:
    """The device ops of the run a reader is given, read once and kept
    on ``r``; no trace file gives no devices."""
    devices = getattr(r, "device_ops", None)
    if devices is None:
        path = program_spans._run_trace_dir()
        try:
            devices = load(path) if path else []
        except FileNotFoundError:
            devices = []
        r.device_ops = devices
    return devices


def scope_runs(ops: list, scope: str) -> list:
    """[start, end] in ps of each maximal run of ``ops`` (one device's,
    by start) whose name stack holds ``scope``, from the run's first op's
    start to its last op's end, so the device's own gaps between the ops
    of a loop count.  An op of another scope ends a run; an op without a
    scope neither ends nor extends one."""
    runs, run = [], None
    for o in ops:
        if scope in o.scope:
            if run is None:
                run = [o.start_ps, o.end_ps]
                runs.append(run)
            run[1] = max(run[1], o.end_ps)
        elif o.scope:
            run = None
    return runs


def scope_ms(devices: list, scope: str):
    """Mean over devices of the milliseconds inside ``scope``, or None
    where no op of the trace carries it."""
    if not any(scope in o.scope for ops in devices for o in ops):
        return None
    return sum(e - s for ops in devices for s, e in scope_runs(ops, scope)
               ) / 1e9 / len(devices)
