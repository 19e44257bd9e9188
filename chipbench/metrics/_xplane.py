"""The traced window's profile as the program-scope readers need it.

``trace_reduce.load`` keeps only the harness's own ``chipbench.*`` host
annotations and the bare names of device ops.  The readers of the
program's own spans and scopes reload the same ``.xplane.pb`` (once per
run) into::

    {"devices": {"/device:TPU:0": {"ops": [[name, t0_ns, t1_ns, op_name],
                                           ...],
                                   "modules": [[name, t0_ns, t1_ns], ...]}},
     "host": [[name, t0_ns, t1_ns], ...]}

``host`` holds the host plane's events of every name: the harness's
annotations and the scheduler's spans (``sched.tick`` and its children,
which the program enters as profiler annotations).  ``op_name`` is the
HLO metadata path of the op (``jit(decode_slots)/while/body/attention/
dot_general``), empty where the op carries none.  A trace of a program
that names no spans or scopes reads as such; the readers then return
None.

On a TPU the op_name is a stat (``tf_op``) of each op's event metadata,
which ``jax.profiler.ProfileData`` does not expose, so the file is read
with a schema of its own: the fields of ``XSpace`` that this module
reads, numbered as in ``xplane.proto`` of the profiler.  Times are
reckoned as ``ProfileData`` reckons them (whole nanoseconds), so they
share ``trace_reduce``'s clock.
"""
from __future__ import annotations

import bisect
import functools

from chipbench import trace_reduce as tr

OP_NAME_STAT = b"tf_op"
TICK = "sched.tick"

_loaded: dict = {}


@functools.cache
def _xspace():
    """The message class of a profile file, for the fields read here
    (strings as bytes, so that no name fails UTF-8 validation)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    i64, u64, f64 = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    raw, msg = F.TYPE_BYTES, F.TYPE_MESSAGE
    schema = {
        "XSpace": [("planes", 1, msg, "XPlane")],
        "XPlane": [("name", 2, raw, None), ("lines", 3, msg, "XLine"),
                   ("event_metadata", 4, msg, "EventMetadataEntry"),
                   ("stat_metadata", 5, msg, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, i64, None),
                               ("value", 2, msg, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, i64, None),
                              ("value", 2, msg, "XStatMetadata")],
        "XLine": [("name", 2, raw, None), ("timestamp_ns", 3, i64, None),
                  ("events", 4, msg, "XEvent")],
        "XEvent": [("metadata_id", 1, i64, None),
                   ("offset_ps", 2, i64, None),
                   ("duration_ps", 3, i64, None)],
        "XStat": [("metadata_id", 1, i64, None),
                  ("double_value", 2, f64, None),
                  ("uint64_value", 3, u64, None),
                  ("int64_value", 4, i64, None),
                  ("str_value", 5, raw, None),
                  ("bytes_value", 6, raw, None),
                  ("ref_value", 7, u64, None)],
        "XEventMetadata": [("name", 2, raw, None),
                           ("stats", 5, msg, "XStat")],
        "XStatMetadata": [("name", 2, raw, None)],
    }
    repeated = {"planes", "lines", "event_metadata", "stat_metadata",
                "events", "stats"}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane",
        syntax="proto3")
    for name, fields in schema.items():
        m = fdp.message_type.add(name=name)
        for fname, number, kind, ref in fields:
            f = m.field.add(name=fname, number=number, type=kind,
                            label=F.LABEL_REPEATED if fname in repeated
                            else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = f".chipbench_xplane.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def _events(line, names):
    """[name, t0_ns, t1_ns] of a line's events, in whole nanoseconds."""
    ts = line.timestamp_ns
    out = []
    for e in line.events:
        t0 = ts + e.offset_ps // 1000
        out.append([names.get(e.metadata_id, ""), t0,
                    t0 + e.duration_ps // 1000])
    return out


def load(path) -> dict:
    with open(path, "rb") as f:
        space = _xspace().FromString(f.read())
    out = {"devices": {}, "host": []}
    for plane in space.planes:
        pname = plane.name.decode(errors="replace")
        device = pname.startswith(tr.DEVICE_PREFIX)
        if not device and pname != tr.HOST_PLANE:
            continue
        stat_names = {s.key: s.value.name for s in plane.stat_metadata}
        names, op_names = {}, {}
        for entry in plane.event_metadata:
            meta = entry.value
            names[entry.key] = meta.name.decode(errors="replace")
            op_names[entry.key] = _op_name(meta, stat_names)
        if not device:
            for line in plane.lines:
                out["host"].extend(_events(line, names))
            continue
        lines = {"ops": [], "modules": []}
        for line in plane.lines:
            lname = line.name.decode(errors="replace")
            if lname == tr.OPS_LINE:
                lines["ops"].extend(
                    ev + [op_names.get(e.metadata_id, "")]
                    for ev, e in zip(_events(line, names), line.events))
            elif lname == tr.MODULES_LINE:
                lines["modules"].extend(_events(line, names))
        out["devices"][pname] = lines
    return out


def _op_name(meta, stat_names) -> str:
    """The HLO op_name of an op's event metadata (its ``tf_op`` stat,
    without the trailing ``:``), or "" where it has none."""
    for s in meta.stats:
        if stat_names.get(s.metadata_id) == OP_NAME_STAT:
            v = (s.str_value or s.bytes_value
                 or stat_names.get(s.ref_value, b""))
            return v.decode(errors="replace").rstrip(":")
    return ""


def of_run(run) -> dict | None:
    """The run's reloaded trace, or None where the run was not traced."""
    if run.trace is None:
        return None
    key = id(run)
    if key not in _loaded:
        from chipbench.harness import CACHE
        _loaded.clear()
        try:
            path = tr.find_xplane(CACHE / "trace" / run.cell.name)
        except FileNotFoundError:
            _loaded[key] = None
        else:
            _loaded[key] = load(path)
    return _loaded[key]


def ticks(trace: dict) -> list[tuple[float, float]]:
    """The ``sched.tick`` annotations wholly inside the traced window,
    in order."""
    lo, hi = tr.window_of(trace)
    return sorted((t0, t1) for name, t0, t1 in trace["host"]
                  if name == TICK and t0 >= lo and t1 <= hi)


def device_planes(trace: dict) -> list[str]:
    """The device planes that ran anything, in order."""
    return [p for p in sorted(trace["devices"])
            if tr.device_ops(trace, p)]


def busy_within(busy, t0: float, t1: float) -> float:
    """Nanoseconds of the sorted disjoint ``busy`` intervals inside
    [t0, t1]."""
    i = max(bisect.bisect_right(busy, (t0,)) - 1, 0)
    total = 0.0
    while i < len(busy) and busy[i][0] < t1:
        a, b = busy[i]
        total += max(0.0, min(b, t1) - max(a, t0))
        i += 1
    return total


def has_scope(op_name: str, scope: str) -> bool:
    return scope in op_name.split("/")
