"""The profiler's ``.xplane.pb`` as plain tuples.

``jax.profiler.ProfileData`` gives an event its name, start and duration,
and not the statistics of its *metadata*; on a TPU that is where the
profiler keeps what it knows of an operation: ``tf_op`` (the ``op_name``
of the HLO instruction, which is the ``jax.named_scope`` path it was
traced under), ``hlo_category``, ``flops``, ``bytes_accessed``. So the file
is parsed here with ``google.protobuf`` against the few fields of
``tsl/profiler/protobuf/xplane.proto`` that the reduction reads; every
other field is skipped by the parser.
"""

from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_INT64, _STRING, _MESSAGE = _F.TYPE_INT64, _F.TYPE_STRING, _F.TYPE_MESSAGE
_PACKAGE = "rbg_bench_xplane"

# message -> [(field, number, type, message type or None, repeated)]
_SCHEMA = {
    "XStat": [("metadata_id", 1, _INT64, None, False),
              ("str_value", 5, _STRING, None, False),
              ("ref_value", 7, _F.TYPE_UINT64, None, False)],
    "XEvent": [("metadata_id", 1, _INT64, None, False),
               ("offset_ps", 2, _INT64, None, False),
               ("duration_ps", 3, _INT64, None, False)],
    "XLine": [("name", 2, _STRING, None, False),
              ("timestamp_ns", 3, _INT64, None, False),
              ("events", 4, _MESSAGE, "XEvent", True)],
    "XEventMetadata": [("id", 1, _INT64, None, False),
                       ("name", 2, _STRING, None, False),
                       ("stats", 5, _MESSAGE, "XStat", True)],
    "XStatMetadata": [("id", 1, _INT64, None, False),
                      ("name", 2, _STRING, None, False)],
    # The two maps of an XPlane are read as what they are on the wire:
    # repeated entries of (key = 1, value = 2).
    "EventMetadataEntry": [("key", 1, _INT64, None, False),
                           ("value", 2, _MESSAGE, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, _INT64, None, False),
                          ("value", 2, _MESSAGE, "XStatMetadata", False)],
    "XPlane": [("name", 2, _STRING, None, False),
               ("lines", 3, _MESSAGE, "XLine", True),
               ("event_metadata", 4, _MESSAGE, "EventMetadataEntry", True),
               ("stat_metadata", 5, _MESSAGE, "StatMetadataEntry", True)],
    "XSpace": [("planes", 1, _MESSAGE, "XPlane", True)],
}


def _space_class():
    fd = descriptor_pb2.FileDescriptorProto(
        name=f"{_PACKAGE}.proto", package=_PACKAGE, syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, number, ftype, mtype, repeated in fields:
            f = m.field.add(name=name, number=number, type=ftype,
                            label=_F.LABEL_REPEATED if repeated
                            else _F.LABEL_OPTIONAL)
            if mtype:
                f.type_name = f".{_PACKAGE}.{mtype}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


SCOPE_STAT = "tf_op"


def read_planes(path: str) -> list:
    """``[{"name", "lines": [{"name", "events": [(start_ns, end_ns, name,
    scope)]}]}]`` of one profile. ``scope`` is the event's ``tf_op``
    statistic, as the profiler wrote it, or ``""`` where it wrote none
    (host events; on the device a ``while``, most copies)."""
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = []
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        scope_ids = {k for k, n in stat_names.items() if n == SCOPE_STAT}
        meta = {}
        for e in plane.event_metadata:
            scope = ""
            for st in e.value.stats:
                if st.metadata_id in scope_ids:
                    scope = st.str_value or stat_names.get(st.ref_value, "")
            meta[e.key] = (e.value.name, scope)
        lines = []
        for ln in plane.lines:
            t0 = ln.timestamp_ns
            events = []
            for ev in ln.events:
                # whole nanoseconds, as ``jax.profiler.ProfileData`` gives
                # them: the start cut off, the duration added to that
                start = int(t0 + ev.offset_ps / 1000.0)
                events.append((start, int(start + ev.duration_ps / 1000.0),
                               *meta.get(ev.metadata_id,
                                         (str(ev.metadata_id), ""))))
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes
