"""Read a profiler ``.xplane.pb`` with nothing but ``google.protobuf``.

``jax.profiler.ProfileData`` hands out an event's own stats but not its
metadata's, and on a TPU the named scope an op was traced under (``tf_op``)
and its category live in the metadata. So the XSpace schema (tsl
``profiler/protobuf/xplane.proto``, the fields the reduction needs) is
declared here and the file is parsed with the protobuf runtime."""
from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, _T.TYPE_INT64, False), ("name", 2, _T.TYPE_STRING, False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True),
               ("stats", 6, "XStat", True)],
    "EventMetadataEntry": [("key", 1, _T.TYPE_INT64, False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, _T.TYPE_INT64, False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("id", 1, _T.TYPE_INT64, False), ("name", 2, _T.TYPE_STRING, False),
              ("timestamp_ns", 3, _T.TYPE_INT64, False),
              ("events", 4, "XEvent", True),
              ("duration_ps", 9, _T.TYPE_INT64, False),
              ("display_name", 11, _T.TYPE_STRING, False)],
    "XEvent": [("metadata_id", 1, _T.TYPE_INT64, False),
               ("offset_ps", 2, _T.TYPE_INT64, False),
               ("duration_ps", 3, _T.TYPE_INT64, False),
               ("stats", 4, "XStat", True),
               ("num_occurrences", 5, _T.TYPE_INT64, False)],
    "XStat": [("metadata_id", 1, _T.TYPE_INT64, False),
              ("double_value", 2, _T.TYPE_DOUBLE, False),
              ("uint64_value", 3, _T.TYPE_UINT64, False),
              ("int64_value", 4, _T.TYPE_INT64, False),
              ("str_value", 5, _T.TYPE_STRING, False),
              ("bytes_value", 6, _T.TYPE_BYTES, False),
              ("ref_value", 7, _T.TYPE_UINT64, False)],
    "XEventMetadata": [("id", 1, _T.TYPE_INT64, False),
                       ("name", 2, _T.TYPE_STRING, False),
                       ("metadata", 3, _T.TYPE_BYTES, False),
                       ("display_name", 4, _T.TYPE_STRING, False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, _T.TYPE_INT64, False),
                      ("name", 2, _T.TYPE_STRING, False)],
}
_CLASSES: dict = {}


def _space_class():
    if not _CLASSES:
        fd = descriptor_pb2.FileDescriptorProto(
            name="benchmark_xplane.proto", package="benchmark_xplane",
            syntax="proto3")
        for msg, fields in _SCHEMA.items():
            m = fd.message_type.add(name=msg)
            for name, number, kind, repeated in fields:
                f = m.field.add(name=name, number=number,
                                label=_T.LABEL_REPEATED if repeated
                                else _T.LABEL_OPTIONAL)
                if isinstance(kind, str):
                    f.type = _T.TYPE_MESSAGE
                    f.type_name = ".benchmark_xplane." + kind
                else:
                    f.type = kind
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fd)
        _CLASSES["XSpace"] = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("benchmark_xplane.XSpace"))
    return _CLASSES["XSpace"]


def _stat_value(stat, stat_names):
    for field in ("str_value", "int64_value", "uint64_value", "double_value"):
        v = getattr(stat, field)
        if v:
            return v
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    return 0


def read(path: str, keep_stats=("tf_op", "hlo_category", "program_id"),
         host_prefix: str = "lg_") -> list:
    """The events of the ``/device:`` planes, and of the other planes those
    whose name starts with ``host_prefix``, as dicts: plane, line, name,
    display, start_ns, dur_ns and the kept stats (the event's own and its
    metadata's)."""
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    events = []
    for plane in space.planes:
        device = plane.name.startswith("/device:")
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        keep = {k for k, v in stat_names.items() if v in keep_stats}
        meta = {}
        for e in plane.event_metadata:
            md = e.value
            if not device and not md.name.startswith(host_prefix):
                continue
            meta[e.key] = (md.display_name or md.name, md.name, {
                stat_names[s.metadata_id]: _stat_value(s, stat_names)
                for s in md.stats if s.metadata_id in keep})
        if not meta:
            continue
        for line in plane.lines:
            base = line.timestamp_ns
            for ev in line.events:
                found = meta.get(ev.metadata_id)
                if found is None:
                    continue
                disp, name, stats = found
                if ev.stats and keep:
                    own = {stat_names[s.metadata_id]: _stat_value(s, stat_names)
                           for s in ev.stats if s.metadata_id in keep}
                    if own:
                        stats = dict(stats, **own)
                events.append({"plane": plane.name, "line": line.name,
                               "name": name, "display": disp,
                               "start_ns": base + ev.offset_ps / 1e3,
                               "dur_ns": ev.duration_ps / 1e3,
                               "stats": stats})
    return events

