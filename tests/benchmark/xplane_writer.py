"""A minimal writer of the profiler's XSpace format (tsl xplane.proto), so
that a recorded trace can be cut down to the events the reducer reads and a
test can lay out a trace of its own.  Field numbers are xplane.proto's."""

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto


def _messages():
    fd = descriptor_pb2.FileDescriptorProto()
    fd.name, fd.package, fd.syntax = "bench_xplane.proto", "bench.xplane", "proto3"

    def msg(name, fields):
        m = fd.message_type.add()
        m.name = name
        for fname, num, ftype, rep, tname in fields:
            f = m.field.add()
            f.name, f.number, f.type = fname, num, ftype
            f.label = _T.LABEL_REPEATED if rep else _T.LABEL_OPTIONAL
            if tname:
                f.type_name = ".bench.xplane." + tname
        return m

    msg("XEvent", [("metadata_id", 1, _T.TYPE_INT64, 0, None),
                   ("offset_ps", 2, _T.TYPE_INT64, 0, None),
                   ("duration_ps", 3, _T.TYPE_INT64, 0, None)])
    msg("XLine", [("id", 1, _T.TYPE_INT64, 0, None),
                  ("name", 2, _T.TYPE_STRING, 0, None),
                  ("timestamp_ns", 3, _T.TYPE_INT64, 0, None),
                  ("events", 4, _T.TYPE_MESSAGE, 1, "XEvent")])
    msg("XEventMetadata", [("id", 1, _T.TYPE_INT64, 0, None),
                           ("name", 2, _T.TYPE_STRING, 0, None)])
    plane = msg("XPlane", [("id", 1, _T.TYPE_INT64, 0, None),
                           ("name", 2, _T.TYPE_STRING, 0, None),
                           ("lines", 3, _T.TYPE_MESSAGE, 1, "XLine")])
    entry = plane.nested_type.add()
    entry.name = "EventMetadataEntry"
    entry.options.map_entry = True
    for fname, num, ftype, tname in (("key", 1, _T.TYPE_INT64, None),
                                     ("value", 2, _T.TYPE_MESSAGE,
                                      "XEventMetadata")):
        f = entry.field.add()
        f.name, f.number, f.type, f.label = fname, num, ftype, _T.LABEL_OPTIONAL
        if tname:
            f.type_name = ".bench.xplane." + tname
    f = plane.field.add()
    f.name, f.number, f.type, f.label = ("event_metadata", 4, _T.TYPE_MESSAGE,
                                         _T.LABEL_REPEATED)
    f.type_name = ".bench.xplane.XPlane.EventMetadataEntry"
    msg("XSpace", [("planes", 1, _T.TYPE_MESSAGE, 1, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench.xplane.XSpace"))


XSpace = _messages()


def write(path, planes):
    """planes: [(plane name, [(line name, [(event name, start_ns, dur_ns)])])],
    the shape benchmark.reduce_trace.read_planes returns."""
    space = XSpace()
    for pid, (pname, lines) in enumerate(planes):
        plane = space.planes.add(id=pid, name=pname)
        ids = {}
        for lid, (lname, events) in enumerate(lines):
            if not events:
                continue
            base = int(min(e[1] for e in events))
            line = plane.lines.add(id=lid, name=lname, timestamp_ns=base)
            for name, start, dur in events:
                if name not in ids:
                    ids[name] = len(ids) + 1
                    plane.event_metadata[ids[name]].id = ids[name]
                    plane.event_metadata[ids[name]].name = name
                line.events.add(metadata_id=ids[name],
                                offset_ps=int(round((start - base) * 1000)),
                                duration_ps=int(round(dur * 1000)))
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
