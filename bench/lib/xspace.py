"""The profiler's ``.xplane.pb`` read as a protobuf message.

``jax.profiler.ProfileData`` gives each event its own stats, but not the
stats of its metadata, where a trace may keep what all the events of one
op share (its op-name path, say).  This reads the file with the ``XSpace``
schema (the fields the benchmark uses, with their numbers from
``tsl/profiler/protobuf/xplane.proto``), built here at run time with the
installed ``protobuf`` package, so that no generated module is needed.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator, Tuple

INT64, UINT64, STRING, MESSAGE = 3, 4, 9, 11
OPTIONAL, REPEATED = 1, 3

# message -> (field name, number, type, label, message type)
SCHEMA = {
    "XSpace": [("planes", 1, MESSAGE, REPEATED, "XPlane")],
    "XPlane": [("name", 2, STRING, OPTIONAL, None),
               ("lines", 3, MESSAGE, REPEATED, "XLine"),
               ("event_metadata", 4, MESSAGE, REPEATED, "EventMetadataEntry"),
               ("stat_metadata", 5, MESSAGE, REPEATED, "StatMetadataEntry")],
    "XLine": [("name", 2, STRING, OPTIONAL, None),
              ("timestamp_ns", 3, INT64, OPTIONAL, None),
              ("events", 4, MESSAGE, REPEATED, "XEvent")],
    "XEvent": [("metadata_id", 1, INT64, OPTIONAL, None),
               ("offset_ps", 2, INT64, OPTIONAL, None),
               ("duration_ps", 3, INT64, OPTIONAL, None),
               ("stats", 4, MESSAGE, REPEATED, "XStat")],
    "XStat": [("metadata_id", 1, INT64, OPTIONAL, None),
              ("str_value", 5, STRING, OPTIONAL, None),
              ("ref_value", 7, UINT64, OPTIONAL, None)],
    "XEventMetadata": [("id", 1, INT64, OPTIONAL, None),
                       ("name", 2, STRING, OPTIONAL, None),
                       ("stats", 5, MESSAGE, REPEATED, "XStat")],
    "XStatMetadata": [("id", 1, INT64, OPTIONAL, None),
                      ("name", 2, STRING, OPTIONAL, None)],
    # the wire form of map<int64, XEventMetadata> and map<int64, XStatMetadata>
    "EventMetadataEntry": [("key", 1, INT64, OPTIONAL, None),
                           ("value", 2, MESSAGE, OPTIONAL, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, INT64, OPTIONAL, None),
                          ("value", 2, MESSAGE, OPTIONAL, "XStatMetadata")],
}
PACKAGE = "bench_xplane"


@functools.lru_cache(maxsize=None)
def message_class(name: str):
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    pool = descriptor_pool.DescriptorPool()
    fd = descriptor_pb2.FileDescriptorProto(name=f"{PACKAGE}.proto",
                                            package=PACKAGE, syntax="proto3")
    for msg, fields in SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for field, number, type_, label, type_name in fields:
            f = m.field.add(name=field, number=number, type=type_,
                            label=label)
            if type_name:
                f.type_name = f".{PACKAGE}.{type_name}"
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{PACKAGE}.{name}"))


def read(path: str):
    space = message_class("XSpace")()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def events(plane, line, keep=None
           ) -> Iterator[Tuple[str, float, float, Dict[str, str]]]:
    """``(name, start_ns, end_ns, string stats)`` of each event on ``line``
    of ``plane`` whose name ``keep`` accepts (all, without ``keep``); the
    stats are the event metadata's, overridden by the event's own
    (interned strings resolved)."""
    names = {e.key: e.value for e in plane.event_metadata
             if keep is None or keep(e.value.name)}
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}

    def strings(stats):
        return {stat_names.get(s.metadata_id, ""):
                s.str_value or stat_names.get(s.ref_value, "")
                for s in stats if s.str_value or s.ref_value}

    shared = {k: strings(m.stats) for k, m in names.items()}
    for ev in line.events:
        meta = names.get(ev.metadata_id)
        if meta is None and keep is not None:
            continue
        start = line.timestamp_ns + ev.offset_ps / 1000.0
        yield (meta.name if meta else "", start,
               start + ev.duration_ps / 1000.0,
               {**shared.get(ev.metadata_id, {}), **strings(ev.stats)})
