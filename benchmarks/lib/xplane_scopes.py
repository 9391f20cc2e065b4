"""Device time by ``jax.named_scope``, read from the raw ``.xplane.pb``.

A scope (``moe.router``, ``attn.window``, ...) is not in an XLA op's name:
the TPU plane keeps it in the op's ``tf_op`` stat, in the plane's
``event_metadata`` table, which ``jax.profiler.ProfileData`` (what
``lib/xplane.py`` reads) does not show.  This reads the file as the protobuf
it is, with message types built here from the seven messages of
``tsl/profiler/protobuf/xplane.proto`` (field numbers checked against
TensorFlow's generated module by ``tests/benchmarks``), so that nothing but
``google.protobuf`` is imported.

``by_pattern(path, patterns)`` gives, for the first chip that ran ops,
``{pattern: {"seconds", "events"}}`` over the ops whose ``tf_op`` stat or
name matches, plus ``"busy_s"``, the union of all op intervals (containers
left out, as in ``lib/xplane.py``).  A trace without a device plane (the CPU)
gives None.
"""

import re

from lib import xplane

_MESSAGES = {
    # message: [(field, number, type, label[, type name])]; types and labels
    # are descriptor.proto's: 1 double, 3 int64, 4 uint64, 9 string, 11
    # message, 12 bytes; label 1 optional, 3 repeated
    "XStat": [("metadata_id", 1, 3, 1), ("double_value", 2, 1, 1),
              ("uint64_value", 3, 4, 1), ("int64_value", 4, 3, 1),
              ("str_value", 5, 9, 1), ("bytes_value", 6, 12, 1),
              ("ref_value", 7, 4, 1)],
    "XEvent": [("metadata_id", 1, 3, 1), ("offset_ps", 2, 3, 1),
               ("num_occurrences", 5, 3, 1), ("duration_ps", 3, 3, 1),
               ("stats", 4, 11, 3, "XStat")],
    "XLine": [("id", 1, 3, 1), ("display_id", 10, 3, 1), ("name", 2, 9, 1),
              ("display_name", 11, 9, 1), ("timestamp_ns", 3, 3, 1),
              ("duration_ps", 9, 3, 1), ("events", 4, 11, 3, "XEvent")],
    "XEventMetadata": [("id", 1, 3, 1), ("name", 2, 9, 1),
                       ("display_name", 4, 9, 1), ("metadata", 3, 12, 1),
                       ("stats", 5, 11, 3, "XStat"), ("child_id", 6, 3, 3)],
    "XStatMetadata": [("id", 1, 3, 1), ("name", 2, 9, 1),
                      ("description", 3, 9, 1)],
    "EventMetadataEntry": [("key", 1, 3, 1),
                           ("value", 2, 11, 1, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, 3, 1),
                          ("value", 2, 11, 1, "XStatMetadata")],
    "XPlane": [("id", 1, 3, 1), ("name", 2, 9, 1),
               ("lines", 3, 11, 3, "XLine"),
               ("event_metadata", 4, 11, 3, "EventMetadataEntry"),
               ("stat_metadata", 5, 11, 3, "StatMetadataEntry"),
               ("stats", 6, 11, 3, "XStat")],
    "XSpace": [("planes", 1, 11, 3, "XPlane"), ("errors", 2, 9, 3),
               ("warnings", 3, 9, 3), ("hostnames", 4, 9, 3)],
}
_PACKAGE = "bench_xplane"
_cache = {}


def message(name: str):
    """The message class ``name`` of the table above."""
    if not _cache:
        from google.protobuf import descriptor_pb2, descriptor_pool
        from google.protobuf import message_factory

        file = descriptor_pb2.FileDescriptorProto(
            name="bench_xplane.proto", package=_PACKAGE, syntax="proto3"
        )
        for msg, fields in _MESSAGES.items():
            m = file.message_type.add(name=msg)
            for fname, number, ftype, label, *type_name in fields:
                f = m.field.add(
                    name=fname, number=number, type=ftype, label=label
                )
                if type_name:
                    f.type_name = f".{_PACKAGE}.{type_name[0]}"
        pool = descriptor_pool.DescriptorPool()
        pool.Add(file)
        for msg in _MESSAGES:
            _cache[msg] = message_factory.GetMessageClass(
                pool.FindMessageTypeByName(f"{_PACKAGE}.{msg}")
            )
    return _cache[name]


def read_space(path: str):
    space = message("XSpace")()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _scope_of(meta, stat_names) -> str:
    """The ``tf_op`` stat of an op's metadata (a string, or a reference into
    the stat names), or ""."""
    for stat in meta.stats:
        if stat_names.get(stat.metadata_id) == "tf_op":
            return stat.str_value or stat_names.get(stat.ref_value, "")
    return ""


def by_pattern(path: str, patterns) -> dict:
    space = read_space(path)
    compiled = {p: re.compile(p) for p in patterns}
    for plane in sorted(space.planes, key=lambda p: p.name):
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        metas = {e.key: e.value for e in plane.event_metadata}
        hits = {}  # metadata id -> the patterns its op matches; containers None
        out = {p: {"seconds": 0.0, "events": 0} for p in patterns}
        intervals = []
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                if ev.metadata_id not in hits:
                    meta = metas.get(ev.metadata_id)
                    name = xplane.op_name(meta.name) if meta else ""
                    scope = _scope_of(meta, stat_names) if meta else ""
                    hits[ev.metadata_id] = (
                        None if xplane.CONTAINER.match(name)
                        else [p for p, rx in compiled.items()
                              if rx.search(name) or rx.search(scope)]
                    )
                if hits[ev.metadata_id] is None:
                    continue
                start = line.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12
                seconds = ev.duration_ps * 1e-12
                intervals.append((start, start + seconds))
                for p in hits[ev.metadata_id]:
                    out[p]["seconds"] += seconds
                    out[p]["events"] += 1
        if intervals:
            out["busy_s"] = xplane.total(xplane.union(intervals))
            return out
    return None
