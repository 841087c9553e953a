"""Read a profiler trace (``.xplane.pb``) without TensorFlow.

The messages below are the subset of ``tsl/profiler/protobuf/xplane.proto``
that the reduction reads, built as a descriptor at import time so that
nothing but ``protobuf`` is needed.  Field numbers are the proto's own.
"""
from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto
_FIELDS = {
    "XSpace": [("planes", 1, _T.TYPE_MESSAGE, "XPlane", True)],
    "XPlane": [
        ("id", 1, _T.TYPE_INT64, None, False),
        ("name", 2, _T.TYPE_STRING, None, False),
        ("lines", 3, _T.TYPE_MESSAGE, "XLine", True),
        ("event_metadata", 4, _T.TYPE_MESSAGE, "XPlane.EventMetadataEntry",
         True),
        ("stat_metadata", 5, _T.TYPE_MESSAGE, "XPlane.StatMetadataEntry",
         True),
        ("stats", 6, _T.TYPE_MESSAGE, "XStat", True),
    ],
    "XLine": [
        ("id", 1, _T.TYPE_INT64, None, False),
        ("name", 2, _T.TYPE_STRING, None, False),
        ("timestamp_ns", 3, _T.TYPE_INT64, None, False),
        ("events", 4, _T.TYPE_MESSAGE, "XEvent", True),
        ("duration_ps", 9, _T.TYPE_INT64, None, False),
    ],
    "XEvent": [
        ("metadata_id", 1, _T.TYPE_INT64, None, False),
        ("offset_ps", 2, _T.TYPE_INT64, None, False),
        ("duration_ps", 3, _T.TYPE_INT64, None, False),
        ("stats", 4, _T.TYPE_MESSAGE, "XStat", True),
    ],
    "XStat": [
        ("metadata_id", 1, _T.TYPE_INT64, None, False),
        ("double_value", 2, _T.TYPE_DOUBLE, None, False),
        ("uint64_value", 3, _T.TYPE_UINT64, None, False),
        ("int64_value", 4, _T.TYPE_INT64, None, False),
        ("str_value", 5, _T.TYPE_STRING, None, False),
        ("bytes_value", 6, _T.TYPE_BYTES, None, False),
        ("ref_value", 7, _T.TYPE_UINT64, None, False),
    ],
    "XEventMetadata": [
        ("id", 1, _T.TYPE_INT64, None, False),
        ("name", 2, _T.TYPE_STRING, None, False),
        ("display_name", 4, _T.TYPE_STRING, None, False),
        ("stats", 5, _T.TYPE_MESSAGE, "XStat", True),
    ],
    "XStatMetadata": [
        ("id", 1, _T.TYPE_INT64, None, False),
        ("name", 2, _T.TYPE_STRING, None, False),
    ],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}


def _add_field(msg, name, number, ftype, type_name, repeated):
    f = msg.field.add(name=name, number=number, type=ftype)
    f.label = _T.LABEL_REPEATED if repeated else _T.LABEL_OPTIONAL
    if type_name:
        f.type_name = ".chipbench.xplane." + type_name


def _build():
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench.xplane",
        syntax="proto3")
    for name, fields in _FIELDS.items():
        msg = fd.message_type.add(name=name)
        for field in fields:
            _add_field(msg, *field)
        if name == "XStat":
            msg.oneof_decl.add(name="value")
            for f in msg.field[1:]:
                f.oneof_index = 0
        if name == "XPlane":
            for entry, value in _MAPS.items():
                sub = msg.nested_type.add(name=entry)
                sub.options.map_entry = True
                _add_field(sub, "key", 1, _T.TYPE_INT64, None, False)
                _add_field(sub, "value", 2, _T.TYPE_MESSAGE, value, False)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.xplane.XSpace"))


XSpace = _build()


def stat_value(stat, names: dict[int, str]):
    """The value of one ``XStat``; a reference stat resolves to its name."""
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    value = getattr(stat, kind)
    return names.get(value, value) if kind == "ref_value" else value


def stats(holder, names: dict[int, str]) -> dict:
    """``{stat name: value}`` of an event, a metadata entry or a plane."""
    return {names.get(s.metadata_id, s.metadata_id): stat_value(s, names)
            for s in holder.stats}


def load(path: str):
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space
