"""The `op_name` of each device op in a trace, by the op's event name.

On a TPU v5e the profiler keeps an XLA op's `op_name` (its scope path,
"jit(_chunk)/while/body/closed_call/lark_roster/gather:") in the stat
`tf_op` of the op's event *metadata*, which `jax.profiler.ProfileData`
does not expose: its events carry only their own stats.  So this reads
the `.xplane.pb` a second time with protobuf, declaring only the fields
it needs of the public XSpace schema (tsl/profiler/protobuf/xplane.proto;
every other field is skipped unparsed), and maps each device-op event
name (the op's HLO text, as ProfileData names the event) to its op_name.
"""
from __future__ import annotations

import functools

from larkbench import trace

#: the event-metadata stat that holds the op_name
OP_NAME_STAT = "tf_op"


@functools.cache
def _xspace_class():
    """The message class of the XSpace subset."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="larkbench_xplane_subset.proto", package="larkbench",
        syntax="proto3")

    def message(name, fields):
        m = fd.message_type.add(name=name)
        for fname, number, kind, repeated, type_name in fields:
            f = m.field.add(name=fname, number=number, type=kind,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if type_name:
                f.type_name = ".larkbench." + type_name

    msg, i64, u64, text = F.TYPE_MESSAGE, F.TYPE_INT64, F.TYPE_UINT64, \
        F.TYPE_STRING
    # field numbers as in xplane.proto; maps are repeated entries
    message("XSpace", [("planes", 1, msg, True, "XPlane")])
    message("XPlane", [
        ("name", 2, text, False, None),
        ("event_metadata", 4, msg, True, "EventMetadataEntry"),
        ("stat_metadata", 5, msg, True, "StatMetadataEntry")])
    message("EventMetadataEntry", [
        ("key", 1, i64, False, None),
        ("value", 2, msg, False, "XEventMetadata")])
    message("StatMetadataEntry", [
        ("key", 1, i64, False, None),
        ("value", 2, msg, False, "XStatMetadata")])
    message("XEventMetadata", [
        ("name", 2, text, False, None),
        ("stats", 5, msg, True, "XStat")])
    message("XStat", [("metadata_id", 1, i64, False, None),
                      ("str_value", 5, text, False, None),
                      ("ref_value", 7, u64, False, None)])
    message("XStatMetadata", [("name", 2, text, False, None)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("larkbench.XSpace"))


def read(path: str) -> dict:
    """{device-op event name: op_name} over the trace's device planes."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = {}
    for plane in space.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) == OP_NAME_STAT:
                    out.setdefault(e.value.name, st.str_value
                                   or stat_names.get(st.ref_value, ""))
    return out
