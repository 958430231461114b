"""The event METADATA of a profiler trace (``.xplane.pb``), read with the
standard library alone.

``jax.profiler.ProfileData`` gives a plane's lines and events with the
events' own stats.  What XLA knows of an instruction (its ``jax.named_scope``
path as the stat ``tf_op``, its category, the flops and bytes of its cost
model) hangs on the plane's ``event_metadata`` instead, which ProfileData
does not show.  This walks the protobuf wire format far enough to decode
those maps and skips the lines, which are nearly all of the file, by their
length; the result joins to ProfileData's events by name.

The fields, from tsl/profiler/protobuf/xplane.proto::

    XSpace  1 planes
    XPlane  2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata (map)
    map entry  1 key, 2 value
    XEventMetadata  1 id, 2 name, 4 display_name, 5 stats
    XStatMetadata   1 id, 2 name
    XStat  1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str, 6 bytes,
           7 ref (the id of a stat_metadata whose name is the string)
"""
import struct

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message's top level: an int
    for a varint, the raw ``memoryview`` for anything with a length or a
    fixed width.  Nothing nested is touched."""
    buf, i, n = memoryview(buf), 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _BYTES:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (_FIXED64, _FIXED32):
            size = 8 if wire == _FIXED64 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield key >> 3, wire, value


def _signed(v):
    """An int64 from its varint: ids are int64 and some are negative."""
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf):
    """(stat metadata id, value, whether the value is a reference)."""
    key, value, ref = None, None, False
    for num, _, v in fields(buf):
        if num == 1:
            key = _signed(v)
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif num == 6:
            value = bytes(v)
        elif num == 7:
            value, ref = _signed(v), True
    return key, value, ref


def _entry(buf):
    """The value message of one map entry."""
    return next((v for num, _, v in fields(buf) if num == 2), b"")


def _plane(buf):
    name, events, stat_names = "", [], {}
    for num, _, v in fields(buf):
        if num == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif num == 4:
            meta = {"id": 0, "name": "", "display_name": "", "stats": []}
            for n, _, x in fields(_entry(v)):
                if n == 1:
                    meta["id"] = _signed(x)
                elif n == 2:
                    meta["name"] = bytes(x).decode("utf-8", "replace")
                elif n == 4:
                    meta["display_name"] = bytes(x).decode("utf-8", "replace")
                elif n == 5:
                    meta["stats"].append(_stat(x))
            events.append(meta)
        elif num == 5:
            sid, sname = 0, ""
            for n, _, x in fields(_entry(v)):
                if n == 1:
                    sid = _signed(x)
                elif n == 2:
                    sname = bytes(x).decode("utf-8", "replace")
            stat_names[sid] = sname
    for meta in events:             # stats by name, references resolved
        meta["stats"] = {stat_names.get(k, str(k)):
                         (stat_names.get(v, "") if ref else v)
                         for k, v, ref in meta["stats"]}
    return name, {m["id"]: m for m in events}


def event_metadata(path):
    """``{plane name: {metadata id: {"id", "name", "display_name", "stats":
    {stat name: value}}}}`` of every plane of the file."""
    with open(path, "rb") as f:
        space = f.read()
    return dict(_plane(v) for num, _, v in fields(space) if num == 1)


def op_scopes(path, stat="tf_op"):
    """``{plane name: {event name: its metadata's <stat>}}``: for a TPU's
    plane, an executed instruction's text to its ``jax.named_scope`` path.
    Instructions without the stat (XLA:TPU's ``ragged-dot`` custom calls
    carry none) are left out."""
    return {plane: {m["name"]: m["stats"][stat] for m in metas.values()
                    if m["stats"].get(stat)}
            for plane, metas in event_metadata(path).items()}
