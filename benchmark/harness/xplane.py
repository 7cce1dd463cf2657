"""The stats that XLA attaches to each op's event metadata in an
``.xplane.pb`` (its source line, its name in the JAX program, its
category), which ``jax.profiler.ProfileData`` does not expose. A
minimal reader of the protobuf wire format for the few fields of
``xplane.proto`` this needs:

XSpace.planes = 1; XPlane.name = 2, event_metadata = 4 (map),
stat_metadata = 5 (map); XEventMetadata.name = 2, display_name = 4,
stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
double = 2, uint64 = 3, int64 = 4, str = 5, ref = 7."""

import struct


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of each field of a message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} not supported")
        yield num, wt, v


def _map_entry(buf):
    key = val = None
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _stat(buf, stat_names):
    mid, val = None, None
    for num, _, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num in (3, 4):
            val = v if num == 3 or v < 1 << 63 else v - (1 << 64)
        elif num == 5:
            val = v.decode("utf-8", "replace")
        elif num == 7:
            val = stat_names.get(v, "")
    return stat_names.get(mid, str(mid)), val


def event_metadata(path: str, plane_filter) -> dict:
    """{plane name: {event name: {stat name: value}}} for the planes
    whose name ``plane_filter`` accepts."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for num, _, plane in _fields(space):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pn, _, v in _fields(plane):
            if pn == 2:
                name = v.decode("utf-8", "replace")
            elif pn == 4:
                metas.append(_map_entry(v)[1])
            elif pn == 5:
                key, sm = _map_entry(v)
                for fn, _, fv in _fields(sm or b""):
                    if fn == 2:
                        stat_names[key] = fv.decode("utf-8", "replace")
        if not plane_filter(name):
            continue
        table = out.setdefault(name, {})
        for m in metas:
            ev_name, stats = "", {}
            for fn, _, fv in _fields(m or b""):
                if fn in (2, 4) and not ev_name:
                    ev_name = fv.decode("utf-8", "replace")
                elif fn == 5:
                    k, val = _stat(fv, stat_names)
                    stats[k] = val
            if ev_name:
                table[ev_name] = stats
    return out
