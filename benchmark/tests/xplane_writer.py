"""Writes a tiny ``.xplane.pb`` by hand, so that the reduction can be
checked against intervals that are known because they were chosen.

The profiler's container (tsl/profiler/protobuf/xplane.proto), as far
as ``jax.profiler.ProfileData`` reads it:

    XSpace         { repeated XPlane planes = 1; }
    XPlane         { int64 id = 1; string name = 2; repeated XLine lines = 3;
                     map<int64, XEventMetadata> event_metadata = 4; }
    XLine          { int64 id = 1; string name = 2; int64 timestamp_ns = 3;
                     repeated XEvent events = 4; }
    XEvent         { int64 metadata_id = 1; int64 offset_ps = 2;
                     int64 duration_ps = 3; }
    XEventMetadata { int64 id = 1; string name = 2; }

Only varints and length-delimited fields are needed.
"""


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint((field << 3) | 2) + _varint(len(payload)) + payload


def xspace(planes: dict) -> bytes:
    """``{plane: {line: [(event name, start_ns, end_ns), ...]}}`` ->
    serialized XSpace."""
    out = b""
    for pid, (plane_name, lines) in enumerate(planes.items(), 1):
        names: dict = {}
        body = _int(1, pid) + _bytes(2, plane_name.encode())
        for lid, (line_name, events) in enumerate(lines.items(), 1):
            line = _int(1, lid) + _bytes(2, line_name.encode()) + _int(3, 0)
            for name, start_ns, end_ns in events:
                mid = names.setdefault(name, len(names) + 1)
                line += _bytes(4, (
                    _int(1, mid) + _int(2, int(start_ns * 1000))
                    + _int(3, int((end_ns - start_ns) * 1000))
                ))
            body += _bytes(3, line)
        for name, mid in names.items():
            meta = _int(1, mid) + _bytes(2, name.encode())
            body += _bytes(4, _int(1, mid) + _bytes(2, meta))
        out += _bytes(1, body)
    return out


def write(path: str, planes: dict) -> None:
    with open(path, "wb") as f:
        f.write(xspace(planes))
