"""Cut a recorded .xplane.pb to a short stretch, with nothing but the
protobuf wire format: ``python -m chipbench.tests.cut_xplane <in> <out>
<start_ms> <length_ms>`` (times from the first device op). Keeps every
plane and line, the events that START inside the stretch, and of the
metadata only ids and names (HLO text and per-event stats go). It made
chipbench/tests/data/step.xplane.pb from record_fixture.py's trace.

XSpace{1: planes}; XPlane{1 id, 2 name, 3 lines, 4 event_metadata map,
5 stat_metadata map, 6 stats}; XLine{1 id, 2 name, 3 timestamp_ns,
4 events, 9 duration_ps, 10 display_id, 11 display_name};
XEvent{1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats};
XEventMetadata{1 id, 2 name, 3 metadata, 4 display_name, 5 stats}.
"""

from __future__ import annotations

import sys


def varint(buf: bytes, at: int):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf: bytes):
    """[(field number, wire type, value)] of one message."""
    out, at = [], 0
    while at < len(buf):
        key, at = varint(buf, at)
        num, wt = key >> 3, key & 7
        if wt == 0:
            value, at = varint(buf, at)
        elif wt == 1:
            value, at = buf[at:at + 8], at + 8
        elif wt == 2:
            size, at = varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wt == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wt}")
        out.append((num, wt, value))
    return out


def enc_varint(value: int) -> bytes:
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def message(rows) -> bytes:
    out = bytearray()
    for num, wt, value in rows:
        out += enc_varint(num << 3 | wt)
        if wt == 0:
            out += enc_varint(value)
        elif wt == 2:
            out += enc_varint(len(value)) + value
        else:
            out += value
    return bytes(out)


def first(rows, num, default=0):
    return next((v for n, _, v in rows if n == num), default)


def line_events(line_rows):
    t_ps = first(line_rows, 3) * 1000
    for num, _, value in line_rows:
        if num == 4:
            ev = fields(value)
            yield t_ps + first(ev, 2), ev


def cut(space: bytes, start_ms: float, length_ms: float) -> bytes:
    planes = [fields(v) for n, _, v in fields(space) if n == 1]
    device_starts = [
        t for p in planes if first(p, 2, b"").startswith(b"/device:")
        for n, _, v in p if n == 3 for t, _ in line_events(fields(v))]
    t0 = min(device_starts) + int(start_ms * 1e9)
    t1 = t0 + int(length_ms * 1e9)
    out_planes = []
    for plane in planes:
        used, rows = set(), []
        for num, wt, value in plane:
            if num == 3:
                line = fields(value)
                kept = [(4, 2, message([r for r in ev if r[0] != 4]))
                        for t, ev in line_events(line) if t0 <= t < t1]
                used.update(first(fields(v), 1) for _, _, v in kept)
                rows.append((3, 2, message(
                    [r for r in line if r[0] != 4] + kept)))
            elif num not in (4, 5, 6):
                rows.append((num, wt, value))
        for num, wt, value in plane:
            if num == 4:       # map entry {1: key, 2: XEventMetadata}
                entry = fields(value)
                if first(entry, 1) in used:
                    meta = [r for r in fields(first(entry, 2, b""))
                            if r[0] in (1, 2, 4)]
                    rows.append((4, 2, message(
                        [(1, 0, first(entry, 1)), (2, 2, message(meta))])))
        out_planes.append((1, 2, message(rows)))
    return message(out_planes)


def main() -> int:
    src, dst, start_ms, length_ms = sys.argv[1:5]
    with open(src, "rb") as fh:
        small = cut(fh.read(), float(start_ms), float(length_ms))
    with open(dst, "wb") as fh:
        fh.write(small)
    print(f"{dst}: {len(small)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
