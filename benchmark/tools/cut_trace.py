"""Cuts a recorded trace down to a stretch of time, for the tests: copies
an ``.xplane.pb`` keeping, on every line of every plane, the events that
begin inside ``[from, to)`` seconds after the first device program
begins (``trim_trace`` drops whole planes; a program of thousands of
small ops still leaves tens of megabytes for a second of serving).
Metadata stay whole, so names and scopes read as before.

    python3 -m benchmark.tools.cut_trace <in.xplane.pb> <out> <from> <to>

``tests/data/tiny_xing.xplane.pb`` is a ``--rehearse --trace 1
--seconds 5`` run of ``xing4-29b-a4b-int8.summarize-threads-16k`` on a
TPU v5 lite (PR 33), trimmed by ``trim_trace`` and cut by this.
"""

from __future__ import annotations

import sys

from benchmark.harness.scope_reduce import wire_fields, text
from benchmark.tools.trim_trace import _varint_bytes

PLANE_LINES, LINE_EVENTS, LINE_T0_NS, EVENT_OFFSET_PS = 3, 4, 3, 2


def _put(out: bytearray, field: int, wire: int, val) -> None:
    out += _varint_bytes(field << 3 | wire)
    if wire == 0:
        out += _varint_bytes(val)
    elif wire == 2:
        out += _varint_bytes(len(val)) + bytes(val)
    else:
        out += bytes(val)


def _line_t0(line) -> int:
    return next((v for f, _w, v in wire_fields(line) if f == LINE_T0_NS),
                0)


def _first_module_ps(data) -> int:
    starts = []
    for _f, _w, plane in wire_fields(data):
        fields = list(wire_fields(plane))
        name = next((text(v) for f, _w, v in fields if f == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        for f, _w, line in fields:
            if f != PLANE_LINES or not any(
                    ff == 2 and text(v) == "XLA Modules"
                    for ff, _ww, v in wire_fields(line)):
                continue
            t0 = _line_t0(line) * 1000
            starts += [t0 + next((v for f3, _w3, v in wire_fields(ev)
                                  if f3 == EVENT_OFFSET_PS), 0)
                       for f2, _w2, ev in wire_fields(line)
                       if f2 == LINE_EVENTS]
    return min(starts)


def cut(data: bytes, lo_s: float, hi_s: float) -> bytes:
    view = memoryview(data)
    zero = _first_module_ps(view)
    lo, hi = zero + int(lo_s * 1e12), zero + int(hi_s * 1e12)
    out = bytearray()
    for field, wire, plane in wire_fields(view):
        new_plane = bytearray()
        for f, w, v in wire_fields(plane):
            if f != PLANE_LINES:
                _put(new_plane, f, w, v)
                continue
            t0 = _line_t0(v) * 1000
            new_line = bytearray()
            for f2, w2, v2 in wire_fields(v):
                if f2 == LINE_EVENTS:
                    at = t0 + next((x for f3, _w3, x in wire_fields(v2)
                                    if f3 == EVENT_OFFSET_PS), 0)
                    if not lo <= at < hi:
                        continue
                _put(new_line, f2, w2, v2)
            _put(new_plane, f, w, new_line)
        _put(out, field, wire, new_plane)
    return bytes(out)


def main(argv=None) -> int:
    src, dst, lo, hi = (argv or sys.argv[1:])
    with open(src, "rb") as f:
        data = f.read()
    small = cut(data, float(lo), float(hi))
    with open(dst, "wb") as f:
        f.write(small)
    print(f"{src}: {len(data)} bytes -> {dst}: {len(small)} bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
