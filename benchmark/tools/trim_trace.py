"""Makes a recorded trace small enough to keep with the tests: copies
an ``.xplane.pb`` without the planes no reducer reads. A trace's
largest plane is ``/host:metadata`` (the programs' HLO protos, over
half of a tiny trace); devices and ``/host:CPU`` are kept byte for
byte.

    python3 -m benchmark.tools.trim_trace <in.xplane.pb> <out.xplane.pb>

``tests/data/tiny_scopes.xplane.pb`` is a ``--rehearse --trace 1
--seconds 5`` run of ``mistral-7b-int8.qa-steady`` on a TPU v5 lite
(PR 26), trimmed by this.
"""

from __future__ import annotations

import sys

from benchmark.harness.scope_reduce import wire_fields, text

DROP = ("/host:metadata",)


def _varint_bytes(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def trim(data: bytes) -> bytes:
    out = bytearray()
    for field, wire, val in wire_fields(memoryview(data)):
        if wire != 2:
            raise ValueError("an XSpace has only length-delimited fields")
        if field == 1 and any(
                f == 2 and text(v) in DROP for f, _w, v in wire_fields(val)):
            continue
        out += _varint_bytes(field << 3 | wire)
        out += _varint_bytes(len(val)) + bytes(val)
    return bytes(out)


def main(argv=None) -> int:
    src, dst = (argv or sys.argv[1:])
    with open(src, "rb") as f:
        data = f.read()
    small = trim(data)
    with open(dst, "wb") as f:
        f.write(small)
    print(f"{src}: {len(data)} bytes -> {dst}: {len(small)} bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
