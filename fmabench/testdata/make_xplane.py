"""Writes ``synthetic.xplane.pb``: a tiny profiler trace with known numbers.

A hand-encoded ``XSpace`` (tsl/profiler/protobuf/xplane.proto: planes,
lines, events, event metadata), so that the trace -> metrics reduction in
``fmabench/xplane.py`` can be checked against numbers worked out by hand.
The layout is the one a TPU trace has: a ``/device:TPU:0`` plane with an
``XLA Ops`` and an ``XLA Modules`` line, and a ``/host:CPU`` plane.

    python fmabench/testdata/make_xplane.py

Device ops (milliseconds from the line's start): two decode steps of two
layers each, a 30 ms hole between them, and one prefill fusion:

    paged_decode_inline.5   0..2      fusion.7   2..6
    paged_decode_inline.5   6..8      fusion.7   8..12
    (idle 12..42, the host is in ``device_get``)
    paged_decode_inline.5  42..44     fusion.7  44..48
    paged_decode_inline.5  48..50     fusion.7  50..54
    (idle 54..60)
    fusion.9               60..100

busy = 24 + 40 = 64 ms of a 100 ms window; idle 36%.
"""

import os


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, wire: int, payload: bytes) -> bytes:
    return varint((num << 3) | wire) + payload


def vint(num: int, n: int) -> bytes:
    return field(num, 0, varint(n))


def blob(num: int, b: bytes) -> bytes:
    return field(num, 2, varint(len(b)) + b)


def event(meta_id: int, start_ms: float, dur_ms: float) -> bytes:
    return vint(1, meta_id) + vint(2, int(start_ms * 1e9)) + vint(3, int(dur_ms * 1e9))


def line(line_id: int, name: str, events) -> bytes:
    body = vint(1, line_id) + blob(2, name.encode()) + vint(3, 1000)
    for e in events:
        body += blob(4, e)
    return body


def plane(plane_id: int, name: str, lines, metadata) -> bytes:
    body = vint(1, plane_id) + blob(2, name.encode())
    for ln in lines:
        body += blob(3, ln)
    for mid, mname in metadata.items():
        meta = vint(1, mid) + blob(2, mname.encode())
        body += blob(4, vint(1, mid) + blob(2, meta))
    return body


def main() -> None:
    dev_meta = {1: "paged_decode_inline.5", 2: "fusion.7", 3: "fusion.9",
                10: "jit_chunk(123)", 11: "jit__prefill(456)"}
    ops = []
    for base in (0, 6, 42, 48):
        ops += [event(1, base, 2), event(2, base + 2, 4)]
    ops.append(event(3, 60, 40))
    modules = [event(10, 0, 12), event(10, 42, 12), event(11, 60, 40)]
    device = plane(1, "/device:TPU:0", [
        line(1, "XLA Modules", modules), line(2, "XLA Ops", ops),
    ], dev_meta)
    host_meta = {1: "device_get", 2: "PjitFunction(chunk)", 3: "tiny"}
    host = plane(2, "/host:CPU", [
        line(7, "python", [event(2, 0, 11), event(1, 11, 32), event(3, 55, 0.5)]),
    ], host_meta)
    space = blob(1, device) + blob(1, host)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "synthetic.xplane.pb")
    with open(path, "wb") as f:
        f.write(space)
    print(path, len(space))


if __name__ == "__main__":
    main()
