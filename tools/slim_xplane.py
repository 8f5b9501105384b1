#!/usr/bin/env python3
"""Cut a profiler trace down to what ``bench/trace_program.py`` reads, so
that a recorded trace is small enough to keep as a test fixture.

    python3 tools/slim_xplane.py IN.xplane.pb OUT.xplane.pb

Kept: each device plane's ``XLA Modules`` and ``XLA Ops`` lines, and the
host's ``engine.*``, ``generate`` and ``arrival_wait`` spans with their
metadata. The device events and every event's description lose their
statistics, the operations' names are cut to the HLO instruction's name
(what ``trace_reduce.op_name`` keeps), and everything else is dropped,
the recording host's name included. Needs
the XPlane protocol buffer that TensorFlow's installation carries; run it
on a host, not on the machine with the chip.
"""
import sys

KEEP_LINES = ("XLA Modules", "XLA Ops")
KEEP_SPANS = ("generate", "arrival_wait")


def slim(space) -> None:
    del space.hostnames[:]
    for plane in list(space.planes):
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:"):
            space.planes.remove(plane)
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        for line in list(plane.lines):
            if device and line.name not in KEEP_LINES:
                plane.lines.remove(line)
                continue
            if device:
                for ev in line.events:
                    del ev.stats[:]
            if not device:
                kept = [ev for ev in line.events
                        if names[ev.metadata_id].startswith("engine.")
                        or names[ev.metadata_id] in KEEP_SPANS]
                del line.events[:]
                line.events.extend(kept)
                if not kept:
                    plane.lines.remove(line)
        used = {ev.metadata_id for line in plane.lines for ev in line.events}
        for k in list(plane.event_metadata):
            if k not in used:
                del plane.event_metadata[k]
            else:
                m = plane.event_metadata[k]
                m.name = m.name.split(" = ", 1)[0]
                m.display_name = ""
                m.metadata = b""
                del m.stats[:]
        if not plane.lines:
            space.planes.remove(plane)


def main(argv=None) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    src, dst = (argv or sys.argv[1:])
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    slim(space)
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())
    return 0


if __name__ == "__main__":
    sys.exit(main())
