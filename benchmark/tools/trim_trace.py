"""Cut a real trace down to a recorded one small enough to keep under
`benchmark/tests/`: the devices' `XLA Ops` lines and the host threads'
longer events inside a short sub-window, no stats, the window's two
markers re-set at the cut, written as a text proto (which
`jax.profiler.ProfileData.from_text_proto` reads, and a person can).

    python -m benchmark.tools.trim_trace <in.xplane.pb> <out.txt> <start_s> <length_s> [min_host_event_us]

`start_s` counts from the harness's `bench.window_begin` marker. Needs
tensorflow's copy of the xplane proto, so it is a builder's tool and
nothing the benchmark imports.
"""
from __future__ import annotations

import sys

from benchmark import trace_reduce


def main() -> int:
    from google.protobuf import text_format
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    src, dst = sys.argv[1], sys.argv[2]
    start_s, length_s = float(sys.argv[3]), float(sys.argv[4])
    min_host_ps = float(sys.argv[5]) * 1e6 if len(sys.argv) > 5 else 20e6
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())

    def abs_ps(line, event):
        return line.timestamp_ns * 1000 + event.offset_ps

    begin_ps = None
    for plane in space.planes:
        names = {k: m.name for k, m in plane.event_metadata.items()}
        for line in plane.lines:
            for e in line.events:
                if names.get(e.metadata_id) == trace_reduce.WINDOW_BEGIN:
                    begin_ps = abs_ps(line, e)
    if begin_ps is None:
        raise SystemExit("no bench.window_begin marker in the trace")
    lo = begin_ps + start_s * 1e12
    hi = lo + length_s * 1e12

    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        kept = out.planes.add()
        kept.id, kept.name = plane.id, plane.name
        ids = {}

        def meta(name):
            if name not in ids:
                ids[name] = len(ids) + 1
                kept.event_metadata[ids[name]].id = ids[name]
                kept.event_metadata[ids[name]].name = name
            return ids[name]

        for line in plane.lines:
            if device and line.name != trace_reduce.OPS_LINE:
                continue
            events = []
            for e in line.events:
                name = names.get(e.metadata_id, "")
                a = abs_ps(line, e)
                if a + e.duration_ps <= lo or a >= hi:
                    continue
                if name in (trace_reduce.WINDOW_BEGIN, trace_reduce.WINDOW_END):
                    continue
                if not device and e.duration_ps < min_host_ps \
                        and not name.startswith(trace_reduce.SPAN_PREFIX):
                    continue
                if trace_reduce.HOST_NOISE.match(name):
                    continue
                if len(name) > 70:      # HLO text: keep what op_name reads
                    name = name[:70] + (
                        " ... " + trace_reduce.MOSAIC
                        if trace_reduce.MOSAIC in name else " ...")
                events.append((name, a, e.duration_ps))
            if not events:
                continue
            new = kept.lines.add()
            new.id, new.name = line.id, line.name
            new.timestamp_ns = int(lo // 1000)
            for name, a, dur in events:
                ev = new.events.add()
                ev.metadata_id = meta(name)
                ev.offset_ps = int(a - new.timestamp_ns * 1000)
                ev.duration_ps = int(dur)
        if not device:
            marks = kept.lines.add()
            marks.id, marks.name = 999999, "python"
            marks.timestamp_ns = int(lo // 1000)
            for name, at in ((trace_reduce.WINDOW_BEGIN, lo),
                             (trace_reduce.WINDOW_END, hi)):
                ev = marks.events.add()
                ev.metadata_id = meta(name)
                ev.offset_ps = int(at - marks.timestamp_ns * 1000)
                ev.duration_ps = 1000
    with open(dst, "w") as f:
        f.write(text_format.MessageToString(out))
    print(dst, sum(len(l.events) for p in out.planes for l in p.lines),
          "events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
