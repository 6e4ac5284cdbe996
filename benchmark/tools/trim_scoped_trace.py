"""Cut a real trace down to one that `benchmark/scope_reduce.py` can be
checked against by hand: `tools/trim_trace.py`'s cut, with two
differences. It keeps the stat `tf_op` of each kept operation's metadata
record (jax's name stack, which is what `scope_reduce` reads), and it
keeps far fewer events: of the devices' `XLA Ops` lines only operations
of at least `min_op_us`, of the host only the program's and the
harness's spans (`dtpu.trainer.*`, `bench.*`), CLIPPED to the cut (a
report boundary's span is as long as its window: the host waits in it
for ten steps). The window's two markers are re-set at the cut.

    python -m benchmark.tools.trim_scoped_trace <in.xplane.pb> <out.txt> <start_s> <length_s> <min_op_us>

`start_s` counts from the harness's `bench.window_begin` marker. Needs
tensorflow's copy of the xplane proto: a builder's tool, as
`trim_trace.py` is, and nothing the benchmark imports.
"""
from __future__ import annotations

import sys

from benchmark import scope_reduce, trace_reduce

KEPT_SPANS = (scope_reduce.names()["span_prefix"], trace_reduce.SPAN_PREFIX)


def main() -> int:
    from google.protobuf import text_format
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    src, dst = sys.argv[1], sys.argv[2]
    start_s, length_s = float(sys.argv[3]), float(sys.argv[4])
    min_op_ps = float(sys.argv[5]) * 1e6
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        raw = f.read()
    space.ParseFromString(raw)
    stacks = scope_reduce.name_stacks(raw)
    markers = (trace_reduce.WINDOW_BEGIN, trace_reduce.WINDOW_END)

    def abs_ps(line, event):
        return line.timestamp_ns * 1000 + event.offset_ps

    begin_ps = min(
        abs_ps(line, e) for plane in space.planes for line in plane.lines
        for e in line.events
        if plane.event_metadata[e.metadata_id].name == markers[0])
    lo = begin_ps + start_s * 1e12
    hi = lo + length_s * 1e12

    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        kept = out.planes.add()
        kept.id, kept.name = plane.id, plane.name
        kept.stat_metadata[1].id = 1
        kept.stat_metadata[1].name = scope_reduce.NAME_STACK_STAT
        ids = {}

        def meta(name, stack=""):
            if name not in ids:
                ids[name] = len(ids) + 1
                record = kept.event_metadata[ids[name]]
                record.id, record.name = ids[name], name
                if stack:
                    stat = record.stats.add()
                    stat.metadata_id, stat.str_value = 1, stack
            return ids[name]

        for line in plane.lines:
            if device and line.name != trace_reduce.OPS_LINE:
                continue
            new = None
            for e in line.events:
                name = plane.event_metadata[e.metadata_id].name
                a, b = abs_ps(line, e), abs_ps(line, e) + e.duration_ps
                if b <= lo or a >= hi or name in markers:
                    continue
                if device and e.duration_ps < min_op_ps:
                    continue
                if not device:
                    if not name.startswith(KEPT_SPANS):
                        continue
                    a, b = max(a, lo), min(b, hi)
                stack = stacks.get(plane.name, {}).get(name, "")
                if len(name) > 70:      # HLO text: keep what op_name reads
                    name = name[:70] + (
                        " ... " + trace_reduce.MOSAIC
                        if trace_reduce.MOSAIC in name else " ...")
                if new is None:
                    new = kept.lines.add()
                    new.id, new.name = line.id, line.name
                    new.timestamp_ns = int(lo // 1000)
                ev = new.events.add()
                ev.metadata_id = meta(name, stack)
                ev.offset_ps = int(a - new.timestamp_ns * 1000)
                ev.duration_ps = int(b - a)
        if not device:
            marks = kept.lines.add()
            marks.id, marks.name = 999999, "python"
            marks.timestamp_ns = int(lo // 1000)
            for name, at in zip(markers, (lo, hi)):
                ev = marks.events.add()
                ev.metadata_id = meta(name)
                ev.offset_ps = int(at - marks.timestamp_ns * 1000)
                ev.duration_ps = 1000
    with open(dst, "w") as f:
        f.write(text_format.MessageToString(out))
    print(dst, sum(len(line.events) for p in out.planes for line in p.lines),
          "events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
