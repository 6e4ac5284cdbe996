"""From a profiler trace (`.xplane.pb`) to numbers.

`jax.profiler.ProfileData` reads the file with nothing but jax: planes
(one per device, `/device:TPU:<n>`, and `/host:CPU`), their lines (a
device's `XLA Ops` line holds one event per operation that ran; the
host's lines are threads), and events with a start and a duration. On
this installation a device event's name is the operation's whole HLO
text (`%fusion.12 = bf16[...] fusion(...)`): `op_name` keeps the
instruction's name, and puts `mosaic:` in front of a Pallas kernel (a
custom call whose target is `tpu_custom_call`).
`load` turns that into plain tuples; everything after it is arithmetic
on intervals, checked in `benchmark/tests/test_trace_reduce.py` against
a small recorded trace.

What is computed, per device and then averaged over the devices used:

- the traced window: between the harness's two marker annotations
  (`bench.window_begin`, `bench.window_end`), which sit on the host
  plane and share the devices' clock;
- busy: the union of the device's operation intervals inside the window
  (control-flow operations such as `while` enclose their bodies' events
  and are left out, else a scanned layer loop would read as one busy
  block); idle share is 1 - busy / window;
- time by operation name (`ops`) and by operation *class* (`op_class`:
  the name without its numeric suffix, `fusion.123` -> `fusion`);
- collectives: the union of collective operations' intervals, and its
  exposed part, in which no other operation ran on that device;
- idle gaps: the complement of busy, each gap named by what the host
  was doing in it: the innermost harness span (`bench.*`) that covers
  most of the gap, else the profiler's own host-thread event that
  overlaps it most, else `unattributed`.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]            # (start_s, end_s)
Event = Tuple[str, float, float]          # (name, start_s, end_s)

WINDOW_BEGIN = "bench.window_begin"
WINDOW_END = "bench.window_end"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: Operations that only enclose other operations' events.
CONTROL_FLOW = re.compile(r"^(while|conditional|cond|call)(\.\d+)?$")
HLO_TEXT = re.compile(r"^%?([\w.\-]+) = ")
MOSAIC = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
#: Host events that say nothing about what the host was doing.
HOST_NOISE = re.compile(r"^(ThreadpoolListener|\$|end: )")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """`fusion.12` from `%fusion.12 = bf16[...] fusion(...)`;
    `mosaic:<name>` for a Pallas kernel; anything else as it is."""
    m = HLO_TEXT.match(event_name)
    if not m:
        return event_name
    return ("mosaic:" if MOSAIC in event_name else "") + m.group(1)


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [(event name, start_s, end_s)]}}; lines
    of one name within a plane (host threads are all `python`) get
    `#<n>` appended. A `.txt` path is read as a text proto (the recorded
    trace of the tests)."""
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines: Dict[str, List[Event]] = {}
        on_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue    # steps, modules, async copies: never read
            name = line.name
            n = 1
            while name in lines:
                n += 1
                name = f"{line.name}#{n}"
            names: Dict[str, str] = {}    # the texts are long: map once

            def short(text: str) -> str:
                if text not in names:
                    names[text] = op_name(text)
                return names[text]

            lines[name] = [
                (short(e.name), e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events
            ]
        out[plane.name] = lines
    return out


# -- interval arithmetic -----------------------------------------------------
def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base: Sequence[Interval], cut: Sequence[Interval]) -> List[Interval]:
    """Parts of `base` (a union) outside `cut` (a union)."""
    out: List[Interval] = []
    j = 0
    for a, b in base:
        cur = a
        while j < len(cut) and cut[j][1] <= cur:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def op_class(name: str) -> str:
    return re.sub(r"[.\-_]?\d+$", "", name)


# -- reduction ---------------------------------------------------------------
def window_of(planes: Dict[str, Dict[str, List[Event]]]) -> Optional[Interval]:
    begin = end = None
    for lines in planes.values():
        for events in lines.values():
            for name, a, _b in events:
                if name == WINDOW_BEGIN:
                    begin = a if begin is None else min(begin, a)
                elif name == WINDOW_END:
                    end = a if end is None else max(end, a)
    if begin is None or end is None or end <= begin:
        return None
    return (begin, end)


def host_events(planes: Dict[str, Dict[str, List[Event]]]) -> List[Event]:
    out: List[Event] = []
    for plane, lines in planes.items():
        if not plane.startswith("/host:"):
            continue
        for events in lines.values():
            out.extend(
                e for e in events
                if e[2] > e[1] and not HOST_NOISE.match(e[0])
                and e[0] not in (WINDOW_BEGIN, WINDOW_END)
            )
    return out


class HostActivity:
    """Names an idle gap by what the host was doing in it (see the
    module docstring). Overlaps are computed with numpy over all host
    events at once: a trace holds hundreds of thousands of them."""

    def __init__(self, host: Sequence[Event]) -> None:
        import numpy as np

        self._np = np
        spans = [e for e in host if e[0].startswith(SPAN_PREFIX)]
        others = [e for e in host if not e[0].startswith(SPAN_PREFIX)]
        self.span_names = [e[0] for e in spans]
        self.other_names = [e[0] for e in others]
        self._spans = np.asarray([(e[1], e[2]) for e in spans],
                                 np.float64).reshape(-1, 2)
        self._others = np.asarray([(e[1], e[2]) for e in others],
                                  np.float64).reshape(-1, 2)

    def _overlaps(self, table, gap: Interval):
        np = self._np
        return np.clip(np.minimum(table[:, 1], gap[1])
                       - np.maximum(table[:, 0], gap[0]), 0.0, None)

    def name(self, gap: Interval) -> str:
        np = self._np
        length = gap[1] - gap[0]
        if len(self._spans):
            covers = self._overlaps(self._spans, gap) >= 0.5 * length
            if covers.any():
                durations = np.where(
                    covers, self._spans[:, 1] - self._spans[:, 0], np.inf)
                return self.span_names[int(durations.argmin())]
        if len(self._others):
            ov = self._overlaps(self._others, gap)
            if ov.max() > 0:
                return self.other_names[int(ov.argmax())]
        return "unattributed"


def reduce_device(events: Sequence[Event], window: Interval,
                  host: "HostActivity",
                  max_named_gaps: int = 300) -> Dict[str, Any]:
    lo, hi = window
    ops: Dict[str, List[float]] = {}
    work: List[Interval] = []
    coll: List[Interval] = []
    compute: List[Interval] = []
    for name, a, b in events:
        if b <= lo or a >= hi or CONTROL_FLOW.match(name):
            continue
        a, b = max(a, lo), min(b, hi)
        rec = ops.setdefault(name, [0.0, 0])
        rec[0] += b - a
        rec[1] += 1
        work.append((a, b))
        (coll if COLLECTIVE.match(name) else compute).append((a, b))
    busy = union(work)
    coll_u, compute_u = union(coll), union(compute)
    gaps = subtract([(lo, hi)], busy)
    named: Dict[str, float] = {}
    longest = sorted(gaps, key=lambda g: g[0] - g[1])
    for gap in longest[:max_named_gaps]:
        label = host.name(gap)
        named[label] = named.get(label, 0.0) + gap[1] - gap[0]
    rest = total(longest[max_named_gaps:])
    if rest:
        named["(shorter gaps, not named)"] = rest
    return {
        "busy_s": total(busy),
        "ops": {k: (v[0], v[1]) for k, v in ops.items()},
        "collective_s": total(coll_u),
        "collective_exposed_s": total(subtract(coll_u, compute_u)),
        "idle_by_host_activity": named,
        "longest_gap_s": (longest[0][1] - longest[0][0]) if longest else 0.0,
        "n_gaps": len(gaps),
    }


def device_op_lines(planes: Dict[str, Dict[str, List[Event]]],
                    n_devices: Optional[int] = None,
                    stand_in: Optional[str] = None) -> List[List[Event]]:
    """One list of operation events per device, by device id. `stand_in`
    (rehearsals off the chip only) names host-plane lines to read as
    devices: the CPU backend has no device plane."""
    if stand_in is not None:
        rx = re.compile(stand_in)
        return [ev for lines in planes.values()
                for name, ev in sorted(lines.items()) if rx.search(name)]
    devices = sorted(
        (int(m.group(1)), name) for name in planes
        if (m := DEVICE_PLANE.match(name)) and planes[name].get(OPS_LINE)
    )
    if n_devices is not None:
        devices = devices[:n_devices]
    return [planes[name][OPS_LINE] for _i, name in devices]


def reduce_trace(planes: Dict[str, Dict[str, List[Event]]],
                 n_devices: Optional[int] = None,
                 stand_in: Optional[str] = None) -> Dict[str, Any]:
    """Everything the layer metrics and `breakdown` read, averaged over
    the devices that ran an operation (the first `n_devices` by id)."""
    window = window_of(planes)
    op_lines = device_op_lines(planes, n_devices, stand_in)
    if not op_lines:
        raise ValueError(
            f"no device plane with an {OPS_LINE!r} line among "
            f"{sorted(planes)}: nothing ran on a device in the trace")
    if window is None:  # no markers (a trace not taken by the harness)
        window = (min(e[1] for ev in op_lines for e in ev),
                  max(e[2] for ev in op_lines for e in ev))
    host = HostActivity(host_events(planes))
    per_device = [reduce_device(ev, window, host) for ev in op_lines]
    n = len(per_device)
    ops: Dict[str, List[float]] = {}
    idle: Dict[str, float] = {}
    for d in per_device:
        for k, (sec, cnt) in d["ops"].items():
            rec = ops.setdefault(k, [0.0, 0.0])
            rec[0] += sec / n
            rec[1] += cnt / n
        for k, sec in d["idle_by_host_activity"].items():
            idle[k] = idle.get(k, 0.0) + sec / n
    classes: Dict[str, float] = {}
    for k, (sec, _cnt) in ops.items():
        classes[op_class(k)] = classes.get(op_class(k), 0.0) + sec
    mean = lambda key: sum(d[key] for d in per_device) / n  # noqa: E731
    return {
        "window_s": window[1] - window[0],
        "devices": n,
        "busy_s": mean("busy_s"),
        "ops": {k: (v[0], v[1]) for k, v in ops.items()},
        "op_class": classes,
        "collective_s": mean("collective_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "idle_by_host_activity": idle,
        "longest_gap_s": max(d["longest_gap_s"] for d in per_device),
        "host_spans": sorted(set(host.span_names)),
    }


def ops_matching(trace: Dict[str, Any], pattern: str) -> Tuple[float, float]:
    """(seconds, calls) per device of the operations whose name matches."""
    rx = re.compile(pattern)
    sec = cnt = 0.0
    for name, (s, c) in trace["ops"].items():
        if rx.search(name):
            sec += s
            cnt += c
    return sec, cnt


def breakdown(trace: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """The contract's `breakdown`: the device operations that took most
    time (by name) and the idle time by what the host was doing."""
    merged: Dict[str, float] = {}
    for k, v in trace["ops"].items():
        # `fusion.<n>` says nothing as a class; everything else (kernels,
        # copies, named fusions) reads better summed over its instances
        key = k if op_class(k) == "fusion" else op_class(k) + " (all)"
        merged[key] = merged.get(key, 0.0) + v[0]
    ops = sorted(merged.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(trace["idle_by_host_activity"].items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
