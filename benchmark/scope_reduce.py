"""Device time by part of the program, from the trace the harness wrote.

`trace_reduce.load` keeps an event's operation name and drops the rest.
This module keeps what it drops: each device event's name stack (jax's
`op_name`, e.g. `jit(train_step)/transpose(jvp(attn))/bshk,hkd->bsd/
dot_general`), and reads it against the names the program gives its work
(`benchmark/scopes.json`: the `jax.named_scope`s of a train step and the
prefix of the trainer's host spans; the benchmark's own copy, held equal
to the program's by `tests/test_step_scopes.py`).

Where the name stack is (looked up on a chip trace with
`tools/trace_dump.py`, PR 24): not in the event's name (the HLO text is
printed without its `metadata={op_name=...}`) and not among the event's
own stats, but in the stat `tf_op` of the event's METADATA record
(`XEventMetadata.stats`, beside `hlo_category`, `flops`, `source`),
which `jax.profiler.ProfileData` does not hand out. So `name_stacks`
reads those few fields off the file's protobuf wire format itself
(`tsl/profiler/protobuf/xplane.proto`; field numbers below), and events
are joined to them by name, which is the instruction's text and unique.

Which trace: `Run` does not carry the trace's directory and `run.py` may
not be edited by the PR that added this file, so `for_run` takes the
newest `.xplane.pb` under `<checkout>/.cache/benchmark/trace/*/`, which
is the one the harness wrote a moment ago (it empties the cell's
directory before every traced run).

What is computed, inside the harness's window (`bench.window_begin` to
`bench.window_end`), per device and then averaged over the devices used:

- `scope_s`: seconds of operations by scope. An operation's scope is the
  one name of `scopes` that is a WHOLE component of its name stack, once
  transformation wrappers are peeled (`jvp(attn)`, `transpose(jvp(mlp))`;
  `checkpoint` and `rematted_computation` are components of their own),
  so `_moe_mlp` is not `mlp`, and a forward recomputed in the backward
  counts under its scope. No such component: `unscoped`. A fusion takes
  the scope of the instruction the compiler kept its metadata from.
  Control-flow operations (`while`, `conditional`) are left out exactly
  as `trace_reduce` leaves them out; collective operations are left out
  of every scope (they overlap compute, and have `collective_exposed`).
  The flash-attention kernels carry no scope: XLA names a Pallas
  kernel's operation after the innermost component of its name stack,
  `kernels/flash_*.json` (`flash_time_share`, `flash_roofline`) find the
  kernels by the names they have under none, and so the program keeps
  its `attn` scope off the attention call. `scopes.json` says under
  which scope those kernels are counted all the same (`flash`);
  the layout copies between the projections and the kernel stay
  `unscoped`, with a name stack that ends in `jvp()/transpose` or
  `transpose(jvp())/reshape`.
- `scope_ops`: within each scope, seconds by operation class (`scope_s`
  is its sum).
- `span_idle_s`, `span_count`: for every host span `dtpu.trainer.*` that
  lies wholly inside the window, the time inside it in which no
  operation ran on the device, and how many such spans there were.
- `scoped`: whether any operation's name stack carried a scope at all (a
  program from before the scopes carries none, and the share readers
  return `None`).
- `flash_s`, `flash_calls`: seconds and calls of the flash kernels by pass,
  `fwd` or `bwd`. On one chip the two have names of their own (`jvp__`,
  `transpose_jvp___`); inside `shard_map` on a mesh both are
  `shard_map`, and what tells them apart is the name stack: an operation
  of the backward pass has a component wrapped in `transpose(...)` and
  none that is `rematted_computation` (a forward that remat repeats
  inside the backward is a forward). The split backward of long
  sequences (a dq and a dk/dv kernel; in no cell) would count as two
  calls, as it does in `kernels/flash_backward.json`.
"""
from __future__ import annotations

import functools
import glob
import json
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
UNSCOPED = "unscoped"
#: The stat of an event's metadata record that holds jax's name stack.
NAME_STACK_STAT = "tf_op"
WRAPPED = re.compile(r"^\w+\((.*)\)$")
REMAT = "rematted_computation"


@functools.lru_cache(maxsize=1)
def names() -> Dict[str, Any]:
    with open(os.path.join(HERE, "scopes.json")) as f:
        return json.load(f)


@functools.lru_cache(maxsize=1)
def flash_kernels() -> List[Tuple[re.Pattern, int]]:
    """(pattern over operation names, events a call) of the flash
    kernels: the `kernels/<kernel>.json` that `scopes.json` lists."""
    out = []
    for kernel in names()["flash"]["kernels"]:
        with open(os.path.join(HERE, "kernels", kernel + ".json")) as f:
            k = json.load(f)
        out.append((re.compile(k["pattern"]), int(k.get("events", 1))))
    return out


def pass_of(name_stack: str) -> str:
    """`bwd` for an operation of the backward pass, else `fwd` (see the
    module docstring)."""
    parts = name_stack.split("/")
    if REMAT in parts or not any(p.startswith("transpose(") for p in parts):
        return "fwd"
    return "bwd"


def scope_of(name_stack: str, scopes: Sequence[str]) -> Optional[str]:
    """The outermost component of the name stack that is, once its
    transformation wrappers are peeled, one of `scopes`."""
    for part in name_stack.split("/"):
        while (m := WRAPPED.match(part)):
            part = m.group(1)
        if part in scopes:
            return part
    return None


# -- the protobuf wire format, as far as the name stacks need it ------------
def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message: a varint as an int, a
    length-delimited field as a memoryview, fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif kind == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")


def _map_value(entry: memoryview) -> memoryview:
    """The value of one `map<int64, Message>` entry (key = 1, value = 2)."""
    return next(v for f, v in _fields(entry) if f == 2)


def name_stacks(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{plane name: {event metadata name: the name stack in that
    metadata record's stats}}, off the serialized `XSpace`: planes = 1; XPlane
    name = 2, event_metadata = 4, stat_metadata = 5; XEventMetadata
    name = 2, stats = 5; XStatMetadata id = 1, name = 2; XStat
    metadata_id = 1, str_value = 5, ref_value = 7 (a string kept once,
    as a stat metadata's name)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        plane_name = ""
        stat_names: Dict[int, str] = {}
        records: List[Tuple[str, List[Tuple[int, Any]]]] = []
        for f, v in _fields(plane):
            if f == 2:
                plane_name = bytes(v).decode()
            elif f == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
            elif f == 4:
                name, stats = "", []
                for f2, v2 in _fields(_map_value(v)):
                    if f2 == 2:
                        name = bytes(v2).decode()
                    elif f2 == 5:
                        stats.append(dict(_fields(v2)))
                records.append((name, stats))
        found: Dict[str, str] = {}
        for name, stats in records:
            for st in stats:
                if stat_names.get(st.get(1)) != NAME_STACK_STAT:
                    continue
                if 5 in st:
                    found[name] = bytes(st[5]).decode()
                elif 7 in st:
                    found[name] = stat_names.get(st[7], "")
        if found:
            out[plane_name] = found
    return out


ScopedEvent = Tuple[str, str, float, float]   # (op, name stack, start, end)


def load(path: str) -> Tuple[List[List[ScopedEvent]],
                             List[trace_reduce.Event]]:
    """(one list of operation events per device, by device id; the host
    planes' events). A `.txt` path is a text proto, as in `trace_reduce`."""
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            xspace = ProfileData.text_proto_to_serialized_xspace(f.read())
    else:
        with open(path, "rb") as f:
            xspace = f.read()
    stacks = name_stacks(xspace)
    devices: List[Tuple[int, List[ScopedEvent]]] = []
    host: List[trace_reduce.Event] = []
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not m and not plane.name.startswith("/host:"):
            continue
        stack_of = stacks.get(plane.name, {})
        for line in plane.lines:
            if not m:
                host.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            elif line.name == trace_reduce.OPS_LINE:
                short: Dict[str, str] = {}      # the texts are long
                events: List[ScopedEvent] = []
                for e in line.events:
                    if e.name not in short:
                        short[e.name] = trace_reduce.op_name(e.name)
                    events.append((
                        short[e.name], stack_of.get(e.name, ""),
                        e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9))
                if events:
                    devices.append((int(m.group(1)), events))
    return [ev for _i, ev in sorted(devices, key=lambda d: d[0])], host


def reduce_device(events: Sequence[ScopedEvent],
                  window: trace_reduce.Interval,
                  spans: Sequence[trace_reduce.Event],
                  scopes: Sequence[str]) -> Dict[str, Any]:
    lo, hi = window
    flash_scope = names()["flash"]["scope"]
    scope_ops: Dict[str, Dict[str, float]] = {}
    flash_s = {"fwd": 0.0, "bwd": 0.0}
    flash_calls = {"fwd": 0.0, "bwd": 0.0}
    scoped = False
    work: List[trace_reduce.Interval] = []
    for op, stack, a, b in events:
        if b <= lo or a >= hi or trace_reduce.CONTROL_FLOW.match(op):
            continue
        a, b = max(a, lo), min(b, hi)
        work.append((a, b))
        if trace_reduce.COLLECTIVE.match(op):
            continue
        scope = scope_of(stack, scopes)
        scoped = scoped or scope is not None
        if op.startswith("mosaic:"):
            for pattern, per_call in flash_kernels():
                if pattern.search(op):
                    scope = scope or flash_scope
                    flash_s[pass_of(stack)] += b - a
                    flash_calls[pass_of(stack)] += 1.0 / per_call
                    break
        by_op = scope_ops.setdefault(scope or UNSCOPED, {})
        cls = trace_reduce.op_class(op)
        by_op[cls] = by_op.get(cls, 0.0) + b - a
    gaps = np.asarray(
        trace_reduce.subtract([(lo, hi)], trace_reduce.union(work)),
        np.float64).reshape(-1, 2)
    span_idle: Dict[str, float] = {}
    span_count: Dict[str, int] = {}
    for name, a, b in spans:
        if a < lo or b > hi:
            continue      # cut by the window's edge: not a whole span
        span_count[name] = span_count.get(name, 0) + 1
        span_idle[name] = span_idle.get(name, 0.0) + float(np.clip(
            np.minimum(gaps[:, 1], b) - np.maximum(gaps[:, 0], a),
            0.0, None).sum())
    return {"scope_ops": scope_ops, "scoped": scoped, "flash_s": flash_s,
            "flash_calls": flash_calls, "span_idle_s": span_idle,
            "span_count": span_count}


def reduce(devices: Sequence[Sequence[ScopedEvent]],
           host: Sequence[trace_reduce.Event],
           n_devices: Optional[int] = None) -> Optional[Dict[str, Any]]:
    """The module docstring's numbers, or `None` where no device ran an
    operation in the trace (a rehearsal off the chip)."""
    devices = list(devices)[:n_devices]
    if not devices:
        return None
    data = names()
    window = trace_reduce.window_of({"host": {"all": list(host)}})
    if window is None:
        window = (min(e[2] for ev in devices for e in ev),
                  max(e[3] for ev in devices for e in ev))
    spans = [e for e in host if e[0].startswith(data["span_prefix"])]
    per_device = [reduce_device(ev, window, spans, data["scopes"])
                  for ev in devices]
    n = len(per_device)

    def mean(tables: Sequence[Dict[str, float]]) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for table in tables:
            for k, v in table.items():
                out[k] = out.get(k, 0.0) + v / n
        return out

    scope_ops = {
        scope: mean([d["scope_ops"].get(scope, {}) for d in per_device])
        for scope in {s for d in per_device for s in d["scope_ops"]}}
    return {
        "window_s": window[1] - window[0],
        "devices": n,
        "scoped": any(d["scoped"] for d in per_device),
        "scope_s": {k: sum(v.values()) for k, v in scope_ops.items()},
        "scope_ops": scope_ops,
        "flash_s": mean([d["flash_s"] for d in per_device]),
        "flash_calls": mean([d["flash_calls"] for d in per_device]),
        "span_idle_s": mean([d["span_idle_s"] for d in per_device]),
        "span_count": mean([d["span_count"] for d in per_device]),
    }


def newest_xplane() -> Optional[str]:
    found = glob.glob(os.path.join(
        ROOT, ".cache", "benchmark", "trace", "*", "plugins", "profile",
        "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, _mtime: float,
                 n_devices: int) -> Optional[Dict[str, Any]]:
    devices, host = load(path)
    return reduce(devices, host, n_devices)


def for_run(run: Any) -> Optional[Dict[str, Any]]:
    """The reduction of the trace this run's harness wrote (one load for
    all the readers of a run), or `None`: not a traced run, no trace
    file, or no device plane in it."""
    if run.trace is None:
        return None
    path = newest_xplane()
    if path is None:
        return None
    return _reduce_file(path, os.path.getmtime(path), int(run.chips))


def scope_share(run: Any, scope: str) -> Optional[float]:
    """Per cent of the traced window the device spent under `scope`;
    `None` where the program opens no scope at all."""
    r = for_run(run)
    if r is None or not r["scoped"] or r["window_s"] <= 0:
        return None
    return 100.0 * r["scope_s"].get(scope, 0.0) / r["window_s"]
