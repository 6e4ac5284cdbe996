"""Device time by INNER scope (`benchmark/lm_scopes.json`), from the trace
the harness wrote: `scope_reduce`'s events and window, read against the
scopes some language models open inside `attn` and `mlp`.

An operation counts under every inner scope that is a whole component
of its name stack once transformation wrappers are peeled (`gdn_scan`
lies inside `gdn`, so the chunked rule's operations count under both),
and a kernel the compiler names itself (the grouped matmul's) under the
scope `lm_scopes.json` gives its name.
Control-flow operations (the scan's `while` itself) and collectives are
left out, as in `scope_reduce`. `None` where there is no trace, or the
program opens none of these scopes (a parent from before them: the
readers then leave their metric out).
"""
from __future__ import annotations

import functools
import json
import os
import re
from typing import Any, Dict, List, Optional, Set, Tuple

from benchmark import scope_reduce, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@functools.lru_cache(maxsize=1)
def _data() -> Dict[str, Any]:
    with open(os.path.join(HERE, "lm_scopes.json")) as f:
        return json.load(f)


def inner_scopes() -> tuple:
    return tuple(_data()["inner_scopes"])


@functools.lru_cache(maxsize=1)
def kernels() -> List[Tuple[str, "re.Pattern"]]:
    """(inner scope, pattern over operation names) of the kernels that
    carry no name stack (`lm_scopes.json`, `kernels`)."""
    return [(scope, re.compile(rx)) for scope, rx in _data()["kernels"].items()]


def scopes_of(name_stack: str) -> Set[str]:
    found = set()
    for part in name_stack.split("/"):
        while (m := scope_reduce.WRAPPED.match(part)):
            part = m.group(1)
        if part in inner_scopes():
            found.add(part)
    return found


def reduce(devices, host, n_devices: int) -> Optional[Dict[str, Any]]:
    devices = list(devices)[:n_devices]
    if not devices:
        return None
    window = trace_reduce.window_of({"host": {"all": list(host)}})
    if window is None:
        window = (min(e[2] for ev in devices for e in ev),
                  max(e[3] for ev in devices for e in ev))
    lo, hi = window
    inner: Dict[str, float] = {}
    for events in devices:
        for op, stack, a, b in events:
            if (b <= lo or a >= hi or trace_reduce.CONTROL_FLOW.match(op)
                    or trace_reduce.COLLECTIVE.match(op)):
                continue
            found = scopes_of(stack) | {
                scope for scope, rx in kernels() if rx.search(op)}
            for scope in found:
                inner[scope] = inner.get(scope, 0.0) + (
                    min(b, hi) - max(a, lo)) / len(devices)
    return {"window_s": hi - lo, "inner_s": inner}


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, _mtime: float, n_devices: int):
    devices, host = scope_reduce.load(path)
    return reduce(devices, host, n_devices)


def for_run(run: Any) -> Optional[Dict[str, Any]]:
    if run.trace is None:
        return None
    path = scope_reduce.newest_xplane()
    if path is None:
        return None
    r = _reduce_file(path, os.path.getmtime(path), int(run.chips))
    return r if r and r["inner_s"] and r["window_s"] > 0 else None


def inner_share(run: Any, scope: str) -> Optional[float]:
    """Per cent of the traced window the device spent under `scope`."""
    r = for_run(run)
    if r is None:
        return None
    return 100.0 * r["inner_s"].get(scope, 0.0) / r["window_s"]


def steps_traced(run: Any, window_s: float) -> Optional[float]:
    """Train steps that fit the traced window at the run's own rate (the
    device is busy throughout a training window, so steps follow time)."""
    rec = run.records
    if rec.get("kind") != "train" or rec.get("steps", 0) <= 0:
        return None
    return window_s * rec["steps"] / rec["wall_s"]


def roofline_share(run: Any, scope: str, needs_a_step) -> Optional[float]:
    """100 x (the least seconds a step's `needs_a_step` = (operations,
    bytes) take on this chip x steps traced) / (seconds under `scope`)."""
    from benchmark import flops_bytes

    r = for_run(run)
    if r is None or r["inner_s"].get(scope, 0.0) <= 0:
        return None
    steps = steps_traced(run, r["window_s"])
    if steps is None:
        return None
    least = flops_bytes.roofline_seconds(*needs_a_step, run.peaks)[0]
    return 100.0 * least * steps / r["inner_s"][scope]
