"""Device time by the inner scopes of `benchmark/mla_scopes.json`, from
the trace the harness wrote: `lm_scope_reduce`'s reduction with the
scope list as data (that module reads `lm_scopes.json` alone).

An operation counts under every one of the scopes that is a whole
component of its name stack once transformation wrappers are peeled.
Control-flow operations and collectives are left out, as in
`scope_reduce`. `None` where there is no trace, or the program opens
none of these scopes (a parent from before them: the readers then leave
their metric out).
"""
from __future__ import annotations

import functools
import json
import os
from typing import Any, Dict, Iterable, Optional, Set

from benchmark import (
    flops_bytes,
    lm_scope_reduce,
    mla_flops_bytes,
    scope_reduce,
    trace_reduce,
)

HERE = os.path.dirname(os.path.abspath(__file__))


@functools.lru_cache(maxsize=1)
def inner_scopes() -> tuple:
    with open(os.path.join(HERE, "mla_scopes.json")) as f:
        return tuple(json.load(f)["inner_scopes"])


def scopes_of(name_stack: str, scopes: Iterable[str]) -> Set[str]:
    found = set()
    for part in name_stack.split("/"):
        while (m := scope_reduce.WRAPPED.match(part)):
            part = m.group(1)
        if part in scopes:
            found.add(part)
    return found


def reduce(devices, host, n_devices: int,
           scopes: Iterable[str]) -> Optional[Dict[str, Any]]:
    """{"window_s", "inner_s": {scope: seconds, mean over devices}} of
    `scope_reduce.load`'s events."""
    devices = list(devices)[:n_devices]
    if not devices:
        return None
    scopes = tuple(scopes)
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
            for scope in scopes_of(stack, scopes):
                inner[scope] = inner.get(scope, 0.0) + (
                    min(b, hi) - max(a, lo)) / len(devices)
    return {"window_s": hi - lo, "inner_s": inner}


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, _mtime: float, n_devices: int):
    devices, host = scope_reduce.load(path)
    return reduce(devices, host, n_devices, inner_scopes())


def inner_share(run: Any, scope: str) -> Optional[float]:
    """Per cent of the traced window the device spent under `scope`;
    `None` where the trace shows nothing under it."""
    if run.trace is None:
        return None
    path = scope_reduce.newest_xplane()
    if path is None:
        return None
    r = _reduce_file(path, os.path.getmtime(path), int(run.chips))
    if not r or r["window_s"] <= 0 or r["inner_s"].get(scope, 0.0) <= 0:
        return None
    return 100.0 * r["inner_s"][scope] / r["window_s"]


def flash_roofline(run: Any, which: str) -> Optional[float]:
    """The blocked flash kernels' share of their roofline, `which` =
    `fwd` or `bwd`: the least time the chip could take for every such
    call in the trace (`flops_bytes.flash_forward` / `flash_backward` at
    the configuration's heads, sequence and query/key width) over the
    time those calls took (`scope_reduce`'s `flash_s`). Calls are counted
    as attention layers (the MTP module's block included) x the steps
    traced, not as kernel events, as `lm_flash_*_roofline` count them.
    `None` where no flash kernel ran or the configuration is of another
    family."""
    r = scope_reduce.for_run(run)
    if (r is None or run.records["kind"] != "train"
            or "kv_lora_rank" not in run.config):
        return None
    measured = r["flash_s"][which]
    steps = lm_scope_reduce.steps_traced(run, r["window_s"])
    if measured <= 0 or steps is None:
        return None
    m = mla_flops_bytes.dims(run.config)
    rows = int(run.traffic["global_batch"]) // run.chips
    seq = int(run.traffic["seq_len"])
    needs = {"fwd": flops_bytes.flash_forward,
             "bwd": flops_bytes.flash_backward}[which]
    least = flops_bytes.roofline_seconds(
        *needs(rows, m["heads"], seq, seq, m["qk_dim"]), run.peaks)[0]
    return 100.0 * least * m["attn_layers"] * steps / measured
