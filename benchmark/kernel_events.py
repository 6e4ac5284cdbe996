"""Which trace events are which kernel.

The program gives its Pallas kernels no stable name yet (no `name=` on
a `pallas_call`, no `named_scope`: the `tracing` issue), so the
reduction finds them by the names the trace prints today. One data file
a kernel, `benchmark/kernels/<kernel>.json`: `pattern` (a regular
expression over the trace's operation names) and `events` (how many
trace events one call of the kernel makes). A PR that adds a kernel adds
a file.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def kernel(name: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, "kernels", name + ".json")) as f:
        return json.load(f)


def seconds_and_calls(trace: Dict[str, Any], name: str) -> Tuple[float, float]:
    """(seconds, calls) per device of a kernel in the traced window."""
    k = kernel(name)
    sec, events = trace_reduce.ops_matching(trace, k["pattern"])
    return sec, events / int(k.get("events", 1))
