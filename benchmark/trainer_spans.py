"""The trainer's report boundary, read three ways: the device's idle time
inside a host span of `Trainer.fit` (`scope_reduce`'s `span_idle_s`),
a number the trainer puts in every `profiling` report (its own clock,
`Timeline.pc`, over every boundary of the window and not only the
traced seconds), and what of the device's idle time no trainer span
covers at all.

`unspanned`: per device, the idle gaps inside the harness's window
(the complement of the device's operations, control flow left out, as
in `trace_reduce`) less the union of every host span whose name starts
with `scopes.json`'s `span_prefix`. Spans are merged before they are
subtracted, so a gap inside `report` inside `boundary` counts once; a
span the window's edge cuts covers its part inside the window.
"""
from __future__ import annotations

import functools
import os
from typing import Any, List, Optional, Sequence, Tuple

from benchmark import scope_reduce
from benchmark import trace_reduce as tr


def span_idle_ms(run: Any, span: str) -> Optional[float]:
    """Device-idle ms inside the trainer's span `<span_prefix><span>`
    over the number of such spans in the traced window; `None` where
    the trace holds none."""
    r = scope_reduce.for_run(run)
    if r is None:
        return None
    name = scope_reduce.names()["span_prefix"] + span
    n = r["span_count"].get(name, 0)
    return 1e3 * r["span_idle_s"][name] / n if n else None


def report_mean_ms(run: Any, key: str) -> Optional[float]:
    """Mean, in ms, of `key` (seconds) over the window's `profiling`
    reports that carry it; `None` where none does."""
    r = run.records
    if r.get("kind") != "train":
        return None
    values = [t[key] for t in r.get("timelines") or () if key in t]
    return 1e3 * sum(values) / len(values) if values else None


def unspanned(devices: Sequence[Sequence[tr.Event]],
              host: Sequence[tr.Event]) -> Optional[Tuple[float, float]]:
    """(idle seconds in no trainer span, idle seconds), summed over the
    devices, inside the window the host's markers give (else the
    devices' first and last operation); `None` where no device ran an
    operation or the host holds no trainer span."""
    prefix = scope_reduce.names()["span_prefix"]
    spans = [(a, b) for name, a, b in host if name.startswith(prefix)]
    if not devices or not spans:
        return None
    window = tr.window_of({"host": {"all": list(host)}}) or (
        min(e[1] for ev in devices for e in ev),
        max(e[2] for ev in devices for e in ev))
    lo, hi = window
    cover = tr.union([(max(a, lo), min(b, hi)) for a, b in spans])
    idle = bare = 0.0
    for events in devices:
        work = tr.union([
            (max(a, lo), min(b, hi)) for name, a, b in events
            if b > lo and a < hi and not tr.CONTROL_FLOW.match(name)])
        gaps = tr.subtract([window], work)
        idle += tr.total(gaps)
        bare += tr.total(tr.subtract(gaps, cover))
    return bare, idle


@functools.lru_cache(maxsize=2)
def _unspanned_file(path: str, _mtime: float, n_devices: int
                    ) -> Optional[Tuple[float, float]]:
    planes = tr.load(path)
    host: List[tr.Event] = [
        e for plane, lines in planes.items() if plane.startswith("/host:")
        for events in lines.values() for e in events]
    return unspanned(tr.device_op_lines(planes, n_devices), host)


def unspanned_share(run: Any) -> Optional[float]:
    """Per cent of the traced window's device idle time in no trainer
    span; `None` off a trace, or where `unspanned` is."""
    if run.trace is None:
        return None
    path = scope_reduce.newest_xplane()
    if path is None:
        return None
    r = _unspanned_file(path, os.path.getmtime(path), int(run.chips))
    if r is None or r[1] <= 0:
        return None
    return 100.0 * r[0] / r[1]
