"""The four readers of the trainer's report boundary and collector
(`boundary_idle_ms`, `boundary_host_ms`, `gc_pause_ms`,
`unspanned_idle_share`) and `trainer_spans` under them, on hand-made
host spans and device events, and on the recorded cut of a chip trace
(`recorded_scoped_trace.txt`, from a program before the boundary span:
the parent's case)."""
import os
import types

import pytest

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark import trainer_spans as ts
from benchmark.run import reader

HERE = os.path.dirname(os.path.abspath(__file__))
CUT = os.path.join(HERE, "recorded_scoped_trace.txt")
MARKERS = [(tr.WINDOW_BEGIN, 0.0, 0.0), (tr.WINDOW_END, 10.0, 10.0)]


def _read(metric, run):
    return reader("layer_metrics", metric).read(run)


def _run(records=None, traced=True, chips=1):
    return types.SimpleNamespace(
        chips=chips, records=records or {"kind": "train", "timelines": []},
        trace={} if traced else None)


def test_nested_spans_are_counted_once():
    # busy 0-2 and 6-10: one gap of 4 s; `report` and `data_wait` inside
    # `boundary`, which covers the gap from 1.5 to 5
    device = [("fusion.1", 0.0, 2.0), ("fusion.2", 6.0, 10.0),
              ("while.1", 0.0, 10.0)]           # control flow: not busy
    host = MARKERS + [("dtpu.trainer.boundary", 1.5, 5.0),
                      ("dtpu.trainer.report", 2.0, 3.0),
                      ("dtpu.trainer.report.sync", 2.0, 2.5),
                      ("dtpu.trainer.data_wait", 4.0, 4.5),
                      ("bench.context.report", 5.0, 6.0)]  # not the trainer's
    assert ts.unspanned([device], host) == (pytest.approx(1.0), 4.0)
    # two devices: the seconds add up, so the share is over both
    other = [("fusion.1", 0.0, 5.5), ("fusion.2", 6.0, 10.0)]
    assert ts.unspanned([device, other], host) == (
        pytest.approx(1.5), pytest.approx(4.5))


def test_a_span_cut_by_the_windows_edge_covers_its_part_inside():
    device = [("fusion.1", 1.0, 9.0), ("fusion.2", 9.5, 12.0)]
    host = MARKERS + [("dtpu.trainer.checkpoint", -1.0, 0.5),
                      ("dtpu.trainer.gc", 9.25, 11.0)]
    # gaps 0-1 and 9-9.5; covered 0-0.5 and 9.25-9.5
    bare, idle = ts.unspanned([device], host)
    assert (bare, idle) == (pytest.approx(0.75), pytest.approx(1.5))
    # without the harness's markers the window is the device's work
    assert ts.unspanned([device], host[2:]) == (
        pytest.approx(0.25), pytest.approx(0.5))


def test_nothing_to_read_is_none():
    device = [("fusion.1", 0.0, 2.0)]
    assert ts.unspanned([device], MARKERS) is None        # no trainer span
    assert ts.unspanned([], MARKERS + [("dtpu.trainer.report", 1, 2)]) is None
    assert _read("unspanned_idle_share", _run(traced=False)) is None
    assert _read("boundary_idle_ms", _run(traced=False)) is None
    # profiling reports without the keys (a program from before them)
    old = {"kind": "train", "timelines": [{"step": 4, "window_s": 1.0}]}
    assert _read("boundary_host_ms", _run(old)) is None
    assert _read("gc_pause_ms", _run(old)) is None
    assert _read("boundary_host_ms", _run({"kind": "serve"})) is None


def test_the_report_readers_take_the_mean_of_the_windows_that_carry_it():
    timelines = [{"step": 2, "window_s": 1.0, "gc_s": 0.0,
                  "gc_collections": 0.0},
                 {"step": 4, "boundary_s": 0.006, "gc_s": 0.002,
                  "gc_collections": 1.0},
                 {"step": 6, "boundary_s": 0.004, "gc_s": 0.001,
                  "gc_collections": 1.0}]
    run = _run({"kind": "train", "timelines": timelines})
    assert _read("boundary_host_ms", run) == pytest.approx(5.0)
    assert _read("gc_pause_ms", run) == pytest.approx(1.0)


def test_boundary_idle_reads_its_span_and_not_the_ones_inside(monkeypatch):
    device = [("fusion.1", "", 0.0, 2.0), ("fusion.2", "", 6.0, 10.0)]
    host = MARKERS + [("dtpu.trainer.boundary", 1.0, 7.0),
                      ("dtpu.trainer.report", 2.0, 3.0),
                      ("dtpu.trainer.boundary", 9.0, 11.0)]  # cut: not counted
    reduced = sr.reduce([device], host, 1)
    monkeypatch.setattr(sr, "for_run", lambda run: reduced)
    assert _read("boundary_idle_ms", _run()) == pytest.approx(4e3)
    assert _read("report_idle_ms", _run()) == pytest.approx(1e3)
    without = sr.reduce([device], MARKERS + host[3:4], 1)
    monkeypatch.setattr(sr, "for_run", lambda run: without)
    assert _read("boundary_idle_ms", _run()) is None


def test_on_a_trace_from_before_the_boundary(monkeypatch):
    """The recorded cut has the phases' spans and no boundary: the
    parent's case. `boundary_idle_ms` is `None` there, and
    `unspanned_idle_share` reads."""
    monkeypatch.setattr(sr, "newest_xplane", lambda: CUT)
    sr._reduce_file.cache_clear()
    ts._unspanned_file.cache_clear()
    run = _run()
    assert _read("boundary_idle_ms", run) is None
    share = _read("unspanned_idle_share", run)
    planes = tr.load(CUT)
    old = tr.reduce_trace(planes, n_devices=1)
    idle = old["window_s"] - old["busy_s"]
    host = [e for p, lines in planes.items() if p.startswith("/host:")
            for events in lines.values() for e in events]
    spans = tr.union([(a, b) for n, a, b in host
                      if n.startswith("dtpu.trainer.")])
    lo, hi = tr.window_of(planes)
    work = tr.union([(max(a, lo), min(b, hi))
                     for n, a, b in tr.device_op_lines(planes, 1)[0]
                     if b > lo and a < hi and not tr.CONTROL_FLOW.match(n)])
    covered = tr.total(tr.subtract(
        [(max(a, lo), min(b, hi)) for a, b in spans if b > lo and a < hi],
        work))
    assert share == pytest.approx(100.0 * (idle - covered) / idle)
    assert 0.0 < share < 100.0
