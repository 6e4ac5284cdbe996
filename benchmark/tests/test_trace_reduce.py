"""The reduction from a trace to numbers, against (1) a small recorded
trace: 40 ms of `small-train-1k` on the v5e around a report boundary
(PR 22's first chip run, cut by `benchmark/tools/trim_trace.py`; the
numbers below were read off it by hand with `tools/trace_dump.py` and a
calculator), and (2) a hand-made two-device trace with collectives."""
import os

import pytest

from benchmark import kernel_events
from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    return tr.load(os.path.join(HERE, "recorded_train_trace.txt"))


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == \
        [(0, 3), (5, 7)]
    assert tr.total([(0, 3), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]


def test_op_names_from_hlo_text():
    text = ('%transpose_jvp___.12 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}) '
            'custom-call(...), custom_call_target="tpu_custom_call", ...')
    assert tr.op_name(text) == "mosaic:transpose_jvp___.12"
    assert tr.op_name("%fusion.1922 = (f32[16,1023,50304]{2,1,0}) fusion(") \
        == "fusion.1922"
    assert tr.op_name("np.asarray(jax.Array)") == "np.asarray(jax.Array)"
    assert tr.op_class("fusion.1922") == "fusion"
    assert tr.op_class("mosaic:jvp__.3") == "mosaic:jvp__"
    assert tr.op_class("all-gather-start.4") == "all-gather-start"


def test_recorded_trace_window_busy_and_idle(recorded):
    assert tr.window_of(recorded) is not None
    r = tr.reduce_trace(recorded, n_devices=1)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.040, abs=1e-9)
    # 1732 operation events, one of them the `cond.54` that encloses
    # others: their durations sum to 37.75 ms, their union is 30.93 ms
    events = recorded["/device:TPU:0"]["XLA Ops"]
    assert len(events) == 1732
    assert sum(b - a for _n, a, b in events) == pytest.approx(37.748e-3, abs=2e-6)
    assert r["busy_s"] == pytest.approx(30.928e-3, abs=2e-6)
    assert "cond.54" not in r["ops"]
    # the report boundary: one gap of 9.06 ms while the host fetched the
    # window's metrics one array at a time
    assert r["longest_gap_s"] == pytest.approx(9.0604e-3, abs=1e-6)
    idle = r["idle_by_host_activity"]
    assert max(idle, key=idle.get) == "np.asarray(jax.Array)"
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-9)
    assert "bench.context.report" in r["host_spans"]


def test_recorded_trace_kernels_and_breakdown(recorded):
    r = tr.reduce_trace(recorded, n_devices=1)
    sec, calls = kernel_events.seconds_and_calls(r, "flash_forward")
    assert calls == 4 and sec == pytest.approx(2.5487e-3, abs=1e-6)
    assert kernel_events.seconds_and_calls(r, "flash_backward") == (0.0, 0.0)
    assert kernel_events.seconds_and_calls(r, "paged_attention") == (0.0, 0.0)
    b = tr.breakdown(r)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "copy (all)"
    assert b["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    assert b["device_ops"] == sorted(b["device_ops"], key=lambda kv: -kv[1])


def _plane(name, lines):
    """A plane of a text proto: {line: [(event, start_us, dur_us)]}."""
    names = sorted({e[0] for ev in lines.values() for e in ev})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ name: "{name}"']
    for n, i in ids.items():
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
    for k, (line, events) in enumerate(lines.items()):
        out.append(f'  lines {{ id: {k + 1} name: "{line}" timestamp_ns: 0')
        for n, start, dur in events:
            out.append(f"    events {{ metadata_id: {ids[n]} offset_ps: "
                       f"{int(start * 1e6)} duration_ps: {int(dur * 1e6)} }}")
        out.append("  }")
    out.append("}")
    return "\n".join(out)


@pytest.fixture()
def two_devices(tmp_path):
    """Window [100, 1100) us. Device 0: compute 100-400, all-gather
    350-600 (50 us under compute, 200 exposed... until fusion.2 at 500:
    exposed 400-500), fusion.2 500-900, idle 900-1100. Device 1: one
    all-reduce 100-1100 with nothing beside it."""
    path = tmp_path / "two.txt"
    path.write_text("\n".join([
        _plane("/device:TPU:0", {"XLA Ops": [
            ("fusion.1", 100, 300), ("all-gather-start.1", 350, 250),
            ("fusion.2", 500, 400)],
            "Steps": [("1", 100, 1000)]}),    # a line that is not read
        _plane("/device:TPU:1", {"XLA Ops": [("all-reduce.7", 100, 1000)]}),
        _plane("/host:CPU", {
            "python": [("bench.window_begin", 100, 0.001),
                       ("bench.window_end", 1100, 0.001),
                       ("bench.trainer.fit", 50, 2000),
                       ("bench.context.report", 880, 230)],
            "python#x": [("PjitFunction(train_step)", 905, 100)]}),
    ]))
    return tr.load(str(path))


def test_collectives_exposed_idle_and_the_mean_over_devices(two_devices):
    r = tr.reduce_trace(two_devices)
    us = 1e-6
    assert r["devices"] == 2 and r["window_s"] == pytest.approx(1000 * us)
    # device 0 busy 100-900 = 800 us, device 1 busy 1000 us
    assert r["busy_s"] == pytest.approx(900 * us)
    # device 0: collective 250 us, of which 400-500 is exposed; device 1:
    # 1000 us, all exposed
    assert r["collective_s"] == pytest.approx((250 + 1000) / 2 * us)
    assert r["collective_exposed_s"] == pytest.approx((100 + 1000) / 2 * us)
    # device 0's one gap (900-1100) lies inside both harness spans: the
    # innermost names it; halved by the mean over two devices
    assert r["idle_by_host_activity"] == {
        "bench.context.report": pytest.approx(100 * us)}
    one = tr.reduce_trace(two_devices, n_devices=1)
    assert one["devices"] == 1 and one["busy_s"] == pytest.approx(800 * us)


def test_a_trace_with_no_device_operation_is_refused():
    with pytest.raises(ValueError, match="nothing ran on a device"):
        tr.reduce_trace({"/host:CPU": {"python": [("x", 0.0, 1.0)]}})
