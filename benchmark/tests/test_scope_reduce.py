"""`scope_reduce` against (1) a cut of the builder's own chip trace: 160
ms of `small-train-1k` on the v5e around a report boundary, one whole
step before it and the head of the next (PR 24's first chip run, cut by
`benchmark/tools/trim_scoped_trace.py ... 1.2 0.16 400`: operations of
400 us and more, the program's and the harness's spans clipped to the
cut; the numbers below were read off the text proto with the protobuf
library and plain loops, not with the code under test), and (2)
hand-made events for what one chip's trace cannot show: two devices, a
collective, a program without scopes.

The trace is from before `flush_report` stopped opening a span with
nothing pending, so it holds a second, empty `dtpu.trainer.report`. It
is also from a tree that named the flash kernels (`dtpu_flash_fwd`,
`dtpu_flash_bwd`, under the `attn` scope); the names and name stacks of
those 29 events were then set, in the text, to what the final tree gives
them (`jvp__` under `jit(train_step)/jvp()/pallas_call`,
`transpose_jvp___` under `.../transpose(jvp())/...`: PR 24's last
one-chip trace and the parent's, `PERF.md`), times untouched. No other
operation of `attn` in the cut is 400 us long."""
import os
import types

import pytest

from benchmark import kernel_events
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.run import load_json

HERE = os.path.dirname(os.path.abspath(__file__))
CUT = os.path.join(HERE, "recorded_scoped_trace.txt")
SCOPES = sr.names()["scopes"]   # `scope_of` itself: tests/test_step_scopes.py


@pytest.fixture(scope="module")
def cut():
    devices, host = sr.load(CUT)
    return devices, host, sr.reduce(devices, host, 1)


@pytest.fixture(scope="module")
def old(cut):
    """`trace_reduce`'s reduction of the same file."""
    return tr.reduce_trace(tr.load(CUT), n_devices=1)


def test_name_stacks_come_from_the_metadata_records_stats(cut):
    devices, _host, _r = cut
    assert len(devices) == 1 and len(devices[0]) == 127
    stacks = {op: stack for op, stack, _a, _b in devices[0]}
    assert stacks["mosaic:jvp__.12"] == "jit(train_step)/jvp()/pallas_call:"
    assert stacks["mosaic:transpose_jvp___.12"] == \
        "jit(train_step)/transpose(jvp())/pallas_call:"
    assert stacks["fusion.1922"] == \
        "jit(train_step)/jvp(head_loss)/bsd,dv->bsv/dot_general:"
    # the logits' backward pass: the compiler kept no metadata for it
    assert stacks["fusion.100"] == ""
    assert stacks["cond.54"] == ""


def test_seconds_by_scope(cut):
    _devices, _host, r = cut
    assert r["devices"] == 1 and r["scoped"] is True
    assert r["window_s"] == pytest.approx(0.160, abs=1e-9)
    # events and milliseconds by scope, read off the text proto (clipped
    # to the cut, which one `mlp` fusion crosses): attn 29 (17 + 12 flash
    # kernels, which carry no scope and are counted under `attn` as
    # `scopes.json` says; nothing else of attn is 400 us long), mlp 81,
    # head_loss 5, optimizer 6, embed 1; the reader rounds to nanoseconds
    want = {"attn": 29.50538789, "mlp": 37.2871725,
            "head_loss": 25.652361328, "optimizer": 5.036216094,
            "embed": 0.782740078}
    for scope, ms in want.items():
        assert r["scope_s"][scope] == pytest.approx(ms * 1e-3, abs=1e-7), scope
    # five events carry no name stack: `fusion.100` (the logits' backward
    # pass), two copies, and two `cond.54`, which is control flow and left
    # out as in trace_reduce
    assert r["scope_s"][sr.UNSCOPED] == pytest.approx(6.041307578e-3, abs=1e-7)
    assert set(r["scope_s"]) == set(SCOPES) | {sr.UNSCOPED}


def test_shares_sum_with_idle_to_the_window(cut, old):
    """No collective and no two operations at once on one chip: the
    scopes' seconds are the busy time, and with idle they are the
    window."""
    _devices, _host, r = cut
    assert sum(r["scope_s"].values()) == pytest.approx(old["busy_s"], abs=1e-9)
    idle = old["window_s"] - old["busy_s"]
    assert sum(r["scope_s"].values()) + idle == pytest.approx(
        r["window_s"], abs=1e-9)


def test_kernels_are_counted_once_and_under_their_scope(cut, old):
    _devices, _host, r = cut
    # by pass, as PR 22's kernel files find them by name on one chip
    fwd = kernel_events.seconds_and_calls(old, "flash_forward")
    bwd = kernel_events.seconds_and_calls(old, "flash_backward")
    assert fwd == (pytest.approx(13.137187968e-3, abs=2e-8), 17)
    assert bwd == (pytest.approx(16.368199922e-3, abs=2e-8), 12)
    assert kernel_events.seconds_and_calls(old, "flash_sharded") == (0, 0)
    assert (r["flash_s"]["fwd"], r["flash_calls"]["fwd"]) == (
        pytest.approx(fwd[0], abs=1e-9), 17)
    assert (r["flash_s"]["bwd"], r["flash_calls"]["bwd"]) == (
        pytest.approx(bwd[0], abs=1e-9), 12)
    by_op = r["scope_ops"]["attn"]
    assert by_op["mosaic:jvp__"] == pytest.approx(fwd[0], abs=1e-9)
    assert by_op["mosaic:transpose_jvp___"] == pytest.approx(bwd[0], abs=1e-9)
    assert sum(by_op.values()) == pytest.approx(r["scope_s"]["attn"], abs=1e-9)


def test_pass_of_reads_the_name_stack():
    """On a mesh both kernels are `shard_map`; a forward that remat
    repeats inside the backward is a forward."""
    loop = "while/body/closed_call/shard_map/pallas_call:"
    assert sr.pass_of("jit(train_step)/jvp()/" + loop) == "fwd"
    assert sr.pass_of("jit(train_step)/transpose(jvp())/" + loop) == "bwd"
    assert sr.pass_of("jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
                      "rematted_computation/shard_map/pallas_call:") == "fwd"
    assert sr.pass_of("jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
                      "shard_map/pallas_call:") == "bwd"
    assert sr.pass_of("") == "fwd"
    markers = [(tr.WINDOW_BEGIN, 0.0, 0.0), (tr.WINDOW_END, 10.0, 10.0)]
    mesh = [_ev("mosaic:shard_map.3", "jit(f)/jvp()/" + loop, 0, 1),
            _ev("mosaic:shard_map.4", "jit(f)/transpose(jvp())/" + loop, 1, 4),
            _ev("mosaic:shard_map.4", "jit(f)/transpose(jvp())/" + loop, 5, 8),
            _ev("mosaic:_unknown_.1", "", 8, 9),      # not a flash kernel
            _ev("fusion.1", "jit(f)/jvp()/attn/mul:", 9, 10)]
    r = sr.reduce([mesh], markers)
    assert r["flash_s"] == {"fwd": 1.0, "bwd": 6.0}
    assert r["flash_calls"] == {"fwd": 1.0, "bwd": 2.0}
    assert r["scope_s"] == {"attn": 8.0, sr.UNSCOPED: 1.0}


def test_idle_inside_the_trainers_spans(cut, old):
    devices, host, r = cut
    # the boundary's gap: the device's last operation of the step ends at
    # 135.174 ms, the next step's first (of 400 us) starts at 148.363
    assert old["longest_gap_s"] == pytest.approx(13.18849e-3, abs=1e-8)
    # spans that lie wholly inside that gap are idle from end to end: the
    # two `report.publish` (27.0 + 7.0 us) ...
    assert r["span_count"]["dtpu.trainer.report.publish"] == 2
    assert r["span_idle_s"]["dtpu.trainer.report.publish"] == \
        pytest.approx(33.96e-6, abs=1e-8)
    # ... while `report` (clipped to the cut: 0 to 146.4404 ms, and the
    # empty one after it, 6.7 us) is idle for the part of the gap before
    # its end, 146.4404 - 135.1743 ms, and for what the cut's dropped
    # short operations leave open earlier: span less busy inside it
    assert r["span_count"]["dtpu.trainer.report"] == 2
    spans = [(a, b) for n, a, b in host if n == "dtpu.trainer.report"]
    work = tr.union([(a, b) for op, _s, a, b in devices[0]
                     if not tr.CONTROL_FLOW.match(op)])
    want = tr.total(tr.subtract(tr.union(spans), work))
    assert r["span_idle_s"]["dtpu.trainer.report"] == \
        pytest.approx(want, abs=1e-9)
    assert want > 11.266e-3
    # every step's two phases are there (eight steps dispatched in the
    # cut's last 13 ms), and a span the window's edge cuts is not counted
    assert r["span_count"]["dtpu.trainer.data_wait"] == 8
    assert r["span_count"]["dtpu.trainer.h2d_put"] == 8
    lo = min(a for _n, a, _b in host if _n == tr.WINDOW_BEGIN)
    cut_by_edge = host + [("dtpu.trainer.checkpoint", lo - 1e-3, lo + 1e-3)]
    assert "dtpu.trainer.checkpoint" not in sr.reduce(
        devices, cut_by_edge, 1)["span_count"]


def _ev(op, stack, a, b):
    return (op, stack, float(a), float(b))


def test_two_devices_collectives_and_a_program_without_scopes():
    markers = [(tr.WINDOW_BEGIN, 0.0, 0.0), (tr.WINDOW_END, 10.0, 10.0)]
    dev0 = [_ev("fusion.1", "jit(f)/jvp(attn)/mul:", 0, 2),
            _ev("all-gather-start.1", "jit(f)/jvp(mlp)/all_gather:", 2, 3),
            _ev("fusion.2", "jit(f)/transpose(jvp(mlp))/dot:", 2, 6),
            _ev("while.3", "jit(f)/while:", 0, 10)]
    dev1 = [_ev("fusion.1", "jit(f)/jvp(attn)/mul:", 0, 4),
            _ev("all-reduce.7", "", 4, 9),
            _ev("copy.9", "", 9, 12)]              # clipped at the window
    host = markers + [("dtpu.trainer.report", 5.0, 9.5)]
    r = sr.reduce([dev0, dev1], host)
    assert r["devices"] == 2 and r["window_s"] == 10.0
    # per device, then the mean; collectives in no scope, `while` left out
    assert r["scope_s"] == {"attn": 3.0, "mlp": 2.0, sr.UNSCOPED: 0.5}
    # idle inside the span: device 0 is idle from 6 on (3.5 s of the
    # span), device 1 not at all (the collective counts as busy)
    assert r["span_idle_s"] == {"dtpu.trainer.report": 1.75}
    assert sr.reduce([dev0, dev1], host, n_devices=1)["scope_s"] == \
        {"attn": 2.0, "mlp": 4.0}
    bare = [[_ev(op, "", a, b) for op, _s, a, b in dev0]
            + [_ev("mosaic:jvp__.1", "jit(f)/jvp()/pallas_call:", 6, 7)]]
    assert sr.reduce(bare, markers)["scoped"] is False  # a kernel is no scope
    assert sr.reduce([], host) is None          # no device plane at all


# -- the readers ------------------------------------------------------------
def _run(old, traced=True):
    return types.SimpleNamespace(
        config=load_json("benchmark", "configs", "gpt2-small.json"),
        traffic=load_json("benchmark", "traffic", "train-1k.json"),
        chips=1, records={"kind": "train"},
        peaks=load_json("benchmark", "peaks.json")["TPU v5 lite"],
        trace=old if traced else None)


def _read(metric, run):
    from benchmark.run import reader

    return reader("layer_metrics", metric).read(run)


def test_the_eight_readers_on_the_cut(cut, old, monkeypatch):
    monkeypatch.setattr(sr, "newest_xplane", lambda: CUT)
    sr._reduce_file.cache_clear()
    run = _run(old)
    shares = {s: _read(s + "_time_share", run) for s in
              ("attn", "mlp", "head_loss", "optimizer", "unscoped")}
    assert shares["attn"] == pytest.approx(100 * 29.50538789e-3 / 0.16)
    assert shares["head_loss"] == pytest.approx(100 * 25.652361328e-3 / 0.16)
    idle = 100.0 * (1.0 - old["busy_s"] / old["window_s"])
    embed = 100 * 0.782740078e-3 / 0.16
    assert sum(shares.values()) + embed + idle == pytest.approx(100.0)
    # 16 rows x 12 heads, 1024 x 1024 x 64, causal: 25.77 GFLOP a forward
    # (0.1308 ms at 197 TFLOP/s), twice that a backward
    fwd_least = 0.5 * 4 * 16 * 12 * 1024 * 1024 * 64 / 197e12
    assert _read("flash_fwd_roofline", run) == pytest.approx(
        100 * 17 * fwd_least / 13.137187968e-3, rel=1e-6)
    assert _read("flash_bwd_roofline", run) == pytest.approx(
        100 * 12 * 2 * fwd_least / 16.368199922e-3, rel=1e-6)
    assert _read("report_idle_ms", run) == pytest.approx(
        1e3 * cut[2]["span_idle_s"]["dtpu.trainer.report"] / 2)
    # PR 22's two flash readers find the kernels as before, and the
    # new pair, weighted by its seconds, is `flash_roofline`
    assert _read("flash_time_share", run) == pytest.approx(
        100 * (13.137187968e-3 + 16.368199922e-3) / 0.16, rel=1e-6)
    assert _read("flash_roofline", run) == pytest.approx(
        100 * (17 + 12 * 2) * fwd_least
        / (13.137187968e-3 + 16.368199922e-3), rel=1e-6)


def test_readers_with_nothing_to_read_return_none(cut, old, monkeypatch):
    sr._reduce_file.cache_clear()
    eight = ("attn_time_share", "mlp_time_share", "head_loss_time_share",
             "optimizer_time_share", "unscoped_time_share",
             "flash_fwd_roofline", "flash_bwd_roofline", "report_idle_ms")
    # not a traced run
    assert [_read(m, _run(old, traced=False)) for m in eight] == [None] * 8
    # a traced run with no trace file, then one with no device plane (a
    # rehearsal off the chip): nothing of `scope_reduce` to read
    no_kernels = dict(old, ops={})
    monkeypatch.setattr(sr, "newest_xplane", lambda: None)
    assert [_read(m, _run(no_kernels)) for m in eight] == [None] * 8
    monkeypatch.setattr(sr, "for_run", lambda run: None)
    assert [_read(m, _run(no_kernels)) for m in eight] == [None] * 8
    # a program from before the scopes and the spans (the kernels were
    # there, under the names they have kept): no share, no idle, and the
    # two rooflines as on the cut
    devices, host, _r = cut
    bare = sr.reduce(
        [[(op, stack if op.startswith("mosaic:") else "", a, b)
          for op, stack, a, b in devices[0]]],
        [e for e in host if not e[0].startswith("dtpu.")], 1)
    monkeypatch.setattr(sr, "for_run", lambda run: bare)
    got = dict(zip(eight, (_read(m, _run(no_kernels)) for m in eight)))
    fwd_least = 0.5 * 4 * 16 * 12 * 1024 * 1024 * 64 / 197e12
    assert got.pop("flash_fwd_roofline") == pytest.approx(
        100 * 17 * fwd_least / 13.137187968e-3, rel=1e-6)
    assert got.pop("flash_bwd_roofline") == pytest.approx(
        100 * 12 * 2 * fwd_least / 16.368199922e-3, rel=1e-6)
    assert list(got.values()) == [None] * 6
