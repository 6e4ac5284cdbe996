"""The names a profiler capture shows (ISSUE 24): `STEP_SCOPES` on the
train step, the Pallas kernels' names (`name=` on the paged kernel; the
flash kernels keep the names jax gives them, which
`benchmark/kernels/flash_*.json` match: `tests/test_tpu_compile.py`
holds those), the trainer's phases as `dtpu.trainer.*` host spans
through `Timeline.phase`, and the benchmark's own copy of those names
(`benchmark/scopes.json`, `benchmark/kernels/dtpu_paged_attn.json`),
which it keeps as data because it imports nothing of the program."""
import dataclasses
import functools
import gc
import glob
import importlib
import json
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import scope_reduce
from determined_tpu import core
from determined_tpu.common import profiling
from determined_tpu.models import gpt as gpt_mod
from determined_tpu.models.base import STEP_SCOPES
from determined_tpu.trainer import Batch, JAXTrial, Trainer
from determined_tpu.trainer import _timeline
from determined_tpu.trainer._timeline import Timeline

# `determined_tpu.ops.flash_attention` / `.paged_attention` name the
# functions: the modules come through importlib (as in
# `benchmark/tools/size_cells.py`).
fa = importlib.import_module("determined_tpu.ops.flash_attention")
paged = importlib.import_module("determined_tpu.ops.paged_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _GPTTrial(JAXTrial):
    def __init__(self, **config):
        super().__init__()
        self._config = config

    def build_model(self, mesh):
        return gpt_mod.GPT(
            dataclasses.replace(gpt_mod.tiny(), **self._config), mesh=mesh)

    def build_optimizer(self):
        return optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))

    def build_training_data(self):
        rng = np.random.default_rng(0)
        while True:
            yield {"tokens": rng.integers(0, 256, (8, 128)).astype(np.int32)}


def _dummy_core(tmp_path):
    return core._context._dummy_init(checkpoint_storage=str(tmp_path))


# -- scopes on the train step -----------------------------------------------
@pytest.mark.parametrize("config", [
    {"layer_loop": "scan", "remat": True},
    {"layer_loop": "unroll", "remat": True},
    {"layer_loop": "scan", "fused_loss": True},
], ids=["scan", "unroll", "fused-loss"])
def test_lowered_train_step_holds_every_scope(tmp_path, config):
    """Forward, backward (`transpose(jvp(attn))`) and recomputed work
    (`checkpoint/rematted_computation/mlp`) all carry their scope, and
    the benchmark's peeling (`scope_reduce.scope_of`) finds each."""
    trainer = Trainer(_GPTTrial(**config), _dummy_core(tmp_path))
    step_fn = trainer._build_step_fn()
    batch = trainer._put_batch(next(trainer.trial.build_training_data()))
    text = step_fn.lower(
        trainer.state, batch, np.float32(1.0), trainer._zero_skips()
    ).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    found = {scope_reduce.scope_of(n, STEP_SCOPES) for n in names}
    assert found >= set(STEP_SCOPES), sorted(found)
    # backward and recomputed work are under their scope too (a scanned
    # body's locations start at the body: the wrappers are on the `while`)
    assert any("transpose(jvp(" in n
               and scope_reduce.scope_of(n, STEP_SCOPES)
               == ("attn" if config["layer_loop"] == "unroll" else "head_loss")
               for n in names)
    if config.get("remat"):
        assert any("rematted_computation" in n
                   and scope_reduce.scope_of(n, STEP_SCOPES) == "mlp"
                   for n in names)


# -- the serving programs run the same block --------------------------------
def _serving_program(which):
    """(fn, args) of one of the four programs the serving engine
    compiles, at `gpt.tiny` size, as shapes."""
    import types

    from determined_tpu.serving.engine import GenerationEngine

    model = gpt_mod.GPT(gpt_mod.tiny())
    c = model.config
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    b, s, sp, pages, page, per, q = 2, 32, 16, 9, 8, 4, 3
    grid = sds((b, s), jnp.int32)
    pool = sds((c.n_layers, pages, page, c.n_heads, c.head_dim), c.dtype)
    slot = sds((b,), jnp.int32)
    step = dict(q_pad=1, kernel="gather")
    tail = (sds((b,), bool), pool, pool, sds((b, per), jnp.int32),
            sds((b,), jnp.float32), sds((2,), jnp.uint32))
    me = types.SimpleNamespace(model=model)
    if which == "prefill":
        return model.prefill_kv, (params, grid, grid, grid)
    if which == "cached_prefill":
        prefix = sds((c.n_layers, b, sp, c.n_heads, c.head_dim), c.dtype)
        return model.prefill_kv_cached, (
            params, grid, grid, grid, prefix, prefix, sds((b, sp), jnp.int32))
    if which == "decode":
        return (functools.partial(GenerationEngine._decode_step, me, **step),
                (params, slot, slot, *tail))
    return (functools.partial(GenerationEngine._spec_decode_step, me, **step),
            (params, sds((b, q), jnp.int32), slot, slot, *tail))


@pytest.mark.parametrize(
    "which", ["prefill", "cached_prefill", "decode", "spec_decode"])
def test_serving_programs_run_the_training_block(which):
    """Each serving program's projections and MLP sit under the `attn`
    and `mlp` scopes, which only `GPT._attn_half` / `_mlp_half` open: the
    serving entry points run the block training runs, not a copy."""
    fn, args = _serving_program(which)
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    under = lambda scope, op: any(  # noqa: E731
        op in n and scope_reduce.scope_of(n, STEP_SCOPES) == scope
        for n in names)
    assert under("attn", "bsd,dthk->bsthk") and under("attn", "bshk,hkd->bsd")
    assert under("mlp", "bsd,df->bsf") and under("mlp", "bsf,fd->bsd")
    assert under("embed", "") and under("head_loss", "bsd,dv->bsv")


def test_the_block_is_written_once():
    """By source (`tests/test_flash_block_discipline.py`'s manner): one
    LayerNorm -> QKV -> attention -> out-projection -> residual sequence
    in `models/gpt.py`; a second `ln1` or projection einsum is a copy of
    `_attn_half`."""
    with open(gpt_mod.__file__) as f:
        src = f.read()
    for once in ('"bsd,dthk->bsthk"', '"bshk,hkd->bsd"',
                 '_layernorm(x, blk["ln1_scale"]',
                 '_layernorm(x, blk["ln2_scale"]'):
        assert src.count(once) == 1, once


def test_scope_is_a_whole_component_of_the_name_stack():
    of = lambda n: scope_reduce.scope_of(n, STEP_SCOPES)  # noqa: E731
    assert of("jit(train_step)/jvp(attn)/bsd,dthk->bsthk/dot_general") == "attn"
    assert of("jit(train_step)/transpose(jvp())/while/body/closed_call/attn/"
              "bshk,hkd->bsd/dot_general") == "attn"
    # the flash kernels sit under no scope (`GPT._attn_half`)
    assert of("jit(train_step)/transpose(jvp())/while/body/closed_call/"
              "shard_map/pallas_call") is None
    assert of("jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
              "rematted_computation/mlp/tanh") == "mlp"
    assert of("jit(train_step)/jvp(mlp)/_moe_mlp/dot_general") == "mlp"
    assert of("jit(train_step)/jvp(_moe_mlp)/dot_general") is None
    assert of("jit(train_step)/optimizer/mul") == "optimizer"
    assert of("jit(train_step)/my_optimizer/mul") is None
    assert of("jit(train_step)/jvp(head_loss)/reduce_max") == "head_loss"
    assert of("") is None


# -- names on the Pallas kernels --------------------------------------------
def _pallas_names(fn, *args):
    """The `name` of every `pallas_call` in fn's jaxpr (sub-jaxprs too)."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def test_paged_attention_pallas_call_is_named():
    pages, page, heads, d, b = 8, 16, 2, 128, 2
    pool = jnp.zeros((pages, page, heads, d), jnp.float32)
    got = _pallas_names(
        lambda q, k, v, pt, ln, act: paged.paged_attention(
            q, k, v, pt, ln, act, interpret=True),
        jnp.zeros((b, 8, heads, d), jnp.float32), pool, pool,
        jnp.zeros((b, 4), jnp.int32), jnp.ones((b,), jnp.int32),
        jnp.ones((b,), bool))
    assert got == [paged.PAGED_ATTN]


def test_seven_pallas_call_sites_and_which_pass_a_name():
    """By source: the paged kernel's `pallas_call(` passes `name=`, the
    six of the flash kernels pass none (a new kernel fails here until it
    is put on one side)."""
    named = {}
    for mod in (fa, paged):
        with open(mod.__file__) as f:
            src = f.read()
        calls = [m.start() for m in re.finditer(r"pl\.pallas_call\(", src)]
        ends = calls[1:] + [len(src)]
        named[mod] = [bool(re.search(r"\bname=[A-Z_]+,", src[at:end]))
                      for at, end in zip(calls, ends)]
    assert named == {fa: [False] * 6, paged: [True]}


# -- the benchmark's copy of the names --------------------------------------
def test_benchmark_data_names_the_same_scopes_kernels_and_spans():
    with open(os.path.join(ROOT, "benchmark", "scopes.json")) as f:
        data = json.load(f)
    assert tuple(data["scopes"]) == STEP_SCOPES
    assert data["span_prefix"] == _timeline.SPAN_PREFIX
    assert set(data["spans"]) == set(_timeline.PHASES) | {
        "report.sync", "report.publish"}
    assert data["flash"]["scope"] in STEP_SCOPES
    for kernel in data["flash"]["kernels"]:
        assert os.path.exists(
            os.path.join(ROOT, "benchmark", "kernels", kernel + ".json"))
    files = glob.glob(os.path.join(ROOT, "benchmark", "kernels", "dtpu_*.json"))
    name = paged.PAGED_ATTN
    assert [os.path.basename(p) for p in files] == [name + ".json"]
    with open(files[0]) as f:
        rx = re.compile(json.load(f)["pattern"])
    # as `trace_reduce.op_name` prints a kernel: `mosaic:<name>[.<n>]`
    assert rx.search("mosaic:" + name) and rx.search(f"mosaic:{name}.12")
    assert not rx.search("mosaic:_unknown_.3")


# -- Timeline.phase ---------------------------------------------------------
class _Clock:
    """A clock that advances only when told to."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _timeline_with_clock(enabled=True):
    tl = Timeline(enabled=enabled)
    tl.pc = clock = _Clock()
    tl.reset_window()
    return tl, clock


def test_phase_accumulates_as_the_old_arithmetic_did():
    tl, clock = _timeline_with_clock()
    for _step in range(3):
        # the hot loop's two phases, then the jitted step's dispatch
        with tl.phase("data_wait"):
            clock.now += 0.25
        with tl.phase("h2d_put"):
            clock.now += 0.125
        clock.now += 1.0
    with tl.phase("report", timed=False):       # flush_report
        with tl.phase("report.sync", timed=False):
            clock.now += 2.0                    # device time: the residual
        with tl.phase("report.publish"):
            clock.now += 0.5
    assert tl.window == {"data_wait": 0.75, "h2d_put": 0.375,
                         "report": 0.5, "checkpoint": 0.0}
    out = tl.close_window()
    assert out["window_s"] == pytest.approx(6.625)
    assert out["report_frac"] == pytest.approx(0.5 / 6.625)
    assert out["step_frac"] == pytest.approx(5.0 / 6.625)
    with tl.phase("checkpoint"):
        clock.now += 4.0
    assert tl.window["checkpoint"] == 4.0


def test_phase_restores_the_samplers_tag_and_nests():
    tl, _clock = _timeline_with_clock()
    tag = lambda: profiling._thread_phase.get(  # noqa: E731
        threading.get_ident())
    profiling.set_phase("step")
    try:
        with tl.phase("data_wait"):
            assert tag() == "data_wait"
        assert tag() == "step"
        with tl.phase("report", timed=False):
            assert tag() == "report"
            with tl.phase("report.sync", timed=False):
                assert tag() == "report"    # children keep the phase's tag
            assert tag() == "report"
            with pytest.raises(RuntimeError):
                with tl.phase("report.publish"):
                    raise RuntimeError("a failed report")
            assert tag() == "report"
        assert tag() == "step"
    finally:
        profiling.set_phase(None)
    assert tag() is None


def test_phase_accumulates_nothing_when_disabled(monkeypatch):
    monkeypatch.setenv("DTPU_TIMELINE", "0")
    tl = Timeline()
    tl.pc = clock = _Clock()
    with tl.phase("data_wait"):
        clock.now += 1.0
    with tl.phase("report.publish"):
        clock.now += 1.0
    assert tl.enabled is False
    assert tl.window == {p: 0.0 for p in _timeline.PHASES}


def _windows(tl, clock, boundaries):
    """Three report windows as `Trainer.fit` runs them: a step's two
    phases and its dispatch, the boundary's end after the dispatch, then
    `flush_report`; with `boundaries` the report boundary's span opens
    before it. Returns the three windows' reports."""
    out = []
    for _window in range(3):
        with tl.phase("data_wait"):
            clock.now += 0.25
        with tl.phase("h2d_put"):
            clock.now += 0.125
        clock.now += 0.5                        # the dispatch
        if tl.boundary is not None:
            tl.end_boundary()
        clock.now += 1.0
        if boundaries:
            tl.begin_boundary()
        with tl.phase("report", timed=False):
            with tl.phase("report.sync", timed=False):
                with tl.boundary_wait():
                    clock.now += 2.0            # the device's last steps
                clock.now += 0.375              # the metrics' fetch
            with tl.phase("report.publish"):
                clock.now += 0.5
            out.append(tl.close_window())
        clock.now += 0.0625                     # the control calls
    return out


def test_boundary_s_is_the_interval_the_window_opened_with():
    tl, clock = _timeline_with_clock()
    plain, plain_clock = _timeline_with_clock()
    got = _windows(tl, clock, boundaries=True)
    want = _windows(plain, plain_clock, boundaries=False)
    # the first window was opened by no boundary; each later one by a
    # boundary whose clock starts when its wait on the device ends: the
    # fetch 0.375, publish 0.5, the control calls, the next step's phases
    # and its dispatch
    assert "boundary_s" not in got[0]
    for report in got[1:]:
        assert report.pop("boundary_s") == pytest.approx(
            0.375 + 0.5 + 0.0625 + 0.25 + 0.125 + 0.5)
    assert got == want                          # the fractions as before
    assert tl.boundary is not None              # open until a dispatch
    tl.end_boundary()
    assert tl.boundary is None
    tl.end_boundary()                           # closing twice is a no-op


class _CountingClock(_Clock):
    def __init__(self):
        super().__init__()
        self.calls = 0

    def __call__(self):
        self.calls += 1
        return self.now


def test_boundary_and_collector_record_nothing_when_disabled(monkeypatch):
    monkeypatch.setenv("DTPU_TIMELINE", "0")
    tl = Timeline()
    tl.pc = clock = _CountingClock()
    callbacks = list(gc.callbacks)
    tl.hook_gc()
    assert gc.callbacks == callbacks
    tl.begin_boundary()
    assert tl.boundary is not None              # the span stays
    with tl.boundary_wait():
        clock.now += 1.0
    tl.end_boundary()
    gc.collect()
    assert clock.calls == 0
    out = tl.close_window()
    assert not {"boundary_s", "gc_s", "gc_collections"} & set(out)


def test_gc_hook_times_collections_until_unhooked():
    tl, clock = _timeline_with_clock()
    callbacks = list(gc.callbacks)
    tl.hook_gc()
    tl.hook_gc()                                # once, however often asked
    try:
        assert len(gc.callbacks) == len(callbacks) + 1
        gc.collect()                            # the fake clock stands still
        for _pause in range(2):                 # two pauses of 3 ms
            tl._on_gc("start", {"generation": 0})
            clock.now += 0.003
            tl._on_gc("stop", {"generation": 0})
        out = tl.close_window()
        again = tl.close_window()               # a new window starts at 0
    finally:
        tl.unhook_gc()
    assert gc.callbacks == callbacks
    assert out["gc_collections"] >= 3.0
    assert out["gc_s"] == pytest.approx(0.006)
    assert again["gc_s"] == 0.0
    assert "gc_s" not in tl.close_window()      # unhooked: not measured


def test_gc_windows_lose_no_collection_under_threads():
    """Collections on many threads while the windows close on this one:
    the windows' counts add up to every collection the hook saw."""
    import sys

    tl = Timeline(enabled=True)
    workers = (os.cpu_count() or 1) + 2
    done = threading.Event()

    def collect():
        for _ in range(20):
            junk = [[i] for i in range(100)]
            junk.append(junk)
            del junk
            gc.collect(0)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    tl.hook_gc()
    try:
        threads = [threading.Thread(target=collect) for _ in range(workers)]
        for t in threads:
            t.start()
        counted = 0.0
        while not done.is_set():
            counted += tl.close_window()["gc_collections"]
            if not any(t.is_alive() for t in threads):
                done.set()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        counted += tl.close_window()["gc_collections"]
    finally:
        tl.unhook_gc()
        sys.setswitchinterval(interval)
    # (a collection asked for while another runs is skipped, not counted)
    assert counted == tl._gc_totals[1] > 0


def test_fit_restores_gc_callbacks_when_it_returns_and_when_it_raises(
        tmp_path):
    class _Failing(_GPTTrial):
        fail_at = None

        def build_training_data(self):
            for i, batch in enumerate(super().build_training_data()):
                if i == self.fail_at:
                    raise RuntimeError("the data source failed")
                yield batch

    trial = _Failing()
    trainer = Trainer(trial, _dummy_core(tmp_path))
    callbacks = list(gc.callbacks)
    trainer.fit(max_length=Batch(2), report_period=Batch(1))
    assert gc.callbacks == callbacks and trainer.timeline.boundary is None
    prof = [m for g, _s, m in trainer.core.train._reported
            if g == "profiling"]
    assert [("boundary_s" in m, "gc_s" in m) for m in prof] == [
        (False, True), (True, True)]
    trial.fail_at = 1
    with pytest.raises(RuntimeError, match="data source"):
        trainer.fit(max_length=Batch(4), report_period=Batch(1))
    assert gc.callbacks == callbacks and trainer.timeline.boundary is None


def test_the_trainer_readers_name_the_programs_span_and_keys():
    """The benchmark imports nothing of the program: its four readers of
    the boundary and the collector carry the names as constants."""
    readers = {m: importlib.import_module("benchmark.layer_metrics." + m)
               for m in ("boundary_idle_ms", "boundary_host_ms",
                         "gc_pause_ms", "unspanned_idle_share")}
    assert readers["boundary_idle_ms"].SPAN == _timeline.BOUNDARY
    assert scope_reduce.names()["span_prefix"] == _timeline.SPAN_PREFIX
    tl, clock = _timeline_with_clock()
    tl.hook_gc()
    try:
        tl.begin_boundary()
        with tl.boundary_wait():
            clock.now += 1.0
        tl.end_boundary()
        out = tl.close_window()
    finally:
        tl.unhook_gc()
    assert readers["boundary_host_ms"].KEY in out
    assert readers["gc_pause_ms"].KEY in out
    assert {r.LAYER for r in readers.values()} == {"trainer"}


# -- the spans in a capture -------------------------------------------------
def test_fit_leaves_the_trainers_spans_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    trainer = Trainer(_GPTTrial(), _dummy_core(tmp_path / "ckpt"))
    trainer.fit(max_length=Batch(1))            # compile outside the capture
    reported = trainer.core.train._reported
    first_report = len(reported)
    trace_dir = str(tmp_path / "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        trainer.fit(max_length=Batch(4), report_period=Batch(1),
                    checkpoint_period=Batch(2))
        # a collection on another thread, under the hook
        trainer.timeline.hook_gc()
        collector = threading.Thread(target=gc.collect)
        collector.start()
        collector.join()
        trainer.timeline.unhook_gc()
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    spans, dispatches = {}, []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                at = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name.startswith(_timeline.SPAN_PREFIX):
                    spans.setdefault(e.name, []).append(at)
                elif e.name.startswith("PjitFunction(train_step"):
                    dispatches.append(at)
    spans = {k: sorted(v) for k, v in spans.items()}
    names = {n[len(_timeline.SPAN_PREFIX):] for n in spans}
    assert names >= {"data_wait", "h2d_put", "report", "report.sync",
                     "report.publish", "checkpoint", "boundary",
                     "boundary.wait", "boundary.control", "boundary.op_end",
                     "gc"}, names
    assert len(spans["dtpu.trainer.data_wait"]) == 3
    assert len(spans["dtpu.trainer.h2d_put"]) == 3

    def inside(child, parent):
        return [any(pa <= a and b <= pb for pa, pb in spans[parent])
                for a, b in spans[child]]

    # the children lie inside a `report` span, every `report` and every
    # part of the boundary inside a `boundary` span
    for child in ("report.sync", "report.publish"):
        assert all(inside("dtpu.trainer." + child, "dtpu.trainer.report"))
    for child in ("report", "boundary.wait", "boundary.control",
                  "boundary.op_end"):
        assert all(inside("dtpu.trainer." + child, "dtpu.trainer.boundary"))
    assert all(inside("dtpu.trainer.boundary.wait", "dtpu.trainer.report.sync"))
    # a boundary at each of the 3 steps; the first two end once the next
    # step's dispatch has returned, before that step's next batch
    boundaries = spans["dtpu.trainer.boundary"]
    assert len(boundaries) == 3
    for a, b in boundaries[:2]:
        dispatch = [d for d in dispatches if a <= d[0] <= b]
        assert dispatch and dispatch[-1][1] <= b
        after = [w for w, _ in spans["dtpu.trainer.data_wait"] if w > a]
        assert after[0] < b and (len(after) == 1 or b < after[1])
    # the same interval on the program's clock: each window's
    # `boundary_s` is its opening boundary's span from the end of its wait
    # on the device, the first window has none
    prof = [m for g, _s, m in reported[first_report:] if g == "profiling"]
    assert len(prof) == 3 and "boundary_s" not in prof[0]
    waits = spans["dtpu.trainer.boundary.wait"]
    assert len(waits) == 3
    for (_a, b), (_w, waited), m in zip(boundaries, waits, prof[1:]):
        span_s = (b - waited) * 1e-9
        assert abs(m["boundary_s"] - span_s) <= 5e-4 + 0.05 * span_s
        assert {"gc_s", "gc_collections"} <= set(m)
    assert trainer.timeline.boundary is None


# -- the inner scopes of the layers only some models have (ISSUE 32) ---------
def test_benchmark_lm_scopes_are_the_programs_inner_scopes():
    """`lm_scopes.json`'s list (ISSUE 32's, which a later PR may not
    edit) and then `mla_scopes.json`'s (ISSUE 35's) are `INNER_SCOPES`."""
    from benchmark import lm_scope_reduce, mla_scope_reduce
    from determined_tpu.models.base import INNER_SCOPES

    assert (lm_scope_reduce.inner_scopes()
            + mla_scope_reduce.inner_scopes()) == INNER_SCOPES
    assert len(set(INNER_SCOPES)) == len(INNER_SCOPES)
    assert not set(INNER_SCOPES) & set(STEP_SCOPES)
    stack = "jit(train_step)/transpose(jvp(attn))/gdn/gdn_scan/while/body/dot"
    assert lm_scope_reduce.scopes_of(stack) == {"gdn", "gdn_scan"}
    assert lm_scope_reduce.scopes_of("jit(f)/jvp(mlp)/moe_routes/x") == set()
    stack = "jit(train_step)/transpose(jvp(mtp))/attn/mla/dot_general"
    assert mla_scope_reduce.scopes_of(
        stack, mla_scope_reduce.inner_scopes()) == {"mtp", "mla"}
    assert lm_scope_reduce.scopes_of(stack) == set()


def _step_names(tmp_path, model, kw):
    """The locations of `model`'s lowered train step on one device (the
    cells' `{data: 1}`: on more, the expert layer runs in a shard_map,
    whose body starts its own locations)."""
    from determined_tpu.exec.builtin_trials import SyntheticTrial
    from determined_tpu.parallel.mesh import MeshConfig, make_mesh

    trial = SyntheticTrial({"model": model, "model_kw": kw,
                            "seq_len": 32, "vocab_size": 96, "batch_size": 8})
    trainer = Trainer(trial, _dummy_core(tmp_path), mesh=make_mesh(
        MeshConfig(data=1), devices=jax.devices()[:1]))
    batch = trainer._put_batch(next(trial.build_training_data()))
    text = trainer._build_step_fn().lower(
        trainer.state, batch, np.float32(1.0), trainer._zero_skips()
    ).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


def _inner_scopes_of(name):
    from benchmark import lm_scope_reduce, mla_scope_reduce

    return lm_scope_reduce.scopes_of(name) | mla_scope_reduce.scopes_of(
        name, mla_scope_reduce.inner_scopes())


def test_lowered_glm4_moe_lite_step_holds_outer_and_inner_scopes(tmp_path):
    """Every block opens `attn` and `mlp`; inside them `mla` and the
    expert layer's three; the MTP module opens `mtp` OUTSIDE the step
    scopes of its parts (`embed`, `attn`, `mlp`, `head_loss`), so each of
    its operations still has its one step scope; forward, backward and
    recomputed alike."""
    kw = dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=16,
        kv_lora_rank=12, qk_nope_head_dim=12, qk_rope_head_dim=4,
        v_head_dim=16, intermediate_size=48, moe_intermediate_size=16,
        n_routed_experts=4, num_experts_routed=16, first_expert=4)
    names = _step_names(tmp_path, "glm4-moe-lite", kw)
    assert {scope_reduce.scope_of(n, STEP_SCOPES) for n in names} >= set(
        STEP_SCOPES)
    outer_of = {"mla": {"attn"}, "moe_route": {"mlp"}, "moe_experts": {"mlp"},
                "moe_shared": {"mlp"},
                "mtp": {"embed", "attn", "mlp", "head_loss"}}
    under = {inner: set() for inner in outer_of}
    for n in names:
        for inner in _inner_scopes_of(n):
            under[inner].add(scope_reduce.scope_of(n, STEP_SCOPES))
    assert under == outer_of
    for inner in ("mla", "mtp"):
        assert any("rematted_computation" in n and inner in _inner_scopes_of(n)
                   for n in names), inner
        assert any("transpose(" in n and inner in _inner_scopes_of(n)
                   for n in names), inner
    # the MTP module's block is under its parts' own scopes too
    assert any({"mtp", "mla"} <= _inner_scopes_of(n) for n in names)
    assert any({"mtp", "moe_experts"} <= _inner_scopes_of(n) for n in names)


def test_lowered_qwen3_next_step_holds_outer_and_inner_scopes(tmp_path):
    """Both mixers open `attn`, the expert layer `mlp`, and inside them
    the inner scopes, forward, backward and recomputed; every inner
    scope lies under the outer one it belongs to."""
    from benchmark import lm_scope_reduce
    from determined_tpu.exec.builtin_trials import SyntheticTrial
    from determined_tpu.models.base import INNER_SCOPES

    kw = dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_key_head_dim=8, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_value_head_dim=8, num_experts=4,
        num_experts_routed=16, num_experts_per_tok=4,
        moe_intermediate_size=16, shared_expert_intermediate_size=16)
    trial = SyntheticTrial({"model": "qwen3-next", "model_kw": kw,
                            "seq_len": 32, "vocab_size": 96, "batch_size": 8})
    trainer = Trainer(trial, _dummy_core(tmp_path))
    batch = trainer._put_batch(next(trial.build_training_data()))
    text = trainer._build_step_fn().lower(
        trainer.state, batch, np.float32(1.0), trainer._zero_skips()
    ).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    assert {scope_reduce.scope_of(n, STEP_SCOPES) for n in names} >= set(
        STEP_SCOPES)
    outer_of = {"gdn": "attn", "gdn_scan": "attn", "gated_attn": "attn",
                "moe_route": "mlp", "moe_experts": "mlp", "moe_shared": "mlp"}
    assert set(outer_of) == set(lm_scope_reduce.inner_scopes())
    seen = set()
    for n in names:
        for inner in lm_scope_reduce.scopes_of(n):
            seen.add(inner)
            # (the body of the mesh's shard_map starts its own locations)
            if n.startswith("jit("):
                assert scope_reduce.scope_of(
                    n, STEP_SCOPES) == outer_of[inner], n
    assert seen == set(outer_of), sorted(seen)
    assert set(outer_of) < set(INNER_SCOPES)
    # the chunked rule repeated in the backward is under its scopes too
    assert any("rematted_computation" in n
               and "gdn_scan" in lm_scope_reduce.scopes_of(n)
               and scope_reduce.scope_of(n, STEP_SCOPES) == "attn"
               for n in names)
