"""What ISSUE 35 added beside the accepted files: `mla_flops_bytes`
against hand-worked values at GLM-4.7-Flash's published widths,
`mla_scope_reduce` against hand-made events, the six new readers on
them (and `None` where they have nothing to read: a parent from before
the scopes, another family's configuration, an untraced run), and the
driver's table."""
import json
import os
import types

import pytest

from benchmark import lm_scope_reduce
from benchmark import mla_flops_bytes as fb
from benchmark import mla_scope_reduce as mr
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.run import load_json, reader

CONFIG = load_json("benchmark", "configs", "glm-4.7-flash.json")
TRAFFIC = load_json("benchmark", "traffic", "train-8k-ep8share.json")
PEAKS = load_json("benchmark", "peaks.json")["TPU v5 lite"]
MLA = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
EXPERT = 3 * 2048 * 1536


def _config(mtp):
    return dict(CONFIG, num_nextn_predict_layers=mtp)


# -- operations and bytes ---------------------------------------------------
def test_an_mla_mixer_by_hand():
    """W_qa 2048 x 768, W_qb 768 x 20 x 256, W_kva 2048 x (512 + 64),
    W_kvb 512 x 20 x (192 + 256), W_o 5120 x 2048: 21.76 M."""
    assert fb.mla_params(CONFIG) == MLA == 21_757_952
    m = fb.dims(CONFIG)
    assert (m["heads"], m["qk_dim"], m["v_dim"]) == (20, 256, 256)
    assert (m["experts_held"], m["experts_routed"], m["top_k"]) == (8, 64, 4)
    assert m["shared_width"] == m["expert_width"] == 1536


@pytest.mark.parametrize("mtp,blocks,expert_layers", [(0, 5, 4), (1, 6, 5)])
@pytest.mark.parametrize("rows", [0.0, 0.5, 4.0])
def test_matmul_weights_a_token_by_the_rows_routed(mtp, blocks,
                                                   expert_layers, rows):
    c = _config(mtp)
    want = (blocks * MLA + 3 * 2048 * 10240
            + expert_layers * (2048 * 64 + EXPERT + rows * EXPERT)
            + mtp * 4096 * 2048 + (1 + mtp) * 2048 * 19360)
    assert fb.matmul_params_per_token(c, rows) == pytest.approx(want)
    assert fb.train_flops_per_token(c, 8192, rows) == pytest.approx(
        6 * want + 12 * blocks * 20 * 256 * 8192)


def test_the_issues_reckoning():
    """ISSUE 35: 268.5 M matmul weights a token and 4.13 GFLOP without
    the MTP module, 5.14 with it; a step of 8192 tokens 33.8 / 42.1
    TFLOP, 172 / 214 ms at the bf16 peak."""
    assert fb.matmul_params_per_token(_config(0), 0.5) / 1e6 == \
        pytest.approx(268.5, abs=0.1)
    for mtp, gflop, ms in ((0, 4.13, 172), (1, 5.14, 214)):
        per_token = fb.train_flops_per_token(_config(mtp), 8192, 0.5)
        assert per_token / 1e9 == pytest.approx(gflop, abs=0.006)
        assert 1e3 * 8192 * per_token / PEAKS["flops_bf16"] == \
            pytest.approx(ms, abs=0.6)


@pytest.mark.parametrize("mtp,layers", [(0, 4), (1, 5)])
def test_the_grouped_matmuls_step_by_hand(mtp, layers):
    flops, nbytes = fb.experts_step(_config(mtp), 8192, 0.5)
    assert flops == pytest.approx(layers * 4096 * 6 * EXPERT)
    assert nbytes == layers * (
        8 * EXPERT * 2 * 3 + 4096 * (2 * 2048 + 3 * 1536) * 2 * 3)
    # at half a row a token, 512 rows an expert, the products still bind
    # (5.9 ms a step of 4 layers against 4.1 of traffic)
    assert flops / PEAKS["flops_bf16"] > nbytes / PEAKS["hbm_bytes_per_s"]


# -- the reduction ------------------------------------------------------------
def _ev(op, stack, a, b):
    return (op, stack, float(a), float(b))


MARKERS = [(tr.WINDOW_BEGIN, 0.0, 0.0), (tr.WINDOW_END, 10.0, 10.0)]
EVENTS = [
    _ev("fusion.1", "jit(f)/jvp(attn)/mla/dot_general:", 0, 2),
    _ev("mosaic:jvp__.1", "jit(f)/jvp()/pallas_call:", 2, 3),
    _ev("fusion.2", "jit(f)/transpose(jvp(attn))/mla/dot_general:", 3, 4),
    _ev("fusion.3", "jit(f)/jvp(mtp)/attn/mla/mul:", 4, 5),
    _ev("fusion.4", "jit(f)/jvp(mtp)/mlp/moe_experts/mul:", 5, 6),
    _ev("mosaic:ragged-dot-none.2", "", 6, 7),
    _ev("fusion.5", "jit(f)/jvp(head_loss)/mtp/dot_general:", 7, 7.5),
    _ev("fusion.6", "jit(f)/jvp(mlp)/mlap/mul:", 7.5, 8),     # no scope's
    _ev("while.7", "jit(f)/mtp/while:", 0, 10),               # control flow
    _ev("mosaic:transpose_jvp___.1",
        "jit(f)/transpose(jvp())/pallas_call:", 8, 10),
    _ev("fusion.8", "jit(f)/jvp(embed)/mtp/dot_general:", 10, 12),  # outside
]


def test_seconds_by_inner_scope():
    r = mr.reduce([EVENTS], MARKERS, 1, mr.inner_scopes())
    assert r["window_s"] == 10.0
    assert r["inner_s"] == {"mla": 4.0, "mtp": 2.5}
    # two devices: the mean; a scope list is data
    r = mr.reduce([EVENTS, EVENTS[:1]], MARKERS, 2, ("mla",))
    assert r["inner_s"] == {"mla": 3.0}
    assert mr.reduce([], MARKERS, 1, mr.inner_scopes()) is None
    assert mr.inner_scopes() == ("mla", "mtp")


def _run(config=CONFIG, traced=True, rows=0.5):
    """A run of 10 s of trace at 2 s a step: 5 steps traced."""
    return types.SimpleNamespace(
        config=config, traffic=TRAFFIC, chips=1, peaks=PEAKS,
        records={"kind": "train", "steps": 15, "wall_s": 30.0,
                 "tokens_per_step": 8192,
                 "counters": {} if rows is None else {
                     "moe_held_rows_per_token": rows}},
        trace={"window_s": 10.0} if traced else None)


def _read(metric, run):
    return reader("layer_metrics", metric).read(run)


NEW = ("mla_train_mfu", "mla_time_share", "mla_flash_fwd_roofline",
       "mla_flash_bwd_roofline", "mla_moe_experts_roofline",
       "mtp_time_share")


@pytest.fixture
def traced(monkeypatch, tmp_path):
    """The three reductions read `EVENTS` for the newest trace."""
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(sr, "newest_xplane", lambda: str(path))
    monkeypatch.setattr(sr, "load", lambda _path: ([EVENTS], MARKERS))
    for mod in (sr, lm_scope_reduce, mr):
        mod._reduce_file.cache_clear()
    yield
    for mod in (sr, lm_scope_reduce, mr):
        mod._reduce_file.cache_clear()


def test_the_six_readers_on_the_events(traced):
    run = _run()
    assert _read("mla_time_share", run) == pytest.approx(40.0)
    assert _read("mtp_time_share", run) == pytest.approx(25.0)
    per_token = fb.train_flops_per_token(CONFIG, 8192, 0.5)
    assert _read("mla_train_mfu", run) == pytest.approx(
        100 * per_token * (15 * 8192 / 30.0) / 197e12)
    # 20 heads, 8192 x 8192 x 256, causal: 0.5 x 4 x 20 x 8192^2 x 256;
    # 6 attention layers (the MTP block's too) x 5 steps, over the 1 s
    # and the 2 s the forward and backward kernels took
    fwd = 0.5 * 4 * 20 * 8192 * 8192 * 256 / 197e12
    assert _read("mla_flash_fwd_roofline", run) == pytest.approx(
        100 * fwd * 6 * 5 / 1.0)
    assert _read("mla_flash_bwd_roofline", run) == pytest.approx(
        100 * 2 * fwd * 6 * 5 / 2.0)
    # the grouped matmuls: `moe_experts` by scope (1 s) and by the
    # kernel's own name (1 s)
    flops, nbytes = fb.experts_step(CONFIG, 8192, 0.5)
    assert _read("mla_moe_experts_roofline", run) == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) * 5 / 2.0)
    # without the module: one block and one expert layer fewer, no share
    run = _run(_config(0))
    assert _read("mtp_time_share", run) is None
    assert _read("mla_flash_fwd_roofline", run) == pytest.approx(
        100 * fwd * 5 * 5 / 1.0)


def test_readers_with_nothing_to_read_return_none(monkeypatch, traced):
    # not a traced run: only the host clock's metric is left
    run = _run(traced=False)
    assert [m for m in NEW if _read(m, run) is not None] == ["mla_train_mfu"]
    # another family's configuration: nothing is counted by keys it does
    # not have (a scope's share is the trace's, whatever the file says)
    qwen = load_json("benchmark", "configs", "qwen3-next-80b-a3b.json")
    assert [m for m in NEW if _read(m, _run(qwen)) is not None] == [
        "mla_time_share"]
    # a program that reports no counter
    assert _read("mla_train_mfu", _run(rows=None)) is None
    assert _read("mla_moe_experts_roofline", _run(rows=None)) is None
    # a parent from before the scopes: the kernels keep their names
    bare = [[(op, stack if op.startswith("mosaic:") else "", a, b)
             for op, stack, a, b in EVENTS]]
    monkeypatch.setattr(sr, "load", lambda _path: (bare, MARKERS))
    for mod in (sr, lm_scope_reduce, mr):
        mod._reduce_file.cache_clear()
    got = {m: _read(m, _run()) for m in NEW}
    assert got["mla_time_share"] is None and got["mtp_time_share"] is None
    assert got["mla_flash_fwd_roofline"] is not None
    # no trace file at all
    monkeypatch.setattr(sr, "newest_xplane", lambda: None)
    assert [_read(m, _run()) for m in NEW[1:]] == [None] * 5


# -- the driver's table -------------------------------------------------------
def test_every_listed_model_type_has_its_reference_and_counters():
    import importlib

    from benchmark.drivers import train_lm, train_lm_models

    with open(os.path.join(os.path.dirname(train_lm.__file__),
                           "lm_models.json")) as f:
        listed = json.load(f)["models"]
    assert train_lm_models.models()["qwen3_next"] == train_lm.MODELS[
        "qwen3_next"]
    for model_type, (_name, module) in train_lm_models.models().items():
        assert model_type in listed
        ref = importlib.import_module(module)
        for name in ("loss_and_gradient", "gradient_gaps", "check"):
            assert callable(getattr(ref, name)), (module, name)
    assert train_lm_models.COUNTERS[:2] == train_lm.COUNTERS
    before = (train_lm.MODELS, train_lm.COUNTERS)
    with train_lm_models.as_train_lm():
        name, kw, _ = train_lm.model_of(CONFIG)
        hparams = train_lm.trial_hparams(CONFIG, TRAFFIC, 1)
    assert name == "glm4-moe-lite" and "source" not in kw
    assert hparams["lr_warmup_steps"] == 2000 and hparams["lr"] == 0.001
    assert (train_lm.MODELS, train_lm.COUNTERS) == before
    # the Qwen cell's traffic gives its trial the same warm-up, unswitched
    qwen = train_lm.trial_hparams(
        load_json("benchmark", "configs", "qwen3-next-80b-a3b.json"),
        load_json("benchmark", "traffic", "train-8k-ep16share.json"), 1)
    assert qwen["lr_warmup_steps"] == 2000 and qwen["lr"] == 0.001


@pytest.mark.parametrize("config,traffic,warmup", [
    ("glm-4.7-flash", "train-8k-ep8share", 2000),
    ("qwen3-next-80b-a3b", "train-8k-ep16share", 2000),
    ("qwen3-next-80b-a3b", None, 0)])
def test_trial_hparams_read_the_warmup_from_the_traffic_file(config, traffic,
                                                            warmup):
    """`train_lm.trial_hparams` passes the traffic file's
    `lr_warmup_steps` itself, 0 where the file has none (the optimizer
    without a schedule), and GLM's trial gets the dictionary that
    `train_lm_models` built for it before: the rate, the ramp and the
    sizes."""
    from benchmark.drivers import train_lm, train_lm_models

    c = load_json("benchmark", "configs", f"{config}.json")
    mix = load_json("benchmark", "traffic",
                    f"{traffic or 'train-8k-ep16share'}.json")
    if traffic is None:
        del mix["lr_warmup_steps"]
    with train_lm_models.as_train_lm():
        name, kw, _ = train_lm.model_of(c)
        hparams = train_lm.trial_hparams(c, mix, 1)
    assert hparams == {
        "model": name, "model_kw": kw, "seq_len": 8192,
        "vocab_size": c["vocab_size"], "batch_size": 1, "lr": 0.001,
        "lr_warmup_steps": warmup}
